"""Smoke run of the served path on one TPU chip.

    python chip_smoke.py [--seed N]

One process, one chip.  Serves ``phi4-mini-3.8b`` at full width (random
weights from ``--seed``) through ``ServeEngine`` and ``ServeFrontend`` on the
``ideal`` backend, streams concurrent requests with
``server.client.stream_generate``, checks every stream and one cached decode
step against a fresh ``api.prefill`` over the same prefix, then runs each
Pallas kernel compiled (never interpreted) and compares it with its
``kernels/ref.py`` oracle.

Times printed here are one smoke run's, compilation included: they are not a
benchmark.  The last line of standard output is one JSON object naming the
device.  Without a TPU, or when any check fails, the script exits non-zero
and prints no such line.
"""

from __future__ import annotations

import argparse
import asyncio
import json
import os
import sys
import time
import traceback
from typing import Callable, Dict, List, Optional, Sequence

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

ARCH = "phi4-mini-3.8b"
SLOTS = 8
MAX_LEN = 2048
PROMPT_LENS = (128, 512, 1024)
N_REQUESTS = 12
MAX_NEW = 32
#: Cached decode vs fresh prefill, bf16 weights and activations through 32
#: layers: RMS of the logit difference over the RMS of the prefill logits.
#: bf16 rounds at 2**-8 (0.4%) per operation; a path that reads the wrong
#: cache row or position differs by order 1.
LOGIT_RTOL = 5e-2
#: The engine's token must sit within this many logit RMS of the reference
#: argmax: bf16 noise can swap near-ties, while a wrong token from a 200k
#: vocabulary sits about four RMS below the maximum.
ARGMAX_RTOL = 0.25


class SmokeError(RuntimeError):
    """A check of the smoke run failed."""


def require_tpu():
    """The first device, which must be a TPU: no run falls back to the CPU."""
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        raise SystemExit(f"chip_smoke: needs a TPU; JAX found "
                         f"{dev.platform!r} ({dev.device_kind})")
    return dev


def make_prompts(seed: int, vocab: int, lens: Sequence[int],
                 n: int) -> List[List[int]]:
    """``n`` prompts cycling through ``lens``, token ids drawn from ``seed``."""
    rng = np.random.default_rng(seed)
    return [rng.integers(3, vocab, lens[i % len(lens)]).tolist()
            for i in range(n)]


def build_engine(cfg, seed: int, slots: int, max_len: int):
    """Parameters initialised on the device from ``seed``, and the engine."""
    from repro.models import model_api
    from repro.serve import ServeEngine

    api = model_api(cfg)
    params = jax.jit(api.init_params)(jax.random.PRNGKey(seed))
    jax.block_until_ready(params)
    return api, ServeEngine(cfg, params, slots=slots, max_len=max_len)


def warm_up(engine, prompt_lens: Sequence[int], vocab: int, seed: int) -> None:
    """Compile what the serve phase runs: one two-token request per prompt
    length through the engine (prefill per length, slot inject, decode)."""
    from repro.serve import Request

    for i, prompt in enumerate(make_prompts(seed + 1, vocab, prompt_lens,
                                            len(prompt_lens))):
        engine.submit(Request(uid=-1 - i, prompt=prompt, max_new_tokens=2))
    stats = engine.run_until_drained()
    if stats.completed != len(prompt_lens):
        raise SmokeError(f"warm-up completed {stats.completed} of "
                         f"{len(prompt_lens)} requests")


def serve(engine, prompts: Sequence[Sequence[int]], max_new: int,
          timeout_s: float = 600.0):
    """Stream every prompt concurrently through a ``ServeFrontend`` on
    127.0.0.1:0.  Returns the client results; ``close`` re-raises a pump
    failure."""
    from repro.server import ServeFrontend
    from repro.server.client import stream_generate

    async def run():
        frontend = ServeFrontend(engine)
        host, port = await frontend.start("127.0.0.1", 0)
        try:
            return await asyncio.gather(*(
                stream_generate(host, port, p, max_new_tokens=max_new,
                                timeout_s=timeout_s) for p in prompts))
        finally:
            await frontend.drain()
            await frontend.close()

    return asyncio.run(run())


def check_streams(results, max_new: int, vocab: int) -> Dict[str, float]:
    """Every stream completed with its full token count, inside the vocab."""
    for i, r in enumerate(results):
        if not r.ok or r.status != "completed":
            raise SmokeError(f"request {i}: HTTP {r.http_status}, "
                             f"status {r.status!r}")
        if len(r.tokens) != max_new:
            raise SmokeError(f"request {i}: {len(r.tokens)} of {max_new} "
                             f"tokens")
        bad = [t for t in r.tokens if not 0 <= t < vocab]
        if bad:
            raise SmokeError(f"request {i}: tokens outside the vocabulary "
                             f"{bad[:4]}")
    ttfts = [r.summary["ttft_s"] for r in results]
    return {"requests_completed": len(results),
            "tokens_generated": sum(len(r.tokens) for r in results),
            "mean_ttft_s": float(np.mean(ttfts))}


def _rms(x: np.ndarray) -> float:
    return float(np.sqrt(np.mean(np.square(x))))


def check_decode_against_prefill(api, params, prompt: Sequence[int],
                                 engine_tokens: Sequence[int],
                                 max_len: int) -> Dict[str, float]:
    """One cached decode step against a fresh prefill over the same prefix.

    Prefills ``prompt``, decodes the engine's first token from that cache,
    and prefills ``prompt + [that token]`` from scratch: the two last-position
    logits must agree within ``LOGIT_RTOL``.  The engine's first two tokens
    must be near-argmax of the prefill and decode logits."""
    prefill = jax.jit(api.prefill, static_argnames=("max_len",))
    decode = jax.jit(api.decode_step)
    tok0, tok1 = int(engine_tokens[0]), int(engine_tokens[1])
    lp, state = prefill(params, {"tokens": jnp.asarray([list(prompt)],
                                                        jnp.int32)},
                        max_len=max_len)
    ld, _ = decode(params, state, jnp.asarray([[tok0]], jnp.int32))
    lf, _ = prefill(params, {"tokens": jnp.asarray([list(prompt) + [tok0]],
                                                   jnp.int32)},
                    max_len=max_len)
    lp, ld, lf = (np.asarray(x, np.float32)[0] for x in (lp, ld, lf))
    for name, x in (("prefill", lp), ("decode", ld), ("fresh prefill", lf)):
        if not np.isfinite(x).all():
            raise SmokeError(f"{name} logits hold NaN or inf")
    out = {"logit_rel_rms": _rms(ld - lf) / _rms(lf),
           "tok0_gap_rms": float(lp.max() - lp[tok0]) / _rms(lp),
           "tok1_gap_rms": float(ld.max() - ld[tok1]) / _rms(ld)}
    if not out["logit_rel_rms"] <= LOGIT_RTOL:
        raise SmokeError(f"cached decode vs fresh prefill: relative RMS "
                         f"{out['logit_rel_rms']:.3g} > {LOGIT_RTOL}")
    for k in ("tok0_gap_rms", "tok1_gap_rms"):
        if not out[k] <= ARGMAX_RTOL:
            raise SmokeError(f"engine token {k[:4]} is {out[k]:.3g} logit "
                             f"RMS below the argmax (> {ARGMAX_RTOL})")
    return out


def _rel_err(x, ref) -> float:
    x, ref = np.asarray(x, np.float64), np.asarray(ref, np.float64)
    return float(np.linalg.norm(x - ref) / (np.linalg.norm(ref) + 1e-30))


def _systolic(a, b, v_map, v_safe):
    from repro.kernels.systolic_mac import systolic_mac
    return systolic_mac(a, b, v_map, v_safe, interpret=False,
                        count_flags=True)


def _razor(a, b):
    from repro.kernels.razor_matmul import razor_matmul
    return razor_matmul(a, b, interpret=False, count_flags=True)


def _islands(a, b, tiers):
    from repro.kernels.precision_island import precision_island
    return precision_island(a, b, tiers, interpret=False)


def _wkv6(r, k, v, w_log, u, s0):
    from repro.kernels.wkv6 import wkv6
    return wkv6(r, k, v, w_log, u, s0, chunk=SCAN_CHUNK, interpret=False)


def _ssd(x, dt, a_log, b, c, d, s0):
    from repro.kernels.ssd_chunk import ssd_chunk
    return ssd_chunk(x, dt, a_log, b, c, d, s0, chunk=SCAN_CHUNK,
                     interpret=False)


#: The matmul kernels run 1024 x 1024 bf16 with 128 x 128 output tiles.
MM, MM_TILE = 1024, 128
#: The scans run 2048 tokens at the head widths of rwkv6-1.6b (32 x 64) and
#: zamba2-2.7b (80 x 64, state 64), in those configs' 64-token chunks.
SCAN_LEN, SCAN_CHUNK, WKV_HEADS, SSD_HEADS, HEAD_DIM, SSD_STATE = \
    2048, 64, 32, 80, 64, 64
_G = MM // MM_TILE
_BF16, _F32, _I32 = jnp.bfloat16, jnp.float32, jnp.int32
_WKV = ((1, SCAN_LEN, WKV_HEADS, HEAD_DIM), _F32)
_BC = ((1, SCAN_LEN, SSD_STATE), _F32)
#: Each kernel's compiled call and its argument (shape, dtype) list.
KERNELS = {
    "systolic_mac": (_systolic, [((MM, MM), _BF16)] * 2
                     + [((_G, _G), _F32)] * 2),
    "razor_matmul": (_razor, [((MM, MM), _BF16)] * 2),
    "precision_island": (_islands, [((MM, MM), _BF16)] * 2
                         + [((_G, _G), _I32)]),
    "wkv6": (_wkv6, [_WKV] * 4 + [((WKV_HEADS, HEAD_DIM), _F32),
                                 ((1, WKV_HEADS, HEAD_DIM, HEAD_DIM), _F32)]),
    "ssd_chunk": (_ssd, [((1, SCAN_LEN, SSD_HEADS, HEAD_DIM), _F32),
                         ((1, SCAN_LEN, SSD_HEADS), _F32),
                         ((SSD_HEADS,), _F32), _BC, _BC, ((SSD_HEADS,), _F32),
                         ((1, SSD_HEADS, SSD_STATE, HEAD_DIM), _F32)]),
}


def kernel_inputs(seed: int) -> Dict[str, tuple]:
    """Inputs at the ``KERNELS`` shapes, in the ranges the kernel tests use."""
    keys = iter(jax.random.split(jax.random.PRNGKey(seed), 16))

    def normal(shape, dtype=_F32, scale=1.0):
        return (jax.random.normal(next(keys), shape) * scale).astype(dtype)

    a, b = normal((MM, MM), _BF16), normal((MM, MM), _BF16)
    v_map = jax.random.uniform(next(keys), (_G, _G), _F32, 0.6, 1.0)
    tiers = jax.random.randint(next(keys), (_G, _G), 0, 3, _I32)
    wkv = [normal(_WKV[0]) for _ in range(3)]
    w_log = -jnp.exp(normal(_WKV[0], scale=0.5))
    h, n = SSD_HEADS, SSD_STATE
    return {
        "systolic_mac": (a, b, v_map, jnp.full((_G, _G), 0.8, _F32)),
        "razor_matmul": (a, b),
        "precision_island": (a, b, tiers),
        "wkv6": (*wkv, w_log, normal((WKV_HEADS, HEAD_DIM), scale=0.1),
                 normal((1, WKV_HEADS, HEAD_DIM, HEAD_DIM), scale=0.1)),
        "ssd_chunk": (normal((1, SCAN_LEN, h, HEAD_DIM)),
                      jax.nn.softplus(normal((1, SCAN_LEN, h))),
                      normal((h,), scale=0.3), normal(_BC[0]),
                      normal(_BC[0]), normal((h,)),
                      jnp.zeros((1, h, n, HEAD_DIM), _F32)),
    }


def _compiled(fn: Callable, *args):
    """Compile ``fn`` for the device, require a Mosaic kernel in it, run."""
    exe = jax.jit(fn).lower(*args).compile()
    if "tpu_custom_call" not in exe.as_text():
        raise SmokeError(f"{fn.__name__}: no compiled Pallas kernel")
    return jax.block_until_ready(exe(*args))


def kernel_checks(seed: int) -> Dict[str, Dict[str, float]]:
    """Each Pallas kernel once, compiled, at the ``KERNELS`` widths, against
    its ``kernels/ref.py`` oracle (run at full f32 matmul precision).

    Tolerances are relative L2 errors: 1e-3 where both sides compute the
    same f32 sums, the interpret-mode tests' bounds for the quantized tiles
    (round-to-nearest ties may break differently), and 1e-2 for the chunked
    scans against the per-token recurrences.  Flag maps and fused flag
    counts must match exactly."""
    from repro.kernels import ref

    args = kernel_inputs(seed)
    got = {name: jax.tree.leaves(_compiled(fn, *args[name]))
           for name, (fn, _) in KERNELS.items()}
    with jax.default_matmul_precision("highest"):
        want = {
            "systolic_mac": ref.systolic_mac(*args["systolic_mac"],
                                             block=MM_TILE),
            "razor_matmul": ref.razor_matmul(*args["razor_matmul"],
                                             block=MM_TILE),
            "precision_island": [ref.precision_island(
                *args["precision_island"], block=MM_TILE)],
            "wkv6": ref.wkv6(*args["wkv6"]),
            "ssd_chunk": ref.ssd(*args["ssd_chunk"]),
        }
    tols = {"systolic_mac": 1e-3, "razor_matmul": 1e-2,
            "precision_island": 4e-2, "wkv6": 1e-2, "ssd_chunk": 1e-2}
    out: Dict[str, Dict[str, float]] = {}
    failed: List[str] = []
    for name, tol in tols.items():
        g, w = got[name], want[name]
        # matmuls: [C, flags[, rel], count] against [C, flags[, rel]];
        # scans: [y, state] against [y, state]
        err = _rel_err(g[0], w[0])
        if name in ("wkv6", "ssd_chunk"):
            err = max(err, _rel_err(g[1], w[1]))
        out[name] = {"rel_err": err, "tol": tol}
        if not err <= tol:
            failed.append(f"{name}: relative error {err:.3g} > {tol}")
        if name in ("systolic_mac", "razor_matmul"):
            flags, count = g[1], g[-1]
            out[name]["flags"] = int(count)
            if not (np.array_equal(flags, w[1])
                    and int(count) == int(np.sum(w[1]))):
                failed.append(f"{name}: flags or fused count differ")
        print(f"kernel {name}: " + ", ".join(
            f"{k} {v:.3e}" if isinstance(v, float) else f"{k} {v}"
            for k, v in out[name].items()))
    if failed:
        raise SmokeError("; ".join(failed))
    return out


def main(argv: Optional[Sequence[str]] = None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    dev = require_tpu()

    from repro.configs import get_config
    from repro.launch.compile_cache import enable_compile_cache

    print(f"chip_smoke: one smoke run, not a benchmark; compile cache "
          f"{enable_compile_cache()}")
    print(f"device: {dev.platform} {dev.device_kind} x{len(jax.devices())}")
    cfg = get_config(ARCH)
    prompts = make_prompts(args.seed, cfg.vocab_size, PROMPT_LENS, N_REQUESTS)
    times: Dict[str, float] = {}
    failures: List[str] = []
    ctx: Dict[str, object] = {}

    def phase(name: str, fn: Callable[[], object]) -> None:
        t0 = time.perf_counter()
        try:
            ctx[name] = fn()
        except Exception as e:      # record, and go on to the next phase
            traceback.print_exc()
            failures.append(f"{name}: {e!r}")
        times[name] = time.perf_counter() - t0
        mem = dev.memory_stats() or {}
        print(f"phase {name}: {times[name]:.2f} s"
              f"{'' if name in ctx else ' FAILED'}; bytes_in_use "
              f"{mem.get('bytes_in_use')}, peak_bytes_in_use "
              f"{mem.get('peak_bytes_in_use')}", flush=True)

    print(f"serving {ARCH} at full width: {cfg.n_layers} layers, d_model "
          f"{cfg.d_model}, vocab {cfg.vocab_size}; slots {SLOTS}, max_len "
          f"{MAX_LEN}, backend ideal")
    phase("init", lambda: build_engine(cfg, args.seed, SLOTS, MAX_LEN))
    if "init" in ctx:
        api, engine = ctx["init"]
        phase("compile", lambda: warm_up(engine, PROMPT_LENS, cfg.vocab_size,
                                         args.seed))
    if "compile" in ctx:
        phase("serve", lambda: serve(engine, prompts, MAX_NEW))
    if "serve" in ctx:
        phase("check", lambda: {
            **check_streams(ctx["serve"], MAX_NEW, cfg.vocab_size),
            **check_decode_against_prefill(api, engine.params, prompts[0],
                                           ctx["serve"][0].tokens, MAX_LEN)})
    if "check" in ctx:
        res = ctx["check"]
        print(f"served {res['requests_completed']} requests, "
              f"{res['tokens_generated']} tokens, mean TTFT "
              f"{res['mean_ttft_s']:.4f} s (prompt lengths {PROMPT_LENS}, "
              f"{MAX_NEW} new tokens each)")
        print(f"cached decode vs fresh prefill: logit relative RMS "
              f"{res['logit_rel_rms']:.3e} (tol {LOGIT_RTOL}); engine tokens "
              f"{res['tok0_gap_rms']:.3e} and {res['tok1_gap_rms']:.3e} logit "
              f"RMS below argmax (tol {ARGMAX_RTOL})")
    phase("kernels", lambda: kernel_checks(args.seed))
    mem = dev.memory_stats() or {}
    print(f"peak_bytes_in_use: {mem.get('peak_bytes_in_use')} of "
          f"bytes_limit {mem.get('bytes_limit')}")
    print("phase times (s): " + ", ".join(f"{k} {v:.2f}"
                                          for k, v in times.items()))
    if failures:
        raise SystemExit("chip_smoke FAILED: " + "; ".join(failures))
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))


if __name__ == "__main__":
    main()
