"""The profiler-trace reduction: on a hand-built trace, where every number
can be worked out by hand, and on a small trace recorded here on the CPU."""

import pytest

from bench import trace as tr


def _trace():
    """One device: ops busy over [0, 20] and [30, 40] ns of a [0, 50]
    window, a decode program over [0, 20], a prefill over [30, 40]; host
    spans: decode_step over [18, 35], prefill over [36, 60]."""
    ops = [("fusion.1", 0, 10, {}), ("fusion.2", 5, 20, {}),
           ("%while.3 = (s32[]) while(x)", 0, 20, {}),
           ("convolution", 30, 40, {}), ("outside", 60, 70, {})]
    mods = [("jit_decode_step(3)", 0, 20, {}),
            ("jit_prefill(4)", 30, 40, {})]
    host = [(tr.WINDOW, 0, 50, {}), ("decode_step", 18, 35, {}),
            ("prefill", 36, 60, {})]
    return {"devices": [{"ops": ops, "modules": mods}], "host": host}


def test_merge_and_clip():
    assert tr.merge([(5, 7), (0, 3), (2, 4), (7, 9), (10, 10)]) == [(0, 4),
                                                                  (5, 9)]
    assert tr.clip([(0, 4), (5, 9)], 3, 6) == [(3, 4), (5, 6)]
    assert tr.idle_gaps([(1, 2), (4, 5)], 0, 6) == [(0, 1), (2, 4), (5, 6)]


def test_busy_union_and_window():
    red = tr.reduce(_trace())
    assert red["window_s"] == pytest.approx(50e-9)
    assert red["busy_s"] == pytest.approx(30e-9)          # 0-20 and 30-40


def test_device_time_per_program_by_module_name():
    red = tr.reduce(_trace())
    assert red["programs"]["jit_decode_step"] == {"device_s": 20e-9,
                                                  "count": 1}
    assert red["programs"]["jit_prefill(4)"]["device_s"] == pytest.approx(
        10e-9)
    assert tr.program_time(red, "jit_prefill") == (pytest.approx(10e-9), 1)
    ops = dict(red["device_ops"])
    assert ops["jit_decode_step: fusion.2"] == pytest.approx(15e-9)
    assert ops["jit_prefill: convolution"] == pytest.approx(10e-9)
    assert not any("outside" in k or "while" in k for k in ops)


def test_gap_attribution_by_covering_host_span():
    red = tr.reduce(_trace())
    # gaps [20, 30] (midpoint 25, inside decode_step) and [40, 50]
    # (midpoint 45, inside prefill)
    assert red["idle_gaps"] == [["decode_step", pytest.approx(10e-9)],
                                ["prefill", pytest.approx(10e-9)]]
    assert tr.label(1000, _trace()["host"]) == "no span"
    nested = [("decode_step", 0, 100, {}), ("prefill", 40, 60, {})]
    assert tr.label(50, nested) == "prefill"


def test_busy_time_is_averaged_over_devices():
    t = _trace()
    t["devices"].append({"ops": [("x", 0, 50, {})], "modules": []})
    assert tr.reduce(t)["busy_s"] == pytest.approx(40e-9)


def test_batched_decode_is_the_longest_variant():
    red = {"programs": {
        "jit_decode_step": {"device_s": 3.0, "count": 300},
        "jit_decode_step(11)": {"device_s": 1.0, "count": 200},    # 5 ms
        "jit_decode_step(22)": {"device_s": 2.0, "count": 100}}}   # 20 ms
    assert tr.batched_decode(red) == (2.0, 100)
    assert tr.absorb_steps(red) == (1.0, 200)
    one = {"programs": {"jit_decode_step(22)": {"device_s": 2.0,
                                                "count": 100}}}
    assert tr.absorb_steps(one) == (0.0, 0.0)
    assert tr.batched_decode({"programs": {}}) == (0.0, 0.0)


def test_op_names_keep_kind_and_shape():
    assert tr.op_name("%copy.44 = bf16[8,2048,8,128]{3,1,2,0:T(8,128)(2,1)} "
                      "copy(bf16[8,2048,8,128]{3,2,1,0} %fusion.124)") == \
        "%copy.44 copy bf16[8,2048,8,128]"
    assert tr.op_name("%while.4 = (s32[]{:T(128)}, bf16[8]{0}) while(x)") \
        == "%while.4 while (s32[], bf16[8])"
    assert tr.op_name("wrapped_sine") == "wrapped_sine"


def test_program_name():
    assert tr.program_name("jit_decode_step(12)") == "jit_decode_step"
    assert tr.program_name("jit__lambda") == "jit__lambda"


def test_no_device_operation_is_an_error():
    with pytest.raises(ValueError):
        tr.reduce({"devices": [], "host": []})


def test_recorded_cpu_trace(tmp_path):
    """A trace recorded by ``jax.profiler`` loads and reduces: the window
    annotation bounds it, the host spans are found, and the programs run
    inside it show with their device time."""
    import jax
    import jax.numpy as jnp
    f = jax.jit(lambda x: jnp.tanh(x @ x) @ x)
    x = jnp.ones((128, 128))
    f(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation(tr.WINDOW):
        for _ in range(4):
            with jax.profiler.TraceAnnotation("decode_step"):
                f(x).block_until_ready()
    jax.profiler.stop_trace()
    loaded = tr.load(tr.find(str(tmp_path)), ["decode_step"])
    names = [h[0] for h in loaded["host"]]
    assert names.count("decode_step") == 4 and tr.WINDOW in names
    red = tr.reduce(loaded)
    assert 0 < red["busy_s"] <= red["window_s"]
    t, n = tr.program_time(red, "jit_")
    assert t > 0 and n >= 4
