"""Compile the served path and the Pallas kernels for one TPU v5e chip.

The chip is described (``v5e:2x2`` topology), not attached: nothing runs,
but XLA and Mosaic refuse here what they would refuse on the chip, such as
blocks that break the (8, 128) tiling or a program that overflows HBM.
Shapes are those of ``chip_smoke.py``: the full-width ``phi4-mini-3.8b``
decode step and prefill, and every kernel at its ``KERNELS`` widths.

The topology is described inside a fixture, never at import, so that every
pytest-xdist worker collects the same tests and only the one running this
file loads the TPU compiler.
"""

import importlib.util
import re
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.configs.base import ShapeConfig
from repro.models import model_api
from repro.models.shardlib import ParamSpec

_ROOT = Path(__file__).resolve().parents[1]
_spec = importlib.util.spec_from_file_location("chip_smoke",
                                               _ROOT / "chip_smoke.py")
chip_smoke = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(chip_smoke)

#: One v5e chip holds 16 GB of HBM.
HBM_BYTES = 16e9


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")      # no compiler logs in /tmp
        try:
            desc = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        yield desc


@pytest.fixture(scope="module")
def one_chip(topo):
    """One described chip, with the persistent cache off: entries compiled
    for a chip that is not attached cannot be read back."""
    from jax.experimental.compilation_cache import compilation_cache
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", enabled)
    compilation_cache.reset_cache()


def _on(sharding, tree):
    return jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sharding),
        tree, is_leaf=lambda x: isinstance(x, ParamSpec))


@pytest.fixture(scope="module")
def served(one_chip):
    cfg = get_config(chip_smoke.ARCH)
    api = model_api(cfg)
    return api, _on(one_chip, api.param_specs())


def _fits(compiled) -> int:
    mem = compiled.memory_analysis()
    used = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes)
    assert used <= HBM_BYTES, f"{used / 1e9:.2f} GB > {HBM_BYTES / 1e9} GB"
    return used


def test_decode_step_fits_one_chip(served, one_chip):
    api, params = served
    shape = ShapeConfig("serve", chip_smoke.MAX_LEN, chip_smoke.SLOTS,
                        "decode")
    state = _on(one_chip, api.decode_state_specs(shape))
    tokens = jax.ShapeDtypeStruct((chip_smoke.SLOTS, 1), jnp.int32,
                                  sharding=one_chip)
    compiled = jax.jit(api.decode_step).lower(params, state, tokens).compile()
    # the 7.67 GB of bf16 weights alone must be among the arguments
    assert _fits(compiled) > 7.5e9


def test_prefill_512_fits_one_chip(served, one_chip):
    api, params = served
    batch = {"tokens": jax.ShapeDtypeStruct((1, 512), jnp.int32,
                                            sharding=one_chip)}
    compiled = jax.jit(api.prefill, static_argnames=("max_len",)).lower(
        params, batch, max_len=chip_smoke.MAX_LEN).compile()
    _fits(compiled)


def test_zamba2_decode_step_reads_weights_in_place(one_chip):
    """The full-width hybrid decode step at batch 1 reads each Mamba layer's
    weights where they lie.  The device keeps ``in_proj`` (54, 2560, 10448)
    with its 2560 axis minor; a loop nested in the group loop made XLA copy
    the whole 2.89 GB stack into row-major order on every step."""
    api = model_api(get_config("zamba2-2.7b"))
    params = _on(one_chip, api.param_specs())
    state = _on(one_chip, api.decode_state_specs(
        ShapeConfig("serve", 1024, 1, "decode")))
    tokens = jax.ShapeDtypeStruct((1, 1), jnp.int32, sharding=one_chip)
    compiled = jax.jit(api.decode_step).lower(params, state, tokens).compile()
    hlo = compiled.as_text()
    for leaf in ("in_proj", "out_proj"):
        dims = ",".join(map(str, params["mamba"][leaf].shape))
        assert not re.search(rf"= bf16\[{dims}\]\S* copy\(", hlo), leaf
    in_proj = params["mamba"]["in_proj"]
    # the regrouped step held 3.68 GB of temporaries: the copy and a group
    assert (compiled.memory_analysis().temp_size_in_bytes
            < in_proj.size * in_proj.dtype.itemsize)


@pytest.mark.parametrize("name", sorted(chip_smoke.KERNELS))
def test_kernel_compiles_for_tpu(name, one_chip):
    fn, arg_shapes = chip_smoke.KERNELS[name]
    args = [jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
            for shape, dtype in arg_shapes]
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    _fits(compiled)
