"""Entry-point plumbing: the compile-cache placement every launcher calls at
startup, and ``launch.serve --serve-http`` failing loudly when the engine
pump dies."""

import jax
import pytest

from repro.configs import get_config
from repro.launch import compile_cache
from repro.models import model_api
from repro.serve import Request, ServeEngine


@pytest.fixture
def restore_cache_dir():
    from jax.experimental.compilation_cache import compilation_cache
    before = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", before)
    compilation_cache.reset_cache()


def test_compile_cache_uses_the_directory_placed_outside(
        monkeypatch, tmp_path, restore_cache_dir):
    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert compile_cache.enable_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before   # nothing set


def test_compile_cache_defaults_to_a_fixed_dir_in_the_checkout(
        monkeypatch, restore_cache_dir):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    root = compile_cache.CACHE_DIR.parent
    assert (root / "chip_smoke.py").is_file() and (root / "src").is_dir()
    first = compile_cache.enable_compile_cache()
    assert first == str(root / ".jax_cache")
    assert jax.config.jax_compilation_cache_dir == first
    assert compile_cache.enable_compile_cache() == first


def test_serve_http_exits_nonzero_when_the_pump_dies(monkeypatch):
    from repro.launch import serve as launch_serve

    cfg = get_config("phi4-mini-3.8b", smoke=True)
    params = model_api(cfg).init_params(jax.random.PRNGKey(0))
    engine = ServeEngine(cfg, params, slots=1, max_len=16)

    def device_fault(*args, **kwargs):
        raise RuntimeError("device fault")

    monkeypatch.setattr(engine, "step", device_fault)
    req = Request(uid=0, prompt=[3, 4], max_new_tokens=2)
    engine.submit(req)                    # gives the pump work to fail on
    with pytest.raises(RuntimeError, match="device fault"):
        launch_serve._serve_http(engine, "127.0.0.1:0")
    assert req.truncated and req.done    # the stream was failed open
