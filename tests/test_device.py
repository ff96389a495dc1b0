"""repro.device: the compile-cache location rule and the peaks table."""
import jax
import pytest

from repro import device


@pytest.fixture
def restore_cache_config():
    keys = ("jax_compilation_cache_dir",
            "jax_persistent_cache_min_compile_time_secs")
    saved = {k: getattr(jax.config, k) for k in keys}
    yield
    for k, v in saved.items():
        jax.config.update(k, v)


@pytest.mark.parametrize("env", [None, "/elsewhere/jax-cache"])
def test_compile_cache_dir(monkeypatch, restore_cache_config, env):
    """JAX_COMPILATION_CACHE_DIR wins when set; otherwise the fixed
    directory inside the checkout, never a per-run name."""
    if env is None:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    else:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", env)
    path = device.enable_compile_cache()
    want = env or str(device.CACHE_DIR)
    assert path == want
    assert jax.config.jax_compilation_cache_dir == want
    assert device.CACHE_DIR.parent.joinpath("chip_smoke.py").exists()


def test_peaks_known_kind():
    p = device.peaks("TPU v5 lite")
    assert (p.bf16_flops, p.hbm_bytes_per_s) == (197e12, 819e9)
    assert "TPU v5e" in p.source


def test_peaks_unknown_kind_is_an_error():
    with pytest.raises(LookupError, match="cpu"):
        device.peaks("cpu")
