"""The machine the code runs on: roofline peaks (utils/machine.py) and the
placement of JAX's persistent compilation cache (utils/compile_cache.py)."""
import jax
import pytest

from repro.utils import compile_cache
from repro.utils.machine import V5E, MachineProfile, machine_profile

PEAKS = ("REPRO_PEAK_FLOPS", "REPRO_HBM_BW", "REPRO_LINK_BW")


@pytest.fixture
def no_peak_env(monkeypatch):
    for name in PEAKS:
        monkeypatch.delenv(name, raising=False)


@pytest.mark.parametrize("kind", ["cpu", "TPU v4", "NVIDIA H100"])
def test_unknown_device_kind_raises(no_peak_env, kind):
    with pytest.raises(ValueError, match="no published peaks"):
        machine_profile(device_kind=kind)
    with pytest.raises(ValueError, match="no published peaks"):
        machine_profile(1e12, 1e11, device_kind=kind)   # link_bw missing


def test_detected_cpu_device_raises(no_peak_env):
    """The device this suite runs on has no published peaks: it is an
    error, never v5e numbers."""
    assert jax.devices()[0].platform == "cpu"
    with pytest.raises(ValueError):
        machine_profile()


def test_explicit_peaks_accepted_for_unknown_device(no_peak_env):
    prof = machine_profile(1e12, 2e11, 3e10, device_kind="cpu")
    assert prof == MachineProfile("cpu", 1e12, 2e11, 3e10)


def test_env_peaks_accepted_for_unknown_device(monkeypatch):
    for name, v in zip(PEAKS, ("1e12", "2e11", "3e10")):
        monkeypatch.setenv(name, v)
    prof = machine_profile(device_kind="cpu")
    assert (prof.peak_flops, prof.hbm_bw, prof.link_bw) == (1e12, 2e11, 3e10)


@pytest.mark.parametrize("kind", ["TPU v5 lite", "TPU v5e", "tpu v5 lite"])
def test_v5e_published_peaks(no_peak_env, kind):
    prof = machine_profile(device_kind=kind)
    assert prof == V5E
    assert prof.link_bw == 200e9          # 1,600 Gbit/s
    assert (prof.peak_flops, prof.hbm_bw) == (197e12, 819e9)


def test_v5e_single_override(no_peak_env):
    prof = machine_profile(hbm_bw=700e9, device_kind="TPU v5 lite")
    assert prof.name == "tpu-v5e+overrides"
    assert (prof.peak_flops, prof.hbm_bw, prof.link_bw) == (
        197e12, 700e9, 200e9)


@pytest.fixture
def config_updates(monkeypatch):
    """Record jax.config.update calls instead of applying them, so the test
    worker's real compilation cache is never moved."""
    calls = []
    monkeypatch.setattr(jax.config, "update",
                        lambda name, value: calls.append((name, value)))
    return calls


def test_compile_cache_honours_env(monkeypatch, tmp_path, config_updates):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert compile_cache.place_compile_cache() == str(tmp_path)
    assert config_updates == []


def test_compile_cache_fixed_path_in_checkout(monkeypatch, config_updates):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    path = compile_cache.place_compile_cache()
    assert config_updates == [("jax_compilation_cache_dir", path)]
    assert path == str(compile_cache.CHECKOUT_CACHE)
    root = compile_cache.CHECKOUT_CACHE.parent
    assert (root / "pyproject.toml").exists()       # inside the checkout
    assert ".jax_cache/" in (root / ".gitignore").read_text().split()


@pytest.mark.parametrize("profile", [None, V5E])
def test_kernel_table_peak_shares_only_with_a_chip_profile(profile):
    """The ladder records no profile where the kernels ran in interpret
    mode; the table then shows bytes/s alone and resolves no peaks."""
    from benchmarks.roofline import render_kernels
    row = {"write_us": 10.0, "read_us": 5.0, "write_bytes_per_s": 8.19e9,
           "read_bytes_per_s": 1.638e10, "identical": True}
    text = render_kernels({"profile": profile and profile.to_dict(),
                           "pallas": row})
    assert "pallas" in text
    if profile is None:
        assert "vs peak" not in text
    else:
        assert text.count("vs peak") == 2
        assert "1.00e-02" in text and "2.00e-02" in text
