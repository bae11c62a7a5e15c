"""chip_smoke.py: it refuses to run without a TPU, and its scenario holds
the byte oracle on the API path and on every replica (here at a tiny
geometry, with the Pallas kernels in interpret mode on the CPU)."""
import importlib.util
import os
import shutil
import subprocess
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]


def _run_script(cwd, env):
    return subprocess.run([sys.executable, "chip_smoke.py"], cwd=cwd,
                          env=env, capture_output=True, text=True,
                          timeout=300)


def test_refuses_without_tpu():
    out = _run_script(ROOT, dict(os.environ, JAX_PLATFORMS="cpu"))
    assert out.returncode != 0
    assert "no TPU" in out.stderr
    assert '"ok"' not in out.stdout


def test_fails_outside_the_repo(tmp_path):
    """Alone in a directory the script stops at its import of the package,
    before it looks for a device: on a chip too it could not pass."""
    shutil.copy(ROOT / "chip_smoke.py", tmp_path)
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    out = _run_script(tmp_path, dict(env, JAX_PLATFORMS="cpu"))
    assert out.returncode != 0
    assert "No module named 'repro'" in out.stderr
    assert "no TPU" not in out.stderr
    assert '"ok"' not in out.stdout


def test_scenario_matches_oracle_on_every_replica():
    spec = importlib.util.spec_from_file_location("chip_smoke",
                                                  ROOT / "chip_smoke.py")
    smoke = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(smoke)
    geometry = dict(smoke.GEOMETRY, payload_elems=64, page_blocks=8,
                    n_extents=256, max_pages=32, kernel="pallas")
    res = smoke.smoke(geometry, n_volumes=4, write_bytes=64 * 200,
                      diverge_writes=16, seed=0, log=lambda *_: None)
    assert res["kernel"] == "pallas"
    assert res["n_pools"] == 3
    assert res["volumes"] == 5                      # 4 + the clone
    assert res["ops"]["pwrite"] == 200 + 1 + 32
    assert res["api_mismatches"] == 0
    assert res["replica_mismatches"] == [0, 0, 0]
