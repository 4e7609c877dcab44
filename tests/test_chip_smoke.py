"""``chip_smoke.py``: it refuses to run without a TPU, and its phases and
checks hold at a tiny scale on the CPU (kernels in the Pallas interpreter).
"""
import dataclasses
import os
import subprocess
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(ROOT))

import chip_smoke  # noqa: E402

TINY = dataclasses.replace(
    chip_smoke.ONE_CHIP, rows=4000, batch=1000, sample=2048, n_lists=16,
    n_slabs=256, max_chain=16, nprobe=4, served=24, check_q=16, clusters=64)


def _run_smoke(cwd, *args):
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env["JAX_PLATFORMS"] = "cpu"
    return subprocess.run([sys.executable, "chip_smoke.py", *args],
                          cwd=cwd, env=env, capture_output=True, text=True,
                          timeout=300)


def test_refuses_without_tpu():
    r = _run_smoke(ROOT)
    assert r.returncode != 0
    assert "needs a TPU" in r.stderr
    assert '"ok"' not in r.stdout


def test_refuses_outside_a_checkout(tmp_path):
    (tmp_path / "chip_smoke.py").write_text(
        (ROOT / "chip_smoke.py").read_text())
    r = _run_smoke(tmp_path)
    assert r.returncode != 0
    assert "no SIVF sources" in r.stderr
    assert '"ok"' not in r.stdout


@pytest.mark.pallas
def test_one_chip_phase_tiny():
    out = chip_smoke.one_chip(TINY, seed=0, impl="pallas_interpret")
    assert out["impl"] == "pallas_interpret"
    assert out["served_searches"] == 2 * TINY.served
    assert out["recall_at_10_full_probe"] >= 0.99


def test_compare_flags_untied_label_swaps():
    import numpy as np
    d = np.array([[1.0, 2.0, 3.0]], np.float32)
    tol = np.array([1e-3])
    assert chip_smoke.compare("same", (d, np.array([[4, 5, 6]])),
                              (d, np.array([[4, 5, 6]])), tol) == 0.0
    # ranks 1 and 2 tie within tol: their labels may swap
    dt = np.array([[1.0, 2.0, 2.0005]], np.float32)
    chip_smoke.compare("tie", (dt, np.array([[4, 6, 5]])),
                       (dt, np.array([[4, 5, 6]])), tol)
    with pytest.raises(chip_smoke.SmokeFailure, match="labels differ"):
        chip_smoke.compare("swap", (d, np.array([[5, 4, 6]])),
                           (d, np.array([[4, 5, 6]])), tol)
    with pytest.raises(chip_smoke.SmokeFailure, match="above tolerance"):
        chip_smoke.compare("far", (d + 0.01, np.array([[4, 5, 6]])),
                           (d, np.array([[4, 5, 6]])), tol)


_FOUR = r"""
import os, sys, dataclasses
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
sys.path.insert(0, os.getcwd())
sys.path.insert(0, os.path.join(os.getcwd(), "src"))
import jax
import chip_smoke
s = dataclasses.replace(chip_smoke.ONE_CHIP, rows=8000, batch=1000,
                        sample=2048, n_lists=16, n_slabs=512, max_chain=32,
                        nprobe=4, check_q=16, clusters=64)
out = chip_smoke.four_chips(s, 0, jax.devices(), impl="xla")
live = out["per_shard_live"]
assert len(live) == 4 and sum(live) == 7200 and min(live) > 1600, out
print("FOUR OK")
"""


def test_four_chip_phase_on_virtual_devices():
    """The --chips 4 phase on four CPU devices: a mesh Index built from
    jax.devices() places one shard per device and matches the one-device
    index bit for bit through ingest, search and delete."""
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env["JAX_PLATFORMS"] = "cpu"
    r = subprocess.run([sys.executable, "-c", _FOUR], cwd=ROOT, env=env,
                       capture_output=True, text=True, timeout=600)
    assert r.returncode == 0, r.stderr[-2000:]
    assert "FOUR OK" in r.stdout
