"""The chip check and the peaks table."""

import json
import os
import subprocess
import sys

import pytest

from bench.harness import device
from bench.harness.cell import ROOT


def test_unknown_device_kind_raises(tmp_path):
    with pytest.raises(KeyError, match="no peaks"):
        device.peaks_for("TPU v99")
    p = tmp_path / "peaks.json"
    p.write_text(json.dumps({"X": {"flops_per_s": 1, "hbm_bytes_per_s": 1}}))
    assert device.peaks_for("X", p)["flops_per_s"] == 1


def test_v5e_peaks_have_a_source():
    p = device.peaks_for("TPU v5 lite")
    assert p["flops_per_s"] == 197e12 and p["hbm_bytes_per_s"] == 819e9
    assert "TPU v5e" in p["source"]


def test_cpu_devices_refused():
    with pytest.raises(device.NoChip):
        device.check_devices(1)


def test_non_tpu_run_exits_nonzero_without_a_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "g500-s18.u7-closed",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=300)
    assert p.returncode != 0
    assert "no chip" in p.stderr
    for line in p.stdout.splitlines():
        assert not line.startswith("{")
