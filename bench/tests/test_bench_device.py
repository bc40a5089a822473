"""A run off a TPU, or on a device the peak table lacks, fails and prints no
result."""
import os
import subprocess
import sys

import pytest

from bench import harness

PEAKS = harness.load_json(harness.BENCH / "peaks.json")


class FakeDevice:
    platform = "tpu"

    def __init__(self, kind):
        self.device_kind = kind


def test_cpu_is_refused():
    with pytest.raises(harness.NoChip, match="needs a TPU"):
        harness.check_devices(1, PEAKS)


def test_unknown_device_kind_is_refused(monkeypatch):
    import jax
    monkeypatch.setattr(jax, "devices", lambda: [FakeDevice("TPU v9")])
    with pytest.raises(harness.NoChip, match="not in bench/peaks.json"):
        harness.check_devices(1, PEAKS)


def test_too_few_chips_is_refused(monkeypatch):
    import jax
    monkeypatch.setattr(jax, "devices", lambda: [FakeDevice("TPU v5 lite")])
    with pytest.raises(harness.NoChip, match="needs 4 chips"):
        harness.check_devices(4, PEAKS)
    assert harness.check_devices(1, PEAKS) == PEAKS["devices"]["TPU v5 lite"]


def test_peaks_hold_the_v5e():
    v5e = PEAKS["devices"]["TPU v5 lite"]
    assert v5e == {"flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


def test_run_on_cpu_exits_nonzero_without_a_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    cell = harness.load_json(harness.ROOT / "BENCHMARK.json")["workloads"][0]
    p = subprocess.run(
        [sys.executable, str(harness.BENCH / "run.py"), "--workload",
         cell["name"], "--seed", str(2**33), "--seconds", "1", "--trace",
         "0"], cwd=harness.ROOT, env=env, capture_output=True, text=True,
        timeout=300)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "needs a TPU" in p.stderr
