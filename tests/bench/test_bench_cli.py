"""The command refuses anything but a TPU listed in the peaks table: exit
code not 0 and no result line."""
import os
import subprocess
import sys

import pytest

import smoke_root

from bench import device


def test_command_refuses_the_cpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, "bench/run.py", "--workload",
         "qwen1.5-0.5b.chat-zipf", "--seed", "3", "--seconds", "1",
         "--trace", "0"], cwd=smoke_root.REPO, env=env,
        capture_output=True, text=True, timeout=120)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "no TPU" in p.stderr


class _Dev:
    platform = "tpu"

    def __init__(self, kind):
        self.device_kind = kind


def test_device_kind_missing_from_peaks_is_refused(monkeypatch):
    import jax

    monkeypatch.setattr(jax, "devices", lambda: [_Dev("TPU v9 imaginary")])
    with pytest.raises(device.NoChip, match="not in peaks.json"):
        device.require(1)
    monkeypatch.setattr(jax, "devices", lambda: [_Dev("TPU v5 lite")])
    assert device.require(1) == {"platform": "tpu", "kind": "TPU v5 lite",
                                 "count": 1}
    with pytest.raises(device.NoChip, match="needs 4 chips"):
        device.require(4)


def test_peaks_table_has_its_source():
    import json

    with open(device.PEAKS_FILE) as f:
        table = json.load(f)
    assert "TPU v5e" in table["source"]
    v5e = table["devices"]["TPU v5 lite"]
    assert v5e["bf16_flops_per_s"] == 197e12
    assert v5e["hbm_bytes_per_s"] == 819e9


def test_gc_clock_times_collections():
    import gc

    clock = device.GcClock()
    try:
        junk = [[i] for i in range(200000)]
        gc.collect()
        assert clock.secs > 0 and 0 < clock.longest <= clock.secs
        clock.reset()
        assert clock.secs == 0 and clock.longest == 0
        del junk
    finally:
        gc.callbacks.remove(clock._on)
