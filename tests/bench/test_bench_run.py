"""The command refuses to measure where it cannot, and prints no result."""
from __future__ import annotations

import os
import subprocess
import sys

import pytest

from bench import harness

CELL = "mobilenet-head-office31.flower-run.c10"


def _run(*args):
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    return subprocess.run(
        [sys.executable, "bench/run.py", *args], cwd=harness.ROOT, env=env,
        capture_output=True, text=True, timeout=120)


def test_no_tpu_exits_nonzero_without_a_result():
    out = _run("--workload", CELL, "--seed", str(2**31 + 5), "--seconds", "1",
               "--trace", "0")
    assert out.returncode != 0
    assert out.stdout.strip() == ""
    assert "no TPU" in out.stderr


def test_unknown_workload_exits_nonzero():
    out = _run("--workload", "no-such-cell", "--seed", "1", "--seconds", "1")
    assert out.returncode != 0 and out.stdout.strip() == ""


def test_unlisted_device_kind_is_refused(monkeypatch):
    class FakeDevice:
        platform, device_kind = "tpu", "TPU v0 imaginary"

    monkeypatch.setattr("jax.devices", lambda: [FakeDevice()])
    with pytest.raises(harness.Refused, match="no peaks"):
        harness.check_device(1)


def test_too_few_chips_is_refused(monkeypatch):
    class FakeDevice:
        platform, device_kind = "tpu", "TPU v5 lite"

    monkeypatch.setattr("jax.devices", lambda: [FakeDevice()])
    with pytest.raises(harness.Refused, match="needs 4 chips"):
        harness.check_device(4)
