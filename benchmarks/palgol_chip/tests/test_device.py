"""The device check and the table of peaks."""

import json
import os
import subprocess
import sys

import pytest

import device
from conftest import BENCH, CHECKOUT


def test_no_tpu_is_refused_before_any_work():
    with pytest.raises(device.NoChip) as e:
        device.tpu_devices(1)
    assert e.value.code  # a SystemExit with a message: non-zero exit


def test_the_command_exits_non_zero_without_a_tpu_and_prints_no_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, str(BENCH / "run.py"), "--workload", "wcc-g500-22",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=CHECKOUT, env=env, capture_output=True, text=True, timeout=120)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "no TPU found" in p.stderr


def test_peaks_are_looked_up_by_device_kind():
    table = BENCH / "peaks.json"
    v5e = device.peaks("TPU v5 lite", table)
    assert v5e["hbm_bytes_per_s"] == 819e9
    assert v5e["bf16_flops_per_s"] == 197e12
    assert "TPU v5e" in json.loads(table.read_text())["source"]


def test_an_unknown_device_kind_is_an_error():
    with pytest.raises(KeyError):
        device.peaks("TPU v99", BENCH / "peaks.json")
