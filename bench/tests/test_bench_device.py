"""Peaks by device kind, and the refusal to run anywhere but on a TPU."""

import os
import subprocess
import sys

import jax
import pytest

from bench import common


def test_known_device_has_its_peaks():
    p = common.peaks_for("TPU v5 lite")
    assert p["bf16_flops_per_s"] == 197e12 and p["hbm_bytes_per_s"] == 819e9
    assert p["source"]


@pytest.mark.parametrize("kind", ["cpu", "TPU v4", "TPU v5e", ""])
def test_unknown_device_is_an_error(kind):
    with pytest.raises(KeyError):
        common.peaks_for(kind)


def test_check_device_refuses_the_cpu():
    assert jax.default_backend() == "cpu"
    assert "TPU" in common.check_device(jax, 1)


def test_run_without_a_tpu_exits_nonzero_and_prints_no_result():
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    proc = subprocess.run(
        [sys.executable, str(common.BENCH / "run.py"), "--workload", "qwen3-1.7b.chat",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        capture_output=True, text=True, env=env, timeout=120, cwd=common.ROOT)
    assert proc.returncode != 0
    assert "{" not in proc.stdout
    assert "needs a TPU" in proc.stderr
