"""The control: the plain reference in fp8 in the program's place must read
worse than the program, and come out not correct through the harness's own
decision, at a size a test can hold.  Requests are served to completion (no
clock), so the readings repeat exactly."""

import functools

import numpy as np
import pytest

from bench import common, harness
from bench.tests import small


@functools.lru_cache(maxsize=None)
def readings(name, seeds=(1, 2, 3)):
    cell = small.cell(name)
    prog, ctrl = [], []
    for seed in seeds:
        params, recs = small.served(cell, seed)
        g = harness.logit_gaps(cell.config, params, recs, control=True)
        prog.append(harness.gap_stats(g["gap"]))
        ctrl.append(harness.gap_stats(g["control_gap"]))
    return prog, ctrl


@pytest.mark.parametrize("name", ["qwen3-1.7b", "starcoder2-3b", "small_moe"])
def test_control_reads_worse_than_the_program(name):
    prog, ctrl = readings(name)
    for key in ("logit_gap_mean", "logit_gap_p99"):
        assert min(c[key] for c in ctrl) > max(p[key] for p in prog), (key, prog, ctrl)


def test_control_is_not_correct_through_the_harness_decision():
    """At this width the gaps are smaller than at the cell's, so the limit is
    set here as the cell's was: between the program's highest mean gap and the
    control's lowest, nearer the program's (readings: program 0.004-0.009,
    control 0.036-0.051)."""
    prog, ctrl = readings("qwen3-1.7b")
    config = {**small.small_config(), "limits": {"logit_gap_mean": 0.02}}
    assert all(harness.decide(p, config)[0] for p in prog), prog
    assert not any(harness.decide(c, config)[0] for c in ctrl), ctrl


def test_moe_control_is_not_correct_through_the_harness_decision():
    """The small MoE cell at the limit its file states (0.16, set from its
    readings: program 0.045-0.124, control 0.193-0.365 over seeds 1-15)."""
    prog, ctrl = readings("small_moe")
    config = small.moe_cell().config
    assert all(harness.decide(p, config)[0] for p in prog), prog
    assert not any(harness.decide(c, config)[0] for c in ctrl), ctrl


def test_reference_rounds_kv_to_mxfp4_like_the_format():
    ref = common.reference_module(small.small_config())
    x = np.array([[0.0, 0.26, -0.74, 1.25, 2.5, 3.1, 5.0, 5.9] + [0.1] * 24], np.float32)
    y = np.asarray(ref.mxfp4_round(x))
    # amax 5.9 / 6 -> scale 2**round(log2(0.983)) = 1: plain E2M1 rounding
    assert y[0, :8].tolist() == [0.0, 0.5, -0.5, 1.0, 2.0, 3.0, 4.0, 6.0]
    assert np.all(y[0, 8:] == 0.0)


def test_chip_readings_separate_at_the_configuration_limit():
    """The readings the limit was set from (``bench/calibrate.py`` on a v5e, at
    the cells' own sizes and load): through the harness's decision at the
    configuration's own limit, the program is correct on every seed and the
    control on none."""
    data = common.load_json(common.BENCH / "tests" / "data" / "qwen3_control_readings.json")
    config = common.load_json(common.BENCH / "configs" / "qwen3-1.7b.json")
    assert len(data["rows"]) >= 10
    for row in data["rows"]:
        assert harness.decide(row["program"], config)[0], row
        assert not harness.decide(row["control"], config)[0], row
