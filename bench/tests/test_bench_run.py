"""CPU rehearsal of a whole run at a reduced width, and the faults that
``correct`` must catch.  These call ``harness.run_cell`` directly: it skips
``run.py``'s look for a chip and drives everything after it."""

import time

import numpy as np
import pytest

from bench import harness
from bench.tests import small


def run(cell, seed=2**31 + 17, seconds=2.0):
    return harness.run_cell(cell, seed, seconds, False, time.monotonic(),
                            peak=small.CPU_PEAK)


@pytest.mark.parametrize("name", ["qwen3-1.7b", "starcoder2-3b", "small_moe"])
def test_run_is_correct_and_reports_its_metrics(name):
    result, check = run(small.cell(name))
    assert result["correct"], check
    assert set(result["metrics"]) == {"output_tok_s", "itl_p95_ms", "setup_s"}
    assert all(v["value"] > 0 for v in result["metrics"].values())
    assert result["attempted"] > 0 and result["failed"] == 0
    assert list(result)[-1] == "check" and check["compiles_in_window"]["value"] == 0


def test_cell_on_more_chips_than_one_engine_is_refused():
    with pytest.raises(ValueError, match="4 chips"):
        run(small.small_cell(chips=4))


def test_altered_token_is_caught(monkeypatch):
    """A token altered where it is produced: the sampler emits the runner-up."""
    from repro.serve.engine import Engine

    def runner_up(self, req, logits_row, token_idx):
        return int(np.argsort(np.asarray(logits_row))[-2])

    monkeypatch.setattr(Engine, "_sample", runner_up)
    result, check = run(small.small_cell())
    assert not result["correct"], check


def test_moe_altered_token_is_caught(monkeypatch):
    """The same fault in the small routed-expert cell (reference ``moe_gqa``)."""
    from repro.serve.engine import Engine

    def runner_up(self, req, logits_row, token_idx):
        return int(np.argsort(np.asarray(logits_row))[-2])

    monkeypatch.setattr(Engine, "_sample", runner_up)
    result, check = run(small.moe_cell())
    assert not result["correct"], check


def test_step_that_keeps_its_state_is_caught(monkeypatch):
    """The decode step returns the pool it was given: no KV is written."""
    import repro.serve.engine as engine_mod

    build = engine_mod.build_paged_steps

    def broken(*a, **k):
        steps = build(*a, **k)
        decode = steps.decode_all

        def decode_all(params, tokens, positions, pool, tables, mask):
            logits, _ = decode(params, tokens, positions, pool, tables, mask)
            return logits, pool

        return steps._replace(decode_all=decode_all)

    monkeypatch.setattr(engine_mod, "build_paged_steps", broken)
    result, check = run(small.small_cell())
    assert not result["correct"], check


def test_traced_run_reads_the_trace_and_stays_correct():
    """The whole --trace 1 path off the chip: the trace holds no TPU plane,
    so every device reader finds nothing and leaves its metric off the line."""
    spec = harness.common.load_json(harness.common.ROOT / "BENCHMARK.json")
    cell = small.small_cell(per_layer=spec["per_layer"])
    result, check = harness.run_cell(cell, 5, 3.0, True, time.monotonic(),
                                     peak=small.CPU_PEAK)
    assert result["correct"], check
    assert result["metrics"] == {}
    assert set(result["breakdown"]) == {"device_ops", "idle_gaps"}
    assert result["device"]["window_s"] > 1.0
