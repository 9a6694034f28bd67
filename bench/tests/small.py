"""Cells at a width a CPU test can hold, built from the real data files."""

from __future__ import annotations

import copy

import numpy as np

from bench import common

SMALL = {"hidden_size": 128, "intermediate_size": 256, "num_hidden_layers": 2,
         "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 32,
         "vocab_size": 512}
PROGRAM = {"d_model": 128, "d_ff": 256, "num_layers": 2, "num_heads": 4,
           "num_kv_heads": 2, "head_dim": 32, "vocab_size": 512}
ENGINE = {"n_slots": 4, "max_len": 256, "page_size": 16, "prefill_chunk": 16}
E2E = ({"name": "output_tok_s", "unit": "tokens/s"}, {"name": "itl_p95_ms", "unit": "ms"},
       {"name": "setup_s", "unit": "s"})
# a routed-expert configuration at test sizes, with its own reference (moe_gqa)
SMALL_MOE = common.BENCH / "tests" / "data" / "small_moe.json"


def small_config(name: str = "qwen3-1.7b", limit: float = 1.0) -> dict:
    c = copy.deepcopy(common.load_json(common.BENCH / "configs" / f"{name}.json"))
    c.update(SMALL)
    c.pop("head_dim", None) if name != "qwen3-1.7b" else None
    c["head_dim"] = 32
    c["program"]["overrides"] = {**c["program"]["overrides"], **PROGRAM}
    c["engine"] = {**c["engine"], **ENGINE}
    c["limits"] = {"logit_gap_max": limit}
    c["min_scored_tokens"] = 40  # answers here are ~8 tokens, not ~100
    return c


def small_traffic() -> dict:
    return {"kind": "poisson", "rate_per_s": 8.0, "preroll_s": 0.5, "block": 4,
            "prompt": {"median": 40, "sigma": 0.5, "min": 8, "max": 120},
            "output": {"median": 8, "sigma": 0.5, "min": 3, "max": 24}}


def small_cell(name: str = "qwen3-1.7b", limit: float = 1.0, per_layer=(),
               end_to_end=None, chips: int = 1) -> common.Cell:
    return common.Cell(name=f"{name}.small", chips=chips, config=small_config(name, limit),
                       traffic=small_traffic(), end_to_end=tuple(end_to_end or E2E),
                       per_layer=tuple(per_layer))


def moe_cell() -> common.Cell:
    """The small routed-expert cell, served as ``small_cell``'s are."""
    return common.Cell(name="small_moe", chips=1, config=common.load_json(SMALL_MOE),
                       traffic=small_traffic(), end_to_end=E2E, per_layer=())


def cell(name: str) -> common.Cell:
    """A small cell by name: a configuration of ``bench/configs`` cut to
    test sizes, or ``small_moe``."""
    return moe_cell() if name == "small_moe" else small_cell(name)


def served(cell: common.Cell, seed: int):
    """(params, records) of eight prompts of 17-121 tokens served to 24
    tokens each, with no clock: the readings repeat exactly."""
    from bench import harness

    _, params, engine = harness.build(cell.config, seed)
    rng = np.random.default_rng(seed)
    recs = [harness.Rec(req=engine.submit(rng.integers(0, 512, n).astype(np.int32), 24),
                        due=0.0, submitted=0.0, done_at=0.0)
            for n in (17, 40, 64, 90, 121, 33, 77, 100)]
    engine.drain()
    return params, recs


CPU_PEAK = {"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e11, "hbm_bytes": 1e9}
