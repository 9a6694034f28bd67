"""BENCHMARK.json names only what the harness can find and run."""

import json
import math
import re

import pytest

from bench import common, harness

SPEC = common.load_json(common.ROOT / "BENCHMARK.json")
# what the harness can report: the window's numbers and the set-up time
E2E_KNOWN = set(harness.window_stats([], 0.0, 1.0)) | {"setup_s"}
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


def test_top_level_keys_and_limits():
    assert set(SPEC) == {"command", "paths", "run_seconds", "configs", "workloads",
                         "end_to_end", "per_layer"}
    assert SPEC["command"] == ["python3", "bench/run.py"] and SPEC["paths"] == ["bench"]
    rs = SPEC["run_seconds"]
    assert isinstance(rs, int) and 1 <= rs <= 51
    runs = 2 + 14 * 24  # the full 24 cells
    assert runs * (rs + 60) + 24 * 180 + 1200 <= 43200
    assert len(json.dumps(SPEC)) < 64 * 1024


def test_names_units_and_entry_keys():
    names = ([c["name"] for c in SPEC["configs"]] + [w["name"] for w in SPEC["workloads"]]
             + [m["name"] for m in SPEC["end_to_end"] + SPEC["per_layer"]])
    assert len(names) == len(set(names)) and all(NAME.match(n) for n in names)
    for c in SPEC["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert c["file"].startswith("bench/") and (common.ROOT / c["file"]).is_file()
        assert not any(k.endswith(("_dim", "_rank", "_size")) for k in c["reduced"])
    for w in SPEC["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert w["chips"] in (1, 4) and len(w["why"]) <= 200
        assert (common.BENCH / "traffic" / f"{w['traffic']}.json").is_file()
    for m in SPEC["end_to_end"] + SPEC["per_layer"]:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
    four = sum(w["chips"] == 4 for w in SPEC["workloads"])
    assert four <= max(1, len(SPEC["workloads"]) // 2)


def test_end_to_end_bounds():
    for m in SPEC["end_to_end"]:
        assert set(m) <= {"name", "unit", "better", "bound", "source", "workloads"}
        assert m["name"] in E2E_KNOWN and m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    assert any(m["name"] == "setup_s" and "workloads" not in m for m in SPEC["end_to_end"])


@pytest.mark.parametrize("w", SPEC["workloads"], ids=lambda w: w["name"])
def test_every_cell_reports_enough(w):
    cell = common.load_cell(w["name"])
    e2e = {m["name"] for m in cell.end_to_end}
    assert "setup_s" in e2e and len(e2e) >= 2 and cell.per_layer
    for m in cell.per_layer:
        assert m["moves"] in e2e


def test_per_layer_metrics_have_readers():
    layers = {}
    for m in SPEC["per_layer"]:
        assert set(m) <= {"name", "unit", "better", "source", "layer", "moves", "workloads"}
        assert (common.BENCH / "metrics" / f"{m['name']}.py").is_file()
        assert m["source"] in ("device_trace", "program_span", "program_counter", "host_clock")
        if m["name"].split(".")[0].endswith("_roofline") or "mfu" in m["name"]:
            assert m["unit"] == "%"
        assert "\n" not in m["layer"] and len(m["layer"]) <= 200
        layers.setdefault(m["layer"].split(" (")[0], set()).add(m["layer"])
    assert all(len(v) == 1 for v in layers.values())  # one spelling per layer


@pytest.mark.parametrize("c", SPEC["configs"], ids=lambda c: c["name"])
def test_config_files_hold_what_the_harness_reads(c):
    cfg = common.load_json(common.ROOT / c["file"])
    assert cfg["source"] == c["source"] and cfg["reduced"] == c["reduced"]
    assert set(cfg["engine"]) >= {"n_slots", "max_len", "page_size", "prefill_chunk",
                                  "kv_dtype", "method"}
    assert cfg["limits"] and all(math.isfinite(v) and v > 0 for v in cfg["limits"].values())
    assert cfg["min_scored_tokens"] >= 100
    ref = common.reference_module(cfg)
    assert ref.dims(cfg)["d"] == cfg["hidden_size"]
    assert callable(getattr(ref, "program_fields", None))
    harness.program_config(cfg)  # the program holds what the file publishes
