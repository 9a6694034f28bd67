"""Engine performance profiler: cost accounting, the zero-interference
contract under a profiler trace, and the bench-regression gate.

Pinned here:

* a real engine run under ``jax.profiler.trace`` writes a trace directory
  whose host plane carries one ``serve.step`` span per engine tick and the
  ``serve.prefill.*`` / ``serve.decode.*`` sections inside them,
* per-call cost accounting is **deterministic**: AOT-lowering the same
  engine's steps twice (and on a freshly-built identical engine) yields
  bit-identical FLOPs/bytes — the HLO is a pure function of the avals,
* **zero interference**: profiling on vs off emits bit-identical tokens and
  compiles exactly the same step shapes (AOT ``lower().compile()`` never
  touches the call-site jit cache),
* roofline-utilization / effective-bandwidth gauges appear in the snapshot
  with physical values, and ``profile_report`` produces a schema-valid v4
  ``profile`` block,
* the regression gate passes a baseline against itself, soft-warns (and
  strict-fails) on an injected 20% throughput regression, hard-fails on
  parity/deterministic drift or a hard field going null, and the CLI exits
  nonzero accordingly.
"""

import glob
import json

import jax
import numpy as np
import pytest
from jax.profiler import ProfileData

from repro.configs import get_reduced_config
from repro.models import build_model
from repro.serve import Engine, EngineConfig, SpecConfig, TelemetryConfig
from repro.serve.telemetry import CATALOG
from repro.serve.telemetry.profiling import (EngineProfiler, profile_report,
                                             step_example_args)
from repro.serve.telemetry import regression
from repro.serve.telemetry.schema import validate_bench

pytestmark = pytest.mark.profile

KEY = jax.random.PRNGKey(0)


@pytest.fixture(scope="module")
def dense_setup():
    cfg = get_reduced_config("qwen3-1.7b")
    model = build_model(cfg)
    params = model.init(KEY)
    return cfg, model, params


def _run_engine(model, params, cfg, *, telemetry=None, spec=None,
                n_requests=3, max_new=5):
    eng = Engine(model, params, EngineConfig(
        n_slots=2, max_len=48, page_size=8, prefill_chunk=8,
        telemetry=telemetry, spec=spec))
    rng = np.random.default_rng(7)
    for i in range(n_requests):
        eng.submit(rng.integers(1, cfg.vocab_size, size=5 + 3 * i),
                   max_new=max_new, arrival_time=0.0)
    t = 0.0
    while eng.sched.pending:
        eng.step(now=t)
        t += 0.01
    return eng, t


# ---------------------------------------------------------------------------
# cost accounting: determinism + physical sanity
# ---------------------------------------------------------------------------


def test_cost_accounting_deterministic(dense_setup):
    cfg, model, params = dense_setup
    eng = Engine(model, params, EngineConfig(
        n_slots=2, max_len=48, page_size=8, prefill_chunk=8))
    p1 = EngineProfiler(eng, registry=None)
    p2 = EngineProfiler(eng, registry=None)
    c1, c2 = p1.phase_costs(), p2.phase_costs()
    assert c1 == c2, "same engine, different costs — lowering is not pure"
    assert set(c1) >= {"decode_all", "prefill_all", "prefill_chunk"}
    for name, cost in c1.items():
        assert cost["flops"] > 0, f"{name}: zero FLOPs"
        assert cost["hbm_bytes"] > 0, f"{name}: zero bytes"
    # batched prefill over a chunk costs more than a single decode token
    assert c1["prefill_all"]["flops"] > c1["decode_all"]["flops"]
    # a second, identically-configured engine costs the same (avals define it)
    eng2 = Engine(model, params, EngineConfig(
        n_slots=2, max_len=48, page_size=8, prefill_chunk=8))
    assert EngineProfiler(eng2, registry=None).phase_costs() == c1


def test_step_example_args_cover_spec_verify(dense_setup):
    cfg, model, params = dense_setup
    eng = Engine(model, params, EngineConfig(
        n_slots=2, max_len=48, page_size=8, prefill_chunk=8,
        spec=SpecConfig(k=3, proposer="self")))
    examples = step_example_args(eng)
    assert "verify_all" in examples
    # verify operand is the k+1 multi-query token block
    assert examples["verify_all"][1].shape == (2, 4)
    costs = EngineProfiler(eng, registry=None).phase_costs()
    # verifying k+1 tokens costs more than decoding one
    assert costs["verify_all"]["flops"] > costs["decode_all"]["flops"]


# ---------------------------------------------------------------------------
# zero interference + live gauges + trace contents
# ---------------------------------------------------------------------------


def test_zero_interference_profiling_on_vs_off(dense_setup, tmp_path):
    cfg, model, params = dense_setup
    plain, _ = _run_engine(model, params, cfg, telemetry=None)
    with jax.profiler.trace(str(tmp_path), create_perfetto_trace=True):
        profiled, t = _run_engine(model, params, cfg,
                                  telemetry=TelemetryConfig(profile=True))
    # token streams bit-identical
    assert ({r.rid: r.tokens for r in plain.completed}
            == {r.rid: r.tokens for r in profiled.completed})
    # exactly the same step shapes compiled: the profiler's AOT
    # lower().compile() never populates the call-site jit cache, and the
    # serve.* spans are host-only
    assert plain.compile_counts() == profiled.compile_counts()
    assert profiled.compile_counts()["decode_all"] == 1
    assert profiled.compile_counts()["prefill_all"] == 1
    assert profiled.compile_counts()["prefill_chunk"] == 0  # paged path

    snap = profiled.telemetry.finalize(t)
    g = snap["gauges"]
    assert g["profile_flops_per_call_decode"] > 0
    assert g["profile_hbm_bytes_per_call_decode"] > 0
    assert 0 < g["roofline_util_decode"] <= 1.0
    assert g["effective_bw_decode"] > 0
    assert g["roofline_util_prefill"] > 0
    # profiler gauges are declared in the catalog (snapshot schema stability)
    for phase in ("prefill", "decode", "verify"):
        for stem in ("profile_flops_per_call_", "profile_hbm_bytes_per_call_",
                     "roofline_util_", "effective_bw_"):
            assert CATALOG[stem + phase][0] == "gauge"

    # the trace directory: an XSpace and a Perfetto trace of the run
    run_dir = tmp_path / "plugins" / "profile"
    assert glob.glob(str(run_dir / "*" / "perfetto_trace.json.gz"))
    xspace = glob.glob(str(run_dir / "*" / "*.xplane.pb"))[0]
    host = [e for p in ProfileData.from_file(xspace).planes if p.name.startswith("/host")
            for line in p.lines for e in line.events if e.name.startswith("serve.")]
    # every engine tick got a serve.step span, counted 1, 2, ...
    ticks = sorted((e for e in host if e.name == "serve.step"), key=lambda e: e.start_ns)
    assert len(ticks) == snap["counters"]["engine_ticks"]
    assert [dict(e.stats)["step"] for e in ticks] == list(range(1, len(ticks) + 1))
    names = {e.name for e in host}
    assert {"serve.admit", "serve.prefill.dispatch", "serve.decode.dispatch",
            "serve.decode.wait", "serve.decode.fetch", "serve.decode.sample",
            "serve.telemetry"} <= names
    # sections sit inside their tick
    for e in host:
        if e.name != "serve.step":
            assert any(t.start_ns <= e.start_ns and e.start_ns + e.duration_ns
                       <= t.start_ns + t.duration_ns for t in ticks), e.name


def test_profiling_off_has_no_profiler(dense_setup):
    cfg, model, params = dense_setup
    eng, t = _run_engine(model, params, cfg, telemetry=None)
    assert eng.telemetry.profiler is None
    snap = eng.telemetry.snapshot(t)
    # gauges exist (catalog) but stay at zero with profiling off
    assert snap["gauges"]["roofline_util_decode"] == 0.0
    assert snap["gauges"]["profile_flops_per_call_decode"] == 0.0


# ---------------------------------------------------------------------------
# bench profile block (schema v4)
# ---------------------------------------------------------------------------


def test_profile_report_schema_valid(dense_setup):
    import importlib.util
    import pathlib
    cfg, model, params = dense_setup
    eng, t = _run_engine(model, params, cfg)
    snap = eng.telemetry.finalize(t)
    block = profile_report(eng, snap)
    assert block is not None
    assert block["decode"] is not None
    assert block["decode"]["flops_per_call"] > 0
    assert block["decode"]["calls"] == snap["counters"]["decode_calls"]
    assert block["decode"]["roofline_util_mean"] > 0
    assert block["verify"] is None  # no speculation in this run
    # splice the block into a minimal bench doc: must validate as v4
    mod_path = (pathlib.Path(__file__).resolve().parent.parent / "benchmarks"
                / "serve_throughput.py")
    spec = importlib.util.spec_from_file_location("serve_throughput", mod_path)
    st = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(st)
    num = {"mxfp4": dict.fromkeys(
        ("tokens_per_sec", "ttft_p50_s", "ttft_p95_s", "tpot_p50_s",
         "tpot_p95_s", "latency_p50_s", "latency_p95_s", "queue_wait_p50_s",
         "decode_tick_p50_s", "decode_tick_p95_s", "prefill_tick_p50_s",
         "pool_occupancy_peak", "free_page_watermark", "cache_bytes",
         "bits_per_kv_elem"), 1.0)}
    num["dense"] = dict(num["mxfp4"])
    rep = {
        "arch": "a", "family": "dense", "n_requests": 2, "max_new": 2,
        "n_slots": 2, **num,
        "decode_backends": {"mxfp4/gather": {"tokens_per_sec": 1.0}},
        "cache_ratio": 3.8, "decode_bytes_ratio_gather_over_paged": 8.0,
        "spec": {"k": 3, "proposer": "self"},
        "profile": block,
    }
    doc = st.make_bench_baseline(rep)
    assert validate_bench(doc) == []
    # the whole section and each phase block are nullable
    doc["profile"]["verify"] = None
    assert validate_bench(doc) == []
    doc["profile"] = None
    assert validate_bench(doc) == []
    # but a present phase block must be complete
    doc["profile"] = {"peak_flops": 1.0, "peak_bw": 1.0,
                      "prefill": None, "decode": {"flops_per_call": 1.0},
                      "verify": None}
    assert any("decode" in e for e in validate_bench(doc))


# ---------------------------------------------------------------------------
# regression gate
# ---------------------------------------------------------------------------


def _fake_bench() -> dict:
    """A small but structurally faithful bench doc for gate tests."""
    return {
        "schema": "repro.bench_serve/v4",
        "arch": "qwen3-1.7b-reduced",
        "family": "dense",
        "config": {"n_requests": 4, "max_new": 4, "n_slots": 2},
        "throughput": {"mxfp4_paged_tok_per_s": 100.0,
                       "dense_paged_tok_per_s": 120.0,
                       "mxfp4_gather_tok_per_s": 80.0},
        "latency": {"ttft_p50_s": 0.1, "ttft_p95_s": 0.2},
        "kv": {"cache_bytes_dense": 1000, "cache_bytes_mxfp4": 266,
               "cache_ratio": 3.76,
               "decode_bytes_ratio_gather_over_paged": 8.5},
        "spec": {"k": 3, "proposer": "self", "acceptance_rate": 1.0},
        "sharding": None,
        "profile": {"peak_flops": 1e14, "peak_bw": 8e11,
                    "decode": {"flops_per_call": 2e7,
                               "hbm_bytes_per_call": 1e6},
                    "verify": None},
    }


def test_gate_passes_on_identical_docs():
    base = _fake_bench()
    ok, deltas, report = regression.gate(base, json.loads(json.dumps(base)))
    assert ok
    assert not any(d.failed or d.warned for d in deltas)
    assert "PASS" in report


def test_gate_on_injected_20pct_throughput_regression():
    base = _fake_bench()
    fresh = json.loads(json.dumps(base))
    fresh["throughput"]["mxfp4_paged_tok_per_s"] *= 0.8  # -20%
    # wall-clock metrics are soft: visible warning, clean exit by default…
    ok, deltas, report = regression.gate(base, fresh)
    assert ok
    d = next(d for d in deltas
             if d.path == "throughput.mxfp4_paged_tok_per_s")
    assert d.warned and d.rel == pytest.approx(-0.2)
    assert "WARN" in report
    # …and a demonstrable failure under --strict (dedicated hardware)
    ok_strict, _, report_strict = regression.gate(base, fresh, strict=True)
    assert not ok_strict
    assert "FAIL" in report_strict
    # a within-band wobble (-5%) neither warns nor fails
    mild = json.loads(json.dumps(base))
    mild["throughput"]["mxfp4_paged_tok_per_s"] *= 0.95
    ok_mild, deltas_mild, _ = regression.gate(base, mild, strict=True)
    assert ok_mild and not any(x.warned for x in deltas_mild)


def test_gate_hard_fails_on_parity_fields():
    base = _fake_bench()
    # deterministic compression ratio drifts → hard fail, no --strict needed
    worse = json.loads(json.dumps(base))
    worse["kv"]["cache_ratio"] = 1.1
    ok, deltas, _ = regression.gate(base, worse)
    assert not ok
    assert next(d for d in deltas if d.path == "kv.cache_ratio").failed
    # a hard field going null (the paged path disappeared) → hard fail
    gone = json.loads(json.dumps(base))
    gone["kv"]["decode_bytes_ratio_gather_over_paged"] = None
    ok, deltas, _ = regression.gate(base, gone)
    assert not ok
    # schema mismatch → hard fail
    old = json.loads(json.dumps(base))
    old["schema"] = "repro.bench_serve/v3"
    ok, _, _ = regression.gate(base, old)
    assert not ok
    # both-null sections compare clean; newly-measured fields never fail
    base2 = json.loads(json.dumps(base))
    fresh2 = json.loads(json.dumps(base))
    fresh2["profile"]["verify"] = {"flops_per_call": 1.0}
    ok, deltas, _ = regression.gate(base2, fresh2)
    assert ok
    assert all(d.status in ("ok", "new", "info", "gone")
               for d in deltas if d.path.startswith("profile."))


def test_gate_cli_exit_codes(tmp_path):
    base_path = tmp_path / "base.json"
    fresh_path = tmp_path / "fresh.json"
    base = _fake_bench()
    base_path.write_text(json.dumps(base))
    fresh_path.write_text(json.dumps(base))
    argv = [str(fresh_path), "--baseline", str(base_path)]
    assert regression.main(argv) == 0
    bad = json.loads(json.dumps(base))
    bad["throughput"]["mxfp4_paged_tok_per_s"] *= 0.8
    fresh_path.write_text(json.dumps(bad))
    assert regression.main(argv) == 0          # soft by default
    assert regression.main(argv + ["--strict"]) == 1
    bad["kv"]["cache_ratio"] = 1.0
    fresh_path.write_text(json.dumps(bad))
    out_json = tmp_path / "report.json"
    assert regression.main(argv + ["--json", str(out_json)]) == 1
    rows = json.loads(out_json.read_text())
    assert any(r["path"] == "kv.cache_ratio" and r["status"] == "fail"
               for r in rows)
    assert regression.main([str(tmp_path / "missing.json"),
                            "--baseline", str(base_path)]) == 2


def test_gate_accepts_committed_baseline_against_itself():
    """The committed BENCH_serve.json must pass the gate vs itself — the
    exact comparison CI's smoke job re-runs with a fresh measurement."""
    import pathlib
    bench_path = (pathlib.Path(__file__).resolve().parent.parent
                  / "BENCH_serve.json")
    base = json.loads(bench_path.read_text())
    ok, _, report = regression.gate(base, json.loads(json.dumps(base)))
    assert ok, report
