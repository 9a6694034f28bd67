"""Readers of the program's spans: a hand-made ring and timeline with known
answers, the pairing onto the trace's clock, and a program without spans."""

import pytest

from bench import common, layers, spans, xtrace
from repro.serve.telemetry import tracing

MS = 1_000_000  # ns
B = 5_000_000 * MS  # ring clock (monotonic ns) at the first step
T = 10 * MS  # the same instant on the trace's clock
EDGE = MS // 100  # engine.step opens and closes 0.01 ms outside serve.step

# ring, in ms from the step's start: step 0 finishes a prompt and decodes,
# steps 1 and 2 (at 100 and 200 ms) only decode
PREFILL_STEP = [("serve.admit", 0, 1, None),
                ("serve.prefill", 1, 31, None),
                ("serve.prefill.marshal", 1, 2, "serve.prefill"),
                ("serve.prefill.dispatch", 2, 3, "serve.prefill"),
                ("serve.prefill.wait", 3, 28, "serve.prefill"),
                ("serve.prefill.fetch", 28, 29, "serve.prefill"),
                ("serve.prefill.sample", 29, 30, "serve.prefill"),
                ("serve.decode", 31, 97, None),
                ("serve.decode.marshal", 31, 33, "serve.decode"),
                ("serve.decode.dispatch", 33, 34, "serve.decode"),
                ("serve.decode.wait", 34, 90, "serve.decode"),
                ("serve.decode.fetch", 90, 93, "serve.decode"),
                ("serve.decode.sample", 93, 96, "serve.decode"),
                ("serve.telemetry", 97, 99, None)]
DECODE_STEP = [("serve.admit", 0, 1, None),
               ("serve.decode", 1, 67, None),
               ("serve.decode.marshal", 1, 3, "serve.decode"),
               ("serve.decode.dispatch", 3, 4, "serve.decode"),
               ("serve.decode.wait", 4, 60, "serve.decode"),
               ("serve.decode.fetch", 60, 63, "serve.decode"),
               ("serve.decode.sample", 63, 66, "serve.decode"),
               ("serve.telemetry", 67, 69, None)]
STEPS = [(0, 100, PREFILL_STEP), (100, 70, DECODE_STEP), (200, 70, DECODE_STEP)]


def _ring():
    out, sid = [], 0
    for at, length, inner in STEPS:
        sid += 1
        step = sid
        out.append(tracing.Span("serve.step", B + at * MS, B + (at + length) * MS, None,
                                {"step": step}, step))
        sids = {}
        for name, s, e, parent in inner:
            sid += 1
            sids[name] = sid
            attrs = ({"slots": 1, "rows_valid": 12, "rows_computed": 32}
                     if name == "serve.prefill.dispatch" else {})
            out.append(tracing.Span(name, B + (at + s) * MS, B + (at + e) * MS,
                                    sids[parent] if parent else step, attrs, sid))
    return out


def _timeline(cut_first_step=False):
    host = [("driver.wait", 0, T - EDGE), ("driver.wait", T + 180 * MS + EDGE, T + 210 * MS - EDGE),
            ("driver.wait", T + 270 * MS + EDGE, 400 * MS)]
    for at, length, _ in STEPS[1 if cut_first_step else 0:]:
        host.append(("engine.step", T + at * MS - EDGE, T + (at + length) * MS + EDGE))
    ops = [("prefill_all_op", T + 3 * MS, T + 28 * MS), ("decode_op", T + 34 * MS, T + 90 * MS),
           ("decode_op", T + 104 * MS, T + 160 * MS), ("decode_op", T + 204 * MS, T + 260 * MS)]
    return xtrace.Timeline(window=(0, 400 * MS),
                           devices={"/device:TPU:0": {"modules": [], "ops": ops}},
                           host=sorted(host, key=lambda h: h[1]))


@pytest.fixture
def ring():
    tracing.LOG.clear()
    for s in _ring():
        tracing.LOG.append(s)
    yield
    tracing.LOG.clear()


def _ctx(lo_ms=-1, hi_ms=400, cut_first_step=False):
    return layers.Context(cell=None, timeline=_timeline(cut_first_step), steps=[], recs=[],
                          t0=(B + lo_ms * MS) / 1e9, t1=(B + hi_ms * MS) / 1e9,
                          peak={}, m={})


METRICS = [m for m in common.load_json(common.ROOT / "BENCHMARK.json")["per_layer"]
           if m["source"] in ("program_span", "program_counter")]


def test_readers_on_a_known_ring(ring):
    ctx = _ctx()
    got = {k: v["value"] for k, v in layers.read_all(ctx, METRICS).items()}
    assert set(got) == {"step_host_ms.saturated", "sample_ms.saturated",
                        "prefill_row_fill.saturated"}
    # host time: 100 - 25 - 56 and twice 70 - 56
    assert got["step_host_ms.saturated"] == pytest.approx((19 + 14 + 14) / 3)
    assert got["sample_ms.saturated"] == pytest.approx(6.0)
    assert got["prefill_row_fill.saturated"] == pytest.approx(100 * 12 / 32)
    assert ctx.notes["host_vs_idle_ms"] == pytest.approx(
        {"steps": 3, "host": 47.0, "device_idle": 13 + 6 + 14 + 44 + 130})


def test_no_prefill_in_window_reads_none(ring):
    ctx = _ctx(lo_ms=99)
    assert spans.prefill_row_fill(ctx) is None
    assert spans.sample_ms(ctx) == pytest.approx(6.0)
    assert spans.step_host_ms(ctx) == pytest.approx(14.0)


def test_align_pairs_steps_and_splits_the_idle_gaps(ring):
    ctx = _ctx()
    assert spans.align(ctx) == pytest.approx(T - EDGE - B)
    assert ctx.notes["span_align"] == {"ring_steps": 3, "harness_steps": 3, "lag": 0,
                                       "pairs": 3, "enclosed": 3, "offset_spread_us": 0.0}
    spans.step_host_ms(ctx)
    # the longest gap inside a step, [100, 114) ms on the trace: the end of
    # step 0 and the start of step 1 (on the ring 0.01 ms earlier)
    split = ctx.notes["longest_idle_gap_ms"]
    assert sum(split.values()) == pytest.approx(14.0)
    assert split == pytest.approx({"serve.decode.fetch": 2.99, "serve.decode.sample": 3.0,
                                   "serve.telemetry": 2.0, "serve.decode.marshal": 2.0,
                                   "serve.admit": 1.0, "serve.decode.dispatch": 1.0,
                                   "serve.decode": 1.0, "serve.step": 1.0,
                                   "serve.decode.wait": 0.01, "outside serve spans": 0.0},
                                  abs=1e-6)
    both = ctx.notes["idle_gaps_in_steps_ms"]  # [38, 44) and [100, 114) ms
    assert both["serve.prefill.sample"] == pytest.approx(1.0)
    assert sum(both.values()) == pytest.approx(20.0)


def test_align_finds_the_lag_when_the_trace_cut_a_step(ring):
    ctx = _ctx(cut_first_step=True)
    assert spans.align(ctx) == pytest.approx(T - EDGE - B)
    assert ctx.notes["span_align"]["lag"] == 1
    assert ctx.notes["span_align"]["enclosed"] == 2


def test_align_takes_the_offset_most_steps_allow():
    """Harness steps open 0.01-0.05 ms before the ring's and close after; one
    pair is off by 2 ms (a stray reading).  The offset is the least one
    inside every other pair's bounds: the latest start gap."""
    ring = [tracing.Span("serve.step", k * 100 * MS, (k * 100 + 50) * MS, None, {}, k)
            for k in range(4)]
    lead = [EDGE, 5 * EDGE, 3 * EDGE, -2 * MS]
    host = [("engine.step", T + k * 100 * MS - d, T + (k * 100 + 50) * MS + EDGE)
            for k, d in enumerate(lead)]
    tracing.LOG.clear()
    for s in ring:
        tracing.LOG.append(s)
    tl = xtrace.Timeline(window=(0, 400 * MS), devices={"/device:TPU:0": {"modules": [],
                                                                          "ops": []}},
                         host=host)
    ctx = layers.Context(cell=None, timeline=tl, steps=[], recs=[], t0=-1e-3, t1=0.5,
                         peak={}, m={})
    try:
        assert spans.align(ctx) == pytest.approx(T - EDGE)
        assert ctx.notes["span_align"]["enclosed"] == 3
        assert ctx.notes["span_align"]["offset_spread_us"] == pytest.approx(40.0)
    finally:
        tracing.LOG.clear()


def test_a_trace_without_a_device_reads_nothing(ring):
    ctx = _ctx()
    ctx.timeline.devices = {}
    assert spans.window(ctx) == [] and spans.align(ctx) is None
    assert layers.read_all(ctx, METRICS) == {} and ctx.notes == {}


def test_a_program_without_the_ring_reads_nothing(ring, monkeypatch):
    monkeypatch.delattr(tracing, "spans_between")
    ctx = _ctx()
    assert spans.window(ctx) == [] and spans.align(ctx) is None
    assert layers.read_all(ctx, METRICS) == {} and ctx.notes == {}
