"""Program spans inside ``Engine.step``: the ring, the profiler's copy, the
engine's span set and counters, and token stamps at emission.

Pinned here:

* spans nest, carry their parent's ``sid`` and their attributes, and the
  ring stays bounded,
* under a CPU profiler trace every ring span has its ``TraceAnnotation``
  event in the ``.xplane.pb``, of the same duration, at one offset,
* the engine's span names start with ``serve.`` and none is a name the
  benchmark's trace reduction reserves for the harness,
* one decode ``Engine.step`` emits the decode span set; prefill dispatch
  spans count ``rows_valid <= rows_computed``, as the counters do, and
  every paged dispatch counts the pages its kernel walks,
* the per-phase tick histograms are the spans' durations,
* a token is stamped when it is emitted: a step of known length shows in
  ``ttft_s``.
"""

import glob
import sys
import time
from pathlib import Path

import jax
import numpy as np
import pytest
from jax.profiler import ProfileData

from repro.configs import get_reduced_config
from repro.models import build_model
from repro.serve import Engine, EngineConfig
from repro.serve.telemetry import tracing
from repro.serve.telemetry.tracing import LOG, SpanLog, span, spans_between

pytestmark = pytest.mark.telemetry

ROOT = Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

DECODE_SET = {"serve.step", "serve.admit", "serve.decode", "serve.decode.marshal",
              "serve.decode.dispatch", "serve.decode.wait", "serve.decode.fetch",
              "serve.decode.sample", "serve.telemetry"}


@pytest.fixture(scope="module")
def dense_setup():
    cfg = get_reduced_config("qwen3-1.7b")
    model = build_model(cfg)
    return cfg, model, model.init(jax.random.PRNGKey(0))


def _engine(model, params, **kw):
    return Engine(model, params, EngineConfig(
        n_slots=2, max_len=48, page_size=8, prefill_chunk=8, **kw))


def _run(eng, cfg, n=3, max_new=4):
    rng = np.random.default_rng(3)
    for i in range(n):
        eng.submit(rng.integers(1, cfg.vocab_size, size=5 + 7 * i), max_new=max_new,
                   arrival_time=0.0)
    lo, t = time.monotonic_ns(), 0.0
    while eng.sched.pending:
        eng.step(now=t)
        t += 0.01
    return spans_between(lo, time.monotonic_ns())


def test_spans_nest_with_parent_and_attrs():
    lo = time.monotonic_ns()
    with span("serve.t.outer", a=1) as outer:
        with span("serve.t.inner") as inner:
            inner.set(n=7)
        assert outer.end_ns is None and outer.seconds > 0
    got = {s.name: s for s in spans_between(lo, time.monotonic_ns())}
    o, i = got["serve.t.outer"], got["serve.t.inner"]
    assert o.parent is None and i.parent == o.sid
    assert o.attrs == {"a": 1} and i.attrs == {"n": 7}
    assert o.start_ns <= i.start_ns <= i.end_ns <= o.end_ns
    assert outer.seconds == pytest.approx(1e-9 * (o.end_ns - o.start_ns))


def test_ring_stays_bounded():
    log = SpanLog(maxlen=4)
    for k in range(10):
        log.append(tracing.Span(f"s{k}", k, k + 1, None, {}, k))
    assert [s.name for s in log.spans] == ["s6", "s7", "s8", "s9"]
    assert [s.name for s in log.between(7, 9)] == ["s7", "s8"]
    assert LOG.spans.maxlen == tracing.SPAN_LOG_SIZE == 65_536


def test_ring_matches_the_profiler_trace(tmp_path):
    jax.profiler.start_trace(str(tmp_path))
    lo = time.monotonic_ns()
    for k in range(6):
        with span("serve.t.step", k=k):
            with span("serve.t.work"):
                time.sleep(0.002)
    hi = time.monotonic_ns()
    jax.profiler.stop_trace()
    ring = [s for s in spans_between(lo, hi) if s.name.startswith("serve.t.")]
    path = glob.glob(str(tmp_path / "plugins" / "profile" / "*" / "*.xplane.pb"))[0]
    events = sorted((e for p in ProfileData.from_file(path).planes if p.name.startswith("/host")
                     for line in p.lines for e in line.events
                     if e.name.startswith("serve.t.")), key=lambda e: e.start_ns)
    ring.sort(key=lambda s: s.start_ns)
    assert [e.name for e in events] == [s.name for s in ring] and len(ring) == 12
    steps = [e for e in events if e.name == "serve.t.step"]
    assert [dict(e.stats)["k"] for e in steps] == list(range(6))
    for s, e in zip(ring, events):
        assert abs((s.end_ns - s.start_ns) - e.duration_ns) < 100_000
    offsets = [s.start_ns - e.start_ns for s, e in zip(ring, events)]
    assert max(offsets) - min(offsets) < 100_000


def test_engine_span_names_leave_harness_names_alone(dense_setup):
    from bench import xtrace

    cfg, model, params = dense_setup
    names = {s.name for s in _run(_engine(model, params), cfg)}
    assert DECODE_SET | {"serve.prefill", "serve.prefill.dispatch"} <= names
    assert all(n.startswith("serve.") for n in names)
    assert not names & set(xtrace.HOST_SPANS)
    assert not any(n.startswith("serve.") for n in xtrace.HOST_SPANS)


def test_one_decode_step_emits_the_decode_set(dense_setup):
    cfg, model, params = dense_setup
    eng = _engine(model, params)
    eng.submit(np.arange(1, 6, dtype=np.int32), max_new=4, arrival_time=0.0)
    eng.step(now=0.0)  # admits and prefills the whole 5-token prompt
    lo = time.monotonic_ns()
    eng.step(now=0.1)
    got = spans_between(lo, time.monotonic_ns())
    assert {s.name for s in got} == DECODE_SET
    step = next(s for s in got if s.name == "serve.step")
    assert step.attrs == {"step": 2, "queued": 0, "prefilling": 0, "decoding": 1}
    by = {s.name: s for s in got}
    # the 6 tokens the decode query sees fill one page of 8; the tables hold
    # 2 slots x ceil(48 / 8) columns
    assert by["serve.decode.dispatch"].attrs == {"slots": 1, "pages_live": 1,
                                                  "page_columns": 12}
    vocab_f32 = 2 * cfg.vocab_size * 4  # [n_slots, V] float32 logits
    assert by["serve.decode.fetch"].attrs == {"bytes": vocab_f32}
    assert by["serve.decode"].parent == step.sid
    assert by["serve.decode.wait"].parent == by["serve.decode"].sid


def test_prefill_rows_and_counters(dense_setup):
    cfg, model, params = dense_setup
    eng = _engine(model, params)
    got = _run(eng, cfg)
    calls = [s.attrs for s in got if s.name == "serve.prefill.dispatch"]
    assert calls and all(0 < a["rows_valid"] <= a["rows_computed"] == 2 * 8 for a in calls)
    # a chunk of 8 rows from position p walks ceil((p + 8) / 8) pages: the
    # prompts of 5 and 12 tokens start together (1 + 1), the 12 goes on from
    # 8 (2), and the 19, admitted once the 5 retires, from 0, 8 and 16
    assert [(a["slots"], a["pages_live"], a["page_columns"]) for a in calls] == [
        (2, 2, 12), (1, 2, 12), (1, 1, 12), (1, 2, 12), (1, 3, 12)]
    c = eng.telemetry.registry.counter
    assert sum(a["rows_valid"] for a in calls) == c("prompt_tokens_prefilled").value
    assert sum(a["rows_computed"] for a in calls) == c("prefill_rows_computed").value
    assert c("prefill_rows_computed").value > c("prompt_tokens_prefilled").value
    fetched = sum(s.attrs["bytes"] for s in got if s.name.endswith(".fetch"))
    assert fetched == c("logits_bytes_fetched").value > 0


def test_phase_histograms_are_the_span_durations(dense_setup):
    cfg, model, params = dense_setup
    eng = _engine(model, params)
    got = _run(eng, cfg)
    h = eng.telemetry.registry.histogram
    for phase in ("prefill", "decode"):
        spans = [s for s in got if s.name == f"serve.{phase}"]
        assert h(f"{phase}_tick_s").count == len(spans) > 0
        assert h(f"{phase}_tick_s").total == pytest.approx(
            1e-9 * sum(s.end_ns - s.start_ns for s in spans))


def test_tokens_are_stamped_at_emission(dense_setup):
    """A prefill of known length (the step sleeps 0.3 s) shows in ttft_s: the
    stamp is the tick's ``now`` plus the time taken up to the fetch, on a
    virtual clock that advances by each step's wall time."""
    cfg, model, params = dense_setup
    eng = _engine(model, params)
    prefill = eng._prefill_all

    def slow(*args):
        time.sleep(0.3)
        return prefill(*args)

    eng._prefill_all = slow
    req = eng.submit(np.arange(1, 6, dtype=np.int32), max_new=3, arrival_time=0.0)
    clock, ends = 0.0, []
    while eng.sched.pending:
        t = time.perf_counter()
        eng.step(now=clock)
        clock += time.perf_counter() - t
        ends.append(clock)
    tr = eng.telemetry.tracer.completed[-1]
    ttft = tr.derived()["ttft_s"]
    assert 0.3 <= ttft <= ends[0] and req.ttft() == ttft
    assert eng.telemetry.registry.histogram("ttft_s").percentile(0.5) == ttft
    # the first tick prefills and then decodes the new slot: its two tokens
    # get two stamps inside it, in emission order; the third, the next tick's
    times = [t for t, _ in tr.token_times]
    assert len(times) == 3 and len(ends) == 2
    assert times[0] < times[1] <= ends[0] < times[2] <= ends[1]
