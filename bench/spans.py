"""The program's own spans inside ``Engine.step``, for the per-layer metrics.

The engine writes every ``serve.*`` span (``repro.serve.telemetry.tracing``)
into the profiler's host plane and into a process-wide ring on
``time.monotonic_ns``, the clock of the harness's traced window
``[ctx.t0, ctx.t1]``.  ``window`` selects the ring's spans of that window;
``align`` puts them on the device trace's clock by pairing each
``serve.step`` with the harness's ``engine.step`` that encloses it.  A
program without the ring gives no spans, and every reader then returns None;
so does a trace that holds no device, as off the chip no reading is a chip's.
Ring spans are ``(name, start_ns, end_ns, parent, attrs, sid)``; ``parent``
is the ``sid`` of the enclosing span.
"""

from __future__ import annotations

from collections import defaultdict

import numpy as np

from bench import layers, xtrace

STEP = "serve.step"
HARNESS_STEP = "engine.step"  # the harness's span around each Engine.step
SAMPLING = ("serve.decode.fetch", "serve.decode.sample")
MAX_LAG = 2  # steps by which the ring and the trace may disagree at the edges


def window(ctx) -> list:
    """The ring's spans that lie inside the traced window."""
    if not ctx.devices:
        return []
    try:
        from repro.serve.telemetry.tracing import spans_between
    except ImportError:  # a program that keeps no span ring
        return []
    return spans_between(int(ctx.t0 * 1e9), int(ctx.t1 * 1e9))


def _ns(s) -> int:
    return s.end_ns - s.start_ns


def steps(spans) -> list[tuple]:
    """``(serve.step span, its descendants)`` for every step in ``spans``."""
    kids = defaultdict(list)
    for s in spans:
        if s.parent is not None:
            kids[s.parent].append(s)

    def below(sid):
        for c in kids.get(sid, ()):
            yield c
            yield from below(c.sid)

    return [(s, list(below(s.sid))) for s in spans if s.name == STEP]


def step_host_ms(ctx) -> float | None:
    """Mean over the window's steps of the step's time less its waits for
    the device (``serve.*.wait``): the host's share of a step."""
    host = [_ns(s) - sum(_ns(d) for d in inner if d.name.endswith(".wait"))
            for s, inner in steps(window(ctx))]
    if not host:
        return None
    cross_check(ctx, host)
    return 1e-6 * float(np.mean(host))


def sample_ms(ctx) -> float | None:
    """Mean per decode step of the logits fetch and the host sampling."""
    per = [sum(_ns(d) for d in inner if d.name in SAMPLING)
           for _, inner in steps(window(ctx))
           if any(d.name == SAMPLING[0] for d in inner)]
    return 1e-6 * float(np.mean(per)) if per else None


def prefill_row_fill(ctx) -> float | None:
    """Prompt rows over rows computed, over the window's prefill calls, in %."""
    calls = [s.attrs for s in window(ctx) if s.name == "serve.prefill.dispatch"]
    computed = sum(a["rows_computed"] for a in calls)
    if not computed:
        return None
    return 100.0 * sum(a["rows_valid"] for a in calls) / computed


def _stab(intervals) -> tuple:
    """The leftmost point that lies in the most of ``intervals``, and how many."""
    intervals = [(a, b) for a, b in intervals if a <= b]
    events = sorted([(a, 0) for a, _ in intervals] + [(b, 1) for _, b in intervals])
    at, most, n = None, 0, 0
    for x, is_end in events:  # at a tie, starts sort before ends
        n += -1 if is_end else 1
        if n > most:
            at, most = x, n
    return at, most


def align(ctx) -> float | None:
    """Offset (ns) that puts ring times on the device trace's clock.  The
    harness's ``engine.step`` encloses the ring's ``serve.step``, so each
    paired step bounds the offset from below by the gap of their starts and
    from above by the gap of their ends; the offset is the least value
    inside the most of these bounds.  Steps pair in order, at the lag (the
    ring and the trace may each hold a step the other cut at an edge) that
    encloses most; the spread of the start gaps goes to the notes."""
    ring = sorted((s for s in window(ctx) if s.name == STEP), key=lambda s: s.start_ns)
    host = sorted((s, e) for n, s, e in ctx.timeline.host if n == HARNESS_STEP)
    best = None
    for lag in sorted(range(-MAX_LAG, MAX_LAG + 1), key=abs):  # ties: the smaller lag
        pairs = list(zip(ring[max(lag, 0):], host[max(-lag, 0):]))
        if not pairs:
            continue
        bounds = [(h0 - r.start_ns, h1 - r.end_ns) for r, (h0, h1) in pairs]
        off, enclosed = _stab(bounds)
        if best is None or enclosed > best[2]:
            best = (lag, off, enclosed, bounds)
    if best is None or not best[2]:
        return None
    lag, off, enclosed, bounds = best
    starts = [a for a, b in bounds if a <= off <= b]
    ctx.note("span_align", {"ring_steps": len(ring), "harness_steps": len(host),
                            "lag": lag, "pairs": len(bounds), "enclosed": enclosed,
                            "offset_spread_us": 1e-3 * (max(starts) - min(starts))})
    return float(off)


def gap_split(spans, lo: float, hi: float) -> dict:
    """Milliseconds of ``[lo, hi]`` (ring clock) under each span name's self
    time, and what no ``serve.*`` span covers."""
    def over(s):
        return max(0.0, min(s.end_ns, hi) - max(s.start_ns, lo))

    kids = defaultdict(float)
    for s in spans:
        if s.parent is not None:
            kids[s.parent] += over(s)
    out = defaultdict(float)
    for s in spans:
        if (own := over(s) - kids[s.sid]) > 0:
            out[s.name] += 1e-6 * own
    out["outside serve spans"] = 1e-6 * (hi - lo) - sum(
        1e-6 * over(s) for s in spans if s.name == STEP)
    return dict(sorted(out.items(), key=lambda kv: -kv[1]))


def cross_check(ctx, host_ns: list) -> None:
    """Notes beside ``step_host_ms``: the host time of the window's steps
    against the device's idle time, and the device's idle gaps inside the
    harness's steps split by the spans the host was in (the longest, and
    all of them summed, the short gaps between ops included)."""
    idle = layers.idle_share(ctx)
    if idle is None:
        return
    win_ms = 1e-6 * (ctx.timeline.window[1] - ctx.timeline.window[0])
    ctx.note("host_vs_idle_ms", {"steps": len(host_ns), "host": 1e-6 * sum(host_ns),
                                 "device_idle": idle * win_ms / 100.0})
    off = align(ctx)
    if off is None:
        return
    gaps = [g for d in ctx.devices for g in xtrace.idle_gaps(ctx.timeline, d)
            if xtrace.host_label(ctx.timeline, *g) == HARNESS_STEP]
    if not gaps:
        return
    spans = window(ctx)
    g0, g1 = max(gaps, key=lambda g: g[1] - g[0])
    ctx.note("longest_idle_gap_ms", gap_split(spans, g0 - off, g1 - off))
    total = defaultdict(float)
    for g0, g1 in gaps:
        for name, ms in gap_split(spans, g0 - off, g1 - off).items():
            total[name] += ms
    ctx.note("idle_gaps_in_steps_ms", dict(sorted(total.items(), key=lambda kv: -kv[1])))
