"""Tracing: program spans inside ``Engine.step``, and request lifecycles.

**Program spans.** :func:`span` (a context manager) times one section of the
engine's host loop — ``serve.step``, ``serve.admit``,
``serve.decode.{marshal,dispatch,wait,fetch,sample}`` and so on, with their
counts as attributes.  Each span is written twice:

* into the JAX profiler's host plane, as a ``jax.profiler.TraceAnnotation``
  — on the same clock as the device ops whenever a profiler trace runs
  (``jax.profiler.trace(dir)``, the ``--profile-out`` option of the front
  ends); outside a trace this costs a flag check;
* into one process-wide bounded ring (:class:`SpanLog`, 65,536 entries) as
  ``(name, start_ns, end_ns, parent, attrs, sid)`` on ``time.monotonic_ns``,
  the clock ``Engine.step(now=...)``'s callers use.  ``parent`` is the
  ``sid`` of the enclosing span, so self time = duration − children.
  :func:`spans_between` reads it.

The ring is process-wide because the profiler it mirrors is.  A few spans per
tick cost a couple of clock reads and a deque append each; they are always on.

**Request lifecycles.** Every request leaves a :class:`RequestTrace` — the
event timestamps ``submit → admit → first_token → retire`` plus per-emission
timestamps ``(t, n_tokens)``.  From those the tracer derives the serving
latencies as *first-class metrics* (fed straight into the registry's
histograms on retire, rather than recomputed by every benchmark):

* ``queue_wait_s``  = admit − submit,
* ``ttft_s``        = first_token − submit,
* ``tpot_s``        = (last_token_t − first_token_t) / (n_tokens − 1)
  (time-per-output-token over the decode phase; ``None`` for single-token
  requests),
* ``request_latency_s`` = retire − submit.

Timestamps are whatever clock the engine is driven on — wall time in live
serving, the virtual clock in ``launch.serve_engine.run_workload`` — the
derivations only ever subtract them.  The engine stamps a token when it is
emitted, after its logits reached the host (tick-start ``now`` plus the time
the tick has taken so far), so TTFT and TPOT include the step that made it.
With a trace path configured, each retired request is appended as one JSON
line (rid, spans, events, token timeline, derived latencies); the last
``keep`` completed traces stay in memory for tests and post-run inspection.
"""

from __future__ import annotations

import dataclasses
import itertools
import json
import threading
import time
from collections import deque
from typing import NamedTuple

from jax.profiler import TraceAnnotation

SPAN_LOG_SIZE = 65_536


class Span(NamedTuple):
    """One finished program span, on ``time.monotonic_ns``."""

    name: str
    start_ns: int
    end_ns: int
    parent: int | None  # sid of the enclosing span
    attrs: dict
    sid: int


class SpanLog:
    """Bounded ring of finished spans: the oldest fall off the end."""

    def __init__(self, maxlen: int = SPAN_LOG_SIZE):
        self.spans: deque[Span] = deque(maxlen=maxlen)

    def append(self, s: Span) -> None:
        self.spans.append(s)

    def between(self, lo_ns: int, hi_ns: int) -> list[Span]:
        """Spans that start and end inside ``[lo_ns, hi_ns]``."""
        return [s for s in self.spans if s.start_ns >= lo_ns and s.end_ns <= hi_ns]

    def clear(self) -> None:
        self.spans.clear()


LOG = SpanLog()
_ids = itertools.count(1)
_open = threading.local()  # per thread: sids of the spans now open


def spans_between(lo_ns: int, hi_ns: int) -> list[Span]:
    """The process's finished spans that lie inside ``[lo_ns, hi_ns]``."""
    return LOG.between(lo_ns, hi_ns)


class span:
    """``with span("serve.decode.fetch", bytes=n) as sp:`` — one program span,
    in the profiler's host plane and in :data:`LOG`.  ``sp.set(k=v)`` adds
    attributes known only inside the section; ``sp.seconds`` is its length
    (so far, while it is open)."""

    __slots__ = ("name", "attrs", "sid", "parent", "start_ns", "end_ns", "_tm")

    def __init__(self, name: str, **attrs):
        self.name, self.attrs, self.end_ns = name, attrs, None

    def __enter__(self) -> "span":
        self._tm = TraceAnnotation(self.name, **self.attrs)
        self._tm.__enter__()
        stack = getattr(_open, "stack", None)
        if stack is None:
            stack = _open.stack = []
        self.parent = stack[-1] if stack else None
        self.sid = next(_ids)
        stack.append(self.sid)
        self.start_ns = time.monotonic_ns()
        return self

    def set(self, **attrs) -> None:
        self.attrs.update(attrs)
        self._tm.set_metadata(**attrs)

    def __exit__(self, *exc) -> None:
        self.end_ns = time.monotonic_ns()
        _open.stack.pop()
        LOG.append(Span(self.name, self.start_ns, self.end_ns, self.parent,
                        self.attrs, self.sid))
        self._tm.__exit__(*exc)

    @property
    def seconds(self) -> float:
        end = time.monotonic_ns() if self.end_ns is None else self.end_ns
        return 1e-9 * (end - self.start_ns)


SPAN_EVENTS = ("submit", "admit", "first_token", "retire")


@dataclasses.dataclass
class RequestTrace:
    rid: int
    events: list = dataclasses.field(default_factory=list)  # [(name, t)]
    token_times: list = dataclasses.field(default_factory=list)  # [(t, n)]

    def event_time(self, name: str) -> float | None:
        for n, t in self.events:
            if n == name:
                return t
        return None

    @property
    def n_tokens(self) -> int:
        return sum(n for _, n in self.token_times)

    def spans(self) -> list[tuple[str, float, float]]:
        """Derived (name, start, end) spans: queued → prefill → decode."""
        out = []
        for name, a, b in (("queued", "submit", "admit"),
                           ("prefill", "admit", "first_token"),
                           ("decode", "first_token", "retire")):
            ta, tb = self.event_time(a), self.event_time(b)
            if ta is not None and tb is not None:
                out.append((name, ta, tb))
        return out

    def derived(self) -> dict:
        sub, adm = self.event_time("submit"), self.event_time("admit")
        ft, ret = self.event_time("first_token"), self.event_time("retire")
        n = self.n_tokens
        tpot = None
        if n > 1 and ft is not None and self.token_times:
            tpot = (self.token_times[-1][0] - ft) / (n - 1)
        return {
            "queue_wait_s": adm - sub if None not in (adm, sub) else None,
            "ttft_s": ft - sub if None not in (ft, sub) else None,
            "tpot_s": tpot,
            "request_latency_s": ret - sub if None not in (ret, sub) else None,
            "n_tokens": n,
        }


class Tracer:
    """Collects per-request traces; feeds latency histograms on retire.

    The engine (and scheduler) report events by request id — the tracer owns
    no request objects.  ``registry`` may be ``None`` (tracing without
    metrics); ``path`` may be ``None`` (metrics without a trace file).
    """

    def __init__(self, registry=None, path: str | None = None, keep: int = 1024):
        self.registry = registry
        self._fh = open(path, "w") if path else None
        self.active: dict[int, RequestTrace] = {}
        self.completed: deque[RequestTrace] = deque(maxlen=max(keep, 1))

    def event(self, rid: int, name: str, t: float) -> None:
        tr = self.active.get(rid)
        if tr is None:
            tr = self.active[rid] = RequestTrace(rid)
        tr.events.append((name, t))
        if name == "retire":
            self._finish(tr)

    def tokens(self, rid: int, t: float, n: int) -> None:
        tr = self.active.get(rid)
        if tr is not None and n > 0:
            tr.token_times.append((t, n))

    def _finish(self, tr: RequestTrace) -> None:
        d = tr.derived()
        if self.registry is not None:
            for name in ("queue_wait_s", "ttft_s", "tpot_s", "request_latency_s"):
                if d[name] is not None:
                    self.registry.histogram(name).observe(d[name])
        if self._fh is not None:
            self._fh.write(json.dumps({
                "rid": tr.rid,
                "spans": [[n, a, b] for n, a, b in tr.spans()],
                "events": [[n, t] for n, t in tr.events],
                "tokens": [[t, n] for t, n in tr.token_times],
                "derived": d,
            }) + "\n")
            self._fh.flush()
        self.completed.append(tr)
        del self.active[tr.rid]

    def close(self) -> None:
        if self._fh is not None:
            self._fh.close()
            self._fh = None
