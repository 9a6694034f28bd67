"""Data-parallel engine replicas over disjoint device groups.

:class:`ReplicatedEngine` runs ``dp`` independent :class:`~repro.serve.engine.
Engine` instances, each on its own ``tp``-device ``('model',)`` mesh
(``launch.mesh.make_serve_meshes``).  Replicas never communicate: each owns
its PagedCache, prefix cache, scheduler, and telemetry registry.  The only
cross-replica machinery is host-side:

* **placement** — ``submit`` consults :class:`~repro.serve.placement.
  ReplicaPlacer` over the replicas' live (free_pages, free_slots)
  inventories, so requests land where capacity is (most free pages first,
  slots break ties, round-robin breaks exact ties);
* **identity** — replicas share one rid counter (``Scheduler(ids=...)``) so
  request ids stay globally unique and ``completed`` can merge back into
  submission order;
* **accounting** — per-replica busy seconds accrue in ``busy_s``; replicas
  step concurrently in real deployments, so aggregate throughput is
  ``total tokens / max(busy_s)`` (the critical-path replica), which is what
  the benchmark reports;
* **telemetry** — ``aggregate_telemetry`` merges the per-replica registries
  (``registry.merge_registries``): counters sum, ``*_peak`` gauges take the
  max, ``*_watermark`` gauges the min, other gauges the mean — and
  histograms POOL their sample reservoirs and cumulative buckets, so
  DP-aggregate TTFT/TPOT percentiles are computed over all replicas'
  samples (averaging per-replica percentiles would be statistically wrong);
* **tracing** — each replica tags its ``serve.step`` span with
  ``replica=<i>``, so one profiler trace (``jax.profiler.trace``) and the
  process-wide span ring tell the replicas' ticks apart.

Exactness: a request's tokens depend only on its own replica's engine, and
every replica is token-exact vs a single-device engine (the TP contract), so
the DP ensemble is token-exact per request as well.
"""

from __future__ import annotations

import itertools
import time

from repro.launch.mesh import make_serve_meshes
from repro.models.registry import Model
from repro.serve.engine import Engine, EngineConfig
from repro.serve.placement import Placement, ReplicaPlacer, ShardingConfig
from repro.serve.scheduler import Request


class _SchedView:
    """Minimal scheduler facade so drivers written against ``engine.sched``
    (e.g. ``launch.serve_engine.run_workload``) work unchanged."""

    def __init__(self, engines):
        self._engines = engines

    @property
    def pending(self) -> int:
        return sum(e.sched.pending for e in self._engines)

    @property
    def queue(self):
        return [r for e in self._engines for r in e.sched.queue]


class ReplicatedEngine:
    """``dp`` data-parallel Engine replicas behind the Engine driver API
    (``submit`` / ``step`` / ``drain`` / ``completed`` / ``sched.pending``)."""

    def __init__(self, model: Model, params, config: EngineConfig | None = None,
                 sharding: ShardingConfig | None = None):
        config = config or EngineConfig()
        sharding = sharding or config.sharding or ShardingConfig()
        if sharding.dp < 2:
            raise ValueError("ReplicatedEngine needs dp >= 2; use Engine for dp=1")
        self.sharding = sharding
        tp, dp = sharding.tp, sharding.dp
        meshes = make_serve_meshes(tp, dp)
        ids = itertools.count()  # shared → globally-unique rids
        # replicas get a dp-stripped config: each Engine validates tp only
        import dataclasses
        rep_cfg = dataclasses.replace(config, sharding=None)
        self.engines = [
            Engine(model, params, rep_cfg,
                   placement=Placement(tp, mesh=m), ids=ids)
            for m in meshes
        ]
        for r, e in enumerate(self.engines):
            e.replica = r
        self.placer = ReplicaPlacer(dp)
        self.busy_s = [0.0] * dp
        self.sched = _SchedView(self.engines)
        self.model, self.config = model, config
        self.paged = self.engines[0].paged
        self.decode_backend = self.engines[0].decode_backend
        self.steps = 0

    # ------------------------------------------------------------------ API

    def submit(self, prompt, max_new: int, extra=None,
               arrival_time: float | None = None, sampling=None) -> Request:
        free_pages = [e.cache.free_pages if e.paged else e.config.n_slots
                      for e in self.engines]
        free_slots = [len(e.sched.free_slots) for e in self.engines]
        r = self.placer.place(free_pages, free_slots)
        req = self.engines[r].submit(prompt, max_new, extra=extra,
                                     arrival_time=arrival_time,
                                     sampling=sampling)
        req.replica = r
        return req

    def step(self, now: float | None = None) -> dict:
        """Tick every replica that has work; busy wall-time accrues per
        replica (replicas run concurrently in deployment, so the driver's
        virtual clock should advance by the max, not the sum — the summary
        dict's ``busy_s`` carries the per-replica splits for that)."""
        now = time.monotonic() if now is None else now
        infos, busy = [], []
        for r, eng in enumerate(self.engines):
            if not eng.sched.pending:
                continue
            t0 = time.perf_counter()
            infos.append(eng.step(now=now))
            dt = time.perf_counter() - t0
            self.busy_s[r] += dt
            busy.append(dt)
        self.steps += 1
        keys = ("admitted", "prefilling", "decoding", "queued")
        out = {k: sum(i[k] for i in infos) for k in keys}
        out["step"] = self.steps
        out["busy_s"] = busy
        return out

    def drain(self, max_steps: int = 100_000) -> list[Request]:
        while self.sched.pending:
            self.step()
            if self.steps > max_steps:
                raise RuntimeError("drain exceeded max_steps — engine wedged?")
        return self.completed

    @property
    def completed(self) -> list[Request]:
        out = [r for e in self.engines for r in e.completed]
        return sorted(out, key=lambda r: r.rid)

    def cache_bytes(self) -> int:
        return sum(e.cache_bytes() for e in self.engines)

    def aggregate_telemetry(self) -> dict:
        """One merged snapshot across replicas via
        :func:`~repro.serve.telemetry.registry.merge_registries`: counters
        sum; gauges ending ``_peak`` take the max, ``_watermark`` the min,
        anything else the mean; histograms pool reservoirs and buckets so
        the aggregate percentiles are over all replicas' samples; binned
        counts add; EWMA rates sum.  Carries the full snapshot sections
        (histograms/binned/rates included — they used to be dropped)."""
        from repro.serve.telemetry.registry import merge_registries
        merged = merge_registries([e.telemetry.registry for e in self.engines])
        agg = merged.snapshot()
        agg["replicas"] = len(self.engines)
        return agg


def make_engine(model: Model, params, config: EngineConfig | None = None):
    """Factory honoring ``EngineConfig.sharding``: a plain (possibly
    tensor-parallel) :class:`Engine` for ``dp == 1``, a
    :class:`ReplicatedEngine` for ``dp > 1``."""
    config = config or EngineConfig()
    sh = config.sharding
    if sh is not None and sh.dp > 1:
        return ReplicatedEngine(model, params, config, sh)
    return Engine(model, params, config)
