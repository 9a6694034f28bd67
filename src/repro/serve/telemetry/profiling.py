"""Engine performance profiler: per-phase device cost accounting.

Opt-in via :class:`~repro.serve.telemetry.TelemetryConfig` (``profile=True``)
and **host-side**: the profiler never changes what the engine's step
functions compile or compute (the zero-interference contract extends to it —
pinned by ``tests/test_profiler.py``).  The timeline of a run is the JAX
profiler's own trace: the engine's ``serve.*`` spans
(:mod:`repro.serve.telemetry.tracing`) land in its host plane beside the
device ops, on one clock (``--profile-out DIR`` on the front ends).

**Per-phase device cost accounting** (:class:`EngineProfiler`).  Each jitted
step the engine owns — batched decode, batched paged prefill, per-slot chunk
prefill, speculative verify, TP-sharded ``shard_map`` variants included — is
AOT-lowered with the exact operand avals the engine feeds it and compiled
*out of band* (``fn.lower(...).compile()`` never touches the call-site jit
cache, so ``jit_compiled_*`` gauges are unaffected).  The compiled module
then goes through the scan-aware HLO analyzer in ``launch/roofline.py``
(``compiled.cost_analysis()`` alone under-counts ``lax.scan`` bodies), giving
model FLOPs, an HBM-traffic proxy, and collective bytes **per call**.  Paired
with the per-phase wall-time sections the engine already measures
(``decode_tick_s`` / ``prefill_tick_s`` / ``verify_tick_s``) this publishes,
per phase and per tick:

* ``roofline_util_<phase>``   — achieved FLOP/s over the peak (how far from
  compute-bound the tick ran),
* ``effective_bw_<phase>``    — HBM-proxy bytes/s actually sustained,
* ``profile_flops_per_call_<phase>`` / ``profile_hbm_bytes_per_call_<phase>``
  — the static per-call cost (the FP4 bytes win as a live number).

Interpret-mode caveat: on CPU the Pallas paged-attention kernel runs in
interpret mode, so its *internal* FLOPs/bytes surface only partially in the
HLO; per-call costs are exact on real backends and a floor here (see
``serve/README.md#observability``).  Utilization gauges divide by the v5e
constants from ``launch.roofline`` unless overridden — on CPU they are
relative numbers for A/B deltas, not absolute hardware truth.
"""

from __future__ import annotations

import dataclasses

import jax
import numpy as np

from repro.launch.roofline import HBM_BW, PEAK_FLOPS_BF16, analyze_compiled

# engine phase -> (calls counter, paged step, fallback step) — the fallback
# covers the gather oracle / dense-slot families whose prefill is the
# per-slot [1, C] chunk loop
PHASES = ("prefill", "decode", "verify")
_PHASE_COUNTERS = {"prefill": "prefill_calls", "decode": "decode_calls",
                   "verify": "verify_calls"}


@dataclasses.dataclass(frozen=True)
class StepCost:
    """Per-call device cost of one jitted step, from its compiled HLO."""

    flops: float
    hbm_bytes: float
    collective_bytes: float

    def to_dict(self) -> dict:
        return dataclasses.asdict(self)


def _aval(x):
    return jax.ShapeDtypeStruct(np.shape(x), np.result_type(x)) \
        if np.isscalar(x) else jax.ShapeDtypeStruct(x.shape, x.dtype)


def _avals(tree):
    return jax.tree_util.tree_map(_aval, tree)


def lower_step_cost(fn, example_args) -> StepCost | None:
    """AOT-lower ``fn`` at ``example_args``'s avals, compile out of band, and
    run the scan-aware roofline analyzer.  Returns ``None`` for steps that
    cannot be lowered (e.g. the TP chunk-prefill convenience lambda).

    This deliberately does NOT call the jitted function: ``lower().compile()``
    produces its own executable and leaves the call-site cache — and
    therefore the engine's ``jit_compiled_*`` gauges and the
    one-compile-per-shape contract — untouched.
    """
    lower = getattr(fn, "lower", None)
    if lower is None:
        return None
    compiled = lower(*_avals(example_args)).compile()
    rep = analyze_compiled(compiled)
    return StepCost(flops=float(rep["flops"]),
                    hbm_bytes=float(rep["mem_bytes"]),
                    collective_bytes=float(rep["total_collective_bytes"]))


def step_example_args(engine) -> dict[str, tuple]:
    """Example operands per jitted step, mirroring exactly what
    ``Engine.step`` marshals (shapes only matter — values are never run)."""
    cfg = engine.config
    B, C = cfg.n_slots, cfg.prefill_chunk
    i32, b8 = np.int32, np.bool_
    tok = lambda s: np.zeros((B, s), i32)
    vec = np.zeros((B,), i32)
    mask = np.zeros((B,), b8)
    params = engine.params
    if engine.paged:
        pool, tables = engine.cache.pool, np.asarray(engine.cache.tables)
        out = {
            "decode_all": (params, tok(1), vec, pool, tables, mask),
            "prefill_chunk": (params, np.zeros((1, C), i32), np.int32(0),
                              tables[0], pool, None),
        }
        if engine._prefill_all is not None:
            out["prefill_all"] = (params, tok(C), vec, vec, pool, tables, mask)
        if engine.spec is not None:
            out["verify_all"] = (params, tok(engine.spec.k + 1), vec, pool,
                                 tables, mask)
        return out
    if engine.backend == "statepool":
        sp = engine.cache
        state = sp.pools()
        kv_tables = np.asarray(sp.kv.tables) if sp.kv is not None else None
        cross_tables = (np.asarray(sp.cross.tables)
                        if sp.cross is not None else None)
        ring1 = np.zeros((1,), i32)
        # extra=None mirrors the dense-slot convention: the lowered cross
        # path reads the pooled plane (decode semantics) — close enough for
        # cost accounting, and shape-compatible for every family
        return {
            "decode_all": (params, tok(1), vec, state, kv_tables, cross_tables,
                           vec, vec, mask),
            "prefill_chunk": (params, np.zeros((1, C), i32), np.int32(0), state,
                              None if kv_tables is None else kv_tables[0],
                              None if cross_tables is None else cross_tables[0],
                              ring1, ring1, None),
        }
    caches = engine.cache.caches
    return {
        "decode_all": (params, tok(1), vec, caches, mask),
        "prefill_chunk": (params, np.zeros((1, C), i32), np.int32(0),
                          np.int32(0), caches, None),
    }


def _phase_step(engine, phase: str) -> str | None:
    """Which jitted step one engine phase spends its device time in."""
    if phase == "decode":
        return "decode_all"
    if phase == "verify":
        return "verify_all" if engine.spec is not None else None
    if phase == "prefill":
        return "prefill_all" if engine._prefill_all is not None else "prefill_chunk"
    return None


class EngineProfiler:
    """Per-engine performance profiler: step cost accounting and roofline /
    bandwidth gauges.

    Created by :class:`~repro.serve.telemetry.EngineTelemetry` when profiling
    is configured; the telemetry hub forwards phase sections
    (:meth:`on_phase`).
    Everything is lazy: a step's HLO is analyzed the first time its phase
    fires (or on an explicit :meth:`phase_costs` call), out of band of the
    measured sections.
    """

    def __init__(self, engine, registry, *, peak_flops: float = PEAK_FLOPS_BF16,
                 peak_bw: float = HBM_BW):
        self.engine = engine
        self.registry = registry
        self.peak_flops = peak_flops
        self.peak_bw = peak_bw
        self._costs: dict[str, StepCost | None] = {}
        self._seen_calls: dict[str, int] = {}
        # accumulated (flops, bytes, wall_s, ticks) per phase for run means
        self._accum: dict[str, list[float]] = {p: [0.0, 0.0, 0.0, 0.0]
                                               for p in PHASES}

    # -- cost accounting ----------------------------------------------------

    def step_cost(self, name: str) -> StepCost | None:
        """Per-call cost of one jitted step (memoized; ``None`` when the step
        does not exist on this engine or cannot be lowered)."""
        if name not in self._costs:
            examples = step_example_args(self.engine)
            if name not in examples:
                self._costs[name] = None
            else:
                fn = getattr(self.engine, "_steps", None)
                fn = getattr(fn, name, None) if fn is not None else None
                if fn is None:  # dense-slot engines keep bare jitted attrs
                    fn = getattr(self.engine, f"_{name}", None)
                self._costs[name] = (lower_step_cost(fn, examples[name])
                                     if fn is not None else None)
        return self._costs[name]

    def phase_costs(self) -> dict[str, dict]:
        """Per-call cost for every step this engine owns — deterministic for
        a fixed engine config (the HLO is a pure function of the avals)."""
        out = {}
        for name in step_example_args(self.engine):
            cost = self.step_cost(name)
            if cost is not None:
                out[name] = cost.to_dict()
        return out

    # -- live hooks (called by EngineTelemetry) ------------------------------

    def on_phase(self, phase: str, dur_s: float) -> None:
        """One tick's phase section finished: refresh the roofline/bandwidth
        gauges from (cost per call) x (calls this tick)."""
        step = _phase_step(self.engine, phase)
        cost = self.step_cost(step) if step is not None else None
        counter = self.registry.counter(_PHASE_COUNTERS[phase]).value
        ncalls = counter - self._seen_calls.get(phase, 0)
        self._seen_calls[phase] = counter
        if cost is not None and ncalls > 0 and dur_s > 0:
            flops = cost.flops * ncalls
            hbm = cost.hbm_bytes * ncalls
            g = self.registry.gauge
            g(f"profile_flops_per_call_{phase}").set(cost.flops)
            g(f"profile_hbm_bytes_per_call_{phase}").set(cost.hbm_bytes)
            g(f"roofline_util_{phase}").set(flops / dur_s / self.peak_flops)
            g(f"effective_bw_{phase}").set(hbm / dur_s)
            acc = self._accum[phase]
            acc[0] += flops
            acc[1] += hbm
            acc[2] += dur_s
            acc[3] += 1

    # -- summaries -----------------------------------------------------------

    def utilization_summary(self) -> dict:
        """Run-mean utilization per phase: totals over every profiled tick
        (robust to per-tick jitter, unlike the last-tick gauges)."""
        out = {"peak_flops": self.peak_flops, "peak_bw": self.peak_bw}
        for phase in PHASES:
            flops, hbm, wall, ticks = self._accum[phase]
            step = _phase_step(self.engine, phase)
            cost = self._costs.get(step) if step is not None else None
            if cost is None or not ticks:
                out[phase] = None
                continue
            out[phase] = {
                "flops_per_call": cost.flops,
                "hbm_bytes_per_call": cost.hbm_bytes,
                "calls": self._seen_calls.get(phase, 0),
                "wall_s": round(wall, 6),
                "roofline_util_mean": (flops / wall / self.peak_flops
                                       if wall > 0 else None),
                "effective_bw_mean": hbm / wall if wall > 0 else None,
            }
        return out


def profile_report(engine, snapshot: dict, *,
                   peak_flops: float = PEAK_FLOPS_BF16,
                   peak_bw: float = HBM_BW) -> dict | None:
    """Post-hoc per-phase cost/utilization report from a finished run's
    telemetry snapshot — the benchmark path: cost-account the steps AFTER the
    timed region and pair them with the measured phase wall-time histograms
    (no live profiler, zero impact on the timed numbers).

    Returns the ``profile`` block of ``BENCH_serve.json`` (schema v4), or
    ``None`` for engines with nothing to account (no jitted steps lowered).
    """
    prof = EngineProfiler(engine, registry=None, peak_flops=peak_flops,
                          peak_bw=peak_bw)
    costs = {name: StepCost(**c) for name, c in prof.phase_costs().items()}
    if not costs:
        return None
    hists, counters = snapshot["histograms"], snapshot["counters"]
    out: dict = {"peak_flops": peak_flops, "peak_bw": peak_bw}
    for phase in PHASES:
        step = _phase_step(engine, phase)
        cost = costs.get(step) if step is not None else None
        wall = (hists.get(f"{phase}_tick_s") or {}).get("sum", 0.0)
        calls = counters.get(_PHASE_COUNTERS[phase], 0)
        if cost is None or not calls:
            out[phase] = None
            continue
        flops, hbm = cost.flops * calls, cost.hbm_bytes * calls
        out[phase] = {
            "flops_per_call": cost.flops,
            "hbm_bytes_per_call": cost.hbm_bytes,
            "calls": calls,
            "wall_s": round(wall, 6),
            "roofline_util_mean": (flops / wall / peak_flops
                                   if wall > 0 else None),
            "effective_bw_mean": hbm / wall if wall > 0 else None,
        }
    return out
