"""One run of one cell: build, warm up, pre-roll, measure, check.

``run.py`` is the command line; this module holds the parts, so the knee
sweep, the calibration of ``correct`` and the CPU tests drive the same code.
The harness stamps every token itself, after ``Engine.step`` returns (the
token is on the host by then), on ``time.monotonic``.
"""

from __future__ import annotations

import contextlib
import dataclasses
import gc
import math
import shutil
import sys
import tempfile
import time

import numpy as np

from bench import common, xtrace
from bench.traffic import generators

SAMPLE_TOKENS = 400  # served tokens the reference re-scores per run
TRACE_SECONDS = 6.0  # longest traced window


# ---------------------------------------------------------------------------
# the system under test
# ---------------------------------------------------------------------------


def program_config(config: dict):
    """The program's ModelConfig for a configuration file, checked field by
    field against what the configuration's reference says the published
    architecture requires (its ``program_fields``)."""
    from repro.configs import get_config

    prog = config["program"]
    cfg = get_config(prog["arch"], **prog.get("overrides", {}))
    ref = common.reference_module(config)
    if not hasattr(ref, "program_fields"):
        raise ValueError(f"reference {config['reference']!r} exports no program_fields, "
                         "so the program cannot be checked against it")
    want = ref.program_fields(config)
    got = {k: getattr(cfg, k, "<no such attribute>") for k in want}
    bad = {k: (got[k], v) for k, v in want.items() if got[k] != v}
    if bad:
        raise ValueError(f"program config differs from {prog['arch']}'s file "
                         f"(program, published): {bad}")
    return cfg


def engine_config(cell_config: dict, traffic: dict) -> dict:
    """The engine's sizing: the configuration's, with the slots and length
    the traffic mix's own deployment sets (``"engine"`` in its file)."""
    return {**cell_config["engine"], **traffic.get("engine", {})}


def build(config: dict, seed: int, engine: dict | None = None):
    """(model, params, engine) with weights drawn from ``seed`` on the device."""
    import jax

    from repro.models import build_model
    from repro.serve import Engine, EngineConfig

    ref = common.reference_module(config)
    model = build_model(program_config(config))
    params = ref.make_params(config, seed)
    want = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    if (jax.tree.structure(want) != jax.tree.structure(params)
            or any(a.shape != b.shape for a, b in zip(jax.tree.leaves(want),
                                                       jax.tree.leaves(params)))):
        raise ValueError("bench weights do not match the program's parameter layout")
    jax.block_until_ready(params)
    return model, params, Engine(model, params, EngineConfig(**(engine or config["engine"])))


def warm_up(engine) -> None:
    """Compile the cell's two step shapes, ``prefill_all`` and ``decode_all``."""
    chunk = engine.config.prefill_chunk
    for _ in range(2):
        engine.submit(np.arange(chunk + 3, dtype=np.int32) % 97, 3)
    engine.drain()


class CompileCounter:
    """Counts JAX compile events (tracing, lowering, backend compile) while on."""

    def __init__(self, jax):
        self.on, self.events = False, 0
        jax.monitoring.register_event_duration_secs_listener(self._seen)

    def _seen(self, event: str, duration: float, **_):
        if self.on and event.startswith("/jax/core/compile"):
            self.events += 1


# ---------------------------------------------------------------------------
# load: open-loop arrivals, stamps for every token
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class Rec:
    req: object  # the engine's Request handle
    due: float
    submitted: float
    admitted: float | None = None
    stamps: list = dataclasses.field(default_factory=list)
    done_at: float | None = None


@dataclasses.dataclass
class StepRec:
    start: float
    end: float
    prefill_rows: list  # (start, n_valid) per prefilling slot
    prefill_finished: int
    decode_contexts: list  # tokens attended per decoding slot


class Driver:
    def __init__(self, engine, traffic: dict, seed: int, vocab: int, seconds: float,
                 annotate: bool = False):
        self.engine = engine
        self.recs: list[Rec] = []
        self.live: list[Rec] = []
        self.steps: list[StepRec] = []
        self.annotate = annotate
        self.schedule = generators.poisson_schedule(traffic, seed, vocab, seconds)
        self.next = 0
        self.t_open = None

    def span(self, name):
        if not self.annotate:
            return contextlib.nullcontext()
        import jax

        return jax.profiler.TraceAnnotation(name)

    def open_at(self, t_open: float) -> None:
        """Fix the window's opening time; arrivals are due relative to it."""
        self.t_open = t_open

    def _submit(self, prompt, max_new, due, now):
        req = self.engine.submit(prompt, max_new, arrival_time=due)
        rec = Rec(req=req, due=due, submitted=now)
        self.recs.append(rec)
        self.live.append(rec)

    def submit_due(self, now: float) -> None:
        while (self.next < len(self.schedule)
               and self.t_open + self.schedule[self.next].due <= now):
            a = self.schedule[self.next]
            self._submit(a.prompt, a.max_new, self.t_open + a.due, now)
            self.next += 1

    def next_due(self) -> float:
        if self.next < len(self.schedule):
            return self.t_open + self.schedule[self.next].due
        return math.inf

    def step(self) -> None:
        from repro.serve.scheduler import RequestState

        snap = [(rec, rec.req.state, rec.req.prefill_pos, len(rec.req.tokens))
                for rec in self.live]
        t_s = time.monotonic()
        self.engine.step(now=t_s)
        t_e = time.monotonic()
        rows, finished, contexts, live = [], 0, [], []
        for rec, state, pos, n in snap:
            r = rec.req
            if state is RequestState.QUEUED and r.state is not RequestState.QUEUED:
                rec.admitted = t_s
            prefilled_now = r.prefill_pos > pos
            if prefilled_now:
                rows.append((pos, r.prefill_pos - pos))
                finished += r.prefill_pos == r.prompt_len
            new = len(r.tokens) - n
            if new:
                rec.stamps += [t_e] * new
                from_prefill = 1 if prefilled_now and r.prefill_pos == r.prompt_len else 0
                if new - from_prefill >= 1:
                    contexts.append(r.prompt_len + len(r.tokens) - 1)
            if r.done:
                rec.done_at = t_e
            else:
                live.append(rec)
        self.live = live
        self.steps.append(StepRec(t_s, t_e, rows, finished, contexts))

    def run(self, until: float) -> float:
        """Drive until ``until``; returns when the last step began before it ended."""
        while (now := time.monotonic()) < until:
            with self.span("driver.submit"):
                self.submit_due(now)
            if self.engine.sched.pending:
                with self.span("engine.step"):
                    self.step()
            else:
                with self.span("driver.wait"):
                    time.sleep(max(0.0, min(self.next_due(), until) - now))
        return time.monotonic()


# ---------------------------------------------------------------------------
# window accounting
# ---------------------------------------------------------------------------


def percentile(values, q: float) -> float | None:
    return float(np.percentile(np.asarray(values, np.float64), q)) if len(values) else None


def window_stats(recs, t0: float, t1: float) -> dict:
    """End-to-end numbers of the window [t0, t1].

    TTFT counts every request due in the window, from its due time; one with
    no first token by ``t1`` counts with the time it has waited so far.  Gaps
    are all inter-token gaps whose later token lands in the window."""
    tokens = sum(1 for r in recs for s in r.stamps if t0 < s <= t1)
    due = [r for r in recs if t0 <= r.due < t1]
    ttft, censored = [], 0
    for r in due:
        if r.stamps and r.stamps[0] <= t1:
            ttft.append(r.stamps[0] - r.due)
        else:
            ttft.append(t1 - r.due)
            censored += 1
    gaps = [b - a for r in recs for a, b in zip(r.stamps, r.stamps[1:]) if t0 < b <= t1]
    late = [r.submitted - r.due for r in due]
    return {
        "window_s": t1 - t0, "tokens": tokens, "output_tok_s": tokens / (t1 - t0),
        "due": len(due), "ttft_samples": len(ttft), "ttft_censored": censored,
        "ttft_p50_ms": _ms(percentile(ttft, 50)), "ttft_p90_ms": _ms(percentile(ttft, 90)),
        "gap_samples": len(gaps), "itl_p50_ms": _ms(percentile(gaps, 50)),
        "itl_p90_ms": _ms(percentile(gaps, 90)), "itl_p95_ms": _ms(percentile(gaps, 95)),
        "itl_p99_ms": _ms(percentile(gaps, 99)),
        "completed": sum(1 for r in recs if r.done_at is not None and t0 < r.done_at <= t1),
        "generator_late_p50_ms": _ms(percentile(late, 50)),
        "generator_late_max_ms": _ms(max(late) if late else None),
    }


def _ms(x):
    return None if x is None else 1e3 * x


# ---------------------------------------------------------------------------
# correct: served tokens against the plain reference
# ---------------------------------------------------------------------------


def sample_finished(recs, t1: float, seed: int, want_tokens: int = SAMPLE_TOKENS):
    """Requests finished by ``t1``: the longest, then others in a seed-drawn
    order until ``want_tokens`` served tokens are in the sample."""
    done = [r for r in recs if r.done_at is not None and r.done_at <= t1]
    if not done:
        return []
    done.sort(key=lambda r: (-len(r.req.tokens), r.req.rid))
    rest = [done[i] for i in generators.rng_for(seed, 7).permutation(np.arange(1, len(done)))]
    sample, n = [], 0
    for r in [done[0], *rest]:
        sample.append(r)
        n += len(r.req.tokens)
        if n >= want_tokens:
            break
    return sample


def logit_gaps(config: dict, params, sample, control: bool = False) -> dict:
    """For every served token of ``sample``: the gap by which its reference
    logit lies below the reference's best at that position.  With
    ``control``, also the gap of the token the fp8 control puts first."""
    import jax.numpy as jnp

    ref = common.reference_module(config)
    score = ref.scorer(config)
    score_c = ref.scorer(config, fp8=True) if control else None
    gaps, gaps_c = [], []
    for rec in sample:
        r = rec.req
        served = np.asarray(r.tokens, np.int32)
        seq = np.concatenate([r.prompt, served[:-1]]).astype(np.int32)
        T = ref.bucket(len(seq))
        toks = np.zeros(T, np.int32)
        toks[:len(seq)] = seq
        rows = np.arange(r.prompt_len - 1, len(seq))
        query = np.zeros((T, 2), np.int32)
        query[rows, 0] = served
        query[rows, 1] = served
        if control:
            _, _, top_c = score_c(params, jnp.asarray(toks), jnp.asarray(query))
            query[rows, 1] = np.asarray(top_c)[rows]
        best, vals, _ = (np.asarray(a) for a in score(params, jnp.asarray(toks),
                                                      jnp.asarray(query)))
        gaps.append(best[rows] - vals[rows, 0])
        gaps_c.append(best[rows] - vals[rows, 1])
    out = {"gap": np.concatenate(gaps) if gaps else np.zeros(0)}
    if control:
        out["control_gap"] = np.concatenate(gaps_c) if gaps_c else np.zeros(0)
    return out


def gap_stats(g: np.ndarray) -> dict:
    """The numbers ``correct`` can compare, from per-token gaps (logits)."""
    if not len(g):
        return {"logit_gap_max": math.inf, "logit_gap_mean": math.inf,
                "logit_gap_p99": math.inf, "mismatch_share": 1.0, "tokens": 0}
    return {"logit_gap_max": float(np.max(g)), "logit_gap_mean": float(np.mean(g)),
            "logit_gap_p99": float(np.percentile(g, 99)),
            "mismatch_share": float(np.mean(g > 0)), "tokens": int(len(g))}


def decide(gaps: dict, config: dict, compiles: int = 0) -> tuple[bool, dict]:
    """``correct`` and the numbers it compared, each beside its limit: at least
    the configuration's ``min_scored_tokens`` re-scored (over a request or two
    of some tens of tokens the mean gap is noise), its ``limits`` on the logit
    gaps, and no compile in the window.  The calibration puts the control's
    gaps through this same decision."""
    limits = config["limits"]
    check = {"scored_tokens": {"value": gaps["tokens"], "limit": config["min_scored_tokens"],
                               "at_least": True}}
    check.update({k: {"value": gaps[k], "limit": float(v)} for k, v in limits.items()})
    check["compiles_in_window"] = {"value": compiles, "limit": 0}
    ok = all(c["value"] >= c["limit"] if c.get("at_least") else c["value"] <= c["limit"]
             for c in check.values())
    return bool(ok), check


# ---------------------------------------------------------------------------
# a whole run
# ---------------------------------------------------------------------------


def log(msg: str) -> None:
    print(msg, flush=True)


def run_cell(cell: common.Cell, seed: int, seconds: float, trace: bool,
             t_process: float, peak: dict | None = None) -> tuple[dict, dict]:
    """One run; returns (result line, check numbers).  ``peak`` stands in for
    the table's entry only where a test drives the run off the chip."""
    import jax

    if cell.chips != 1:
        raise ValueError(f"{cell.name} asks for {cell.chips} chips; this harness builds "
                         "one engine on one chip and has no builder for replicas or a mesh")
    counter = CompileCounter(jax)
    devices = jax.devices()[:cell.chips]
    info = common.device_info(jax)
    peak = peak or common.peaks_for(info["kind"])
    log(f"device: {info}")

    t = time.monotonic()
    split = {"imports_s": t - t_process}
    _, params, engine = build(cell.config, seed, engine_config(cell.config, cell.traffic))
    split["build_s"] = time.monotonic() - t
    t = time.monotonic()
    warm_up(engine)
    split["warmup_s"] = time.monotonic() - t
    counts_before = engine.compile_counts()

    vocab = cell.config["vocab_size"]
    driver = Driver(engine, cell.traffic, seed, vocab, seconds, annotate=trace)
    t = time.monotonic()
    driver.open_at(t + float(cell.traffic["preroll_s"]))
    driver.run(driver.t_open)
    split["preroll_s"] = time.monotonic() - t

    timeline = None
    counter.on = True
    t0 = time.monotonic()
    setup_s = t0 - t_process
    if trace:
        logdir = tempfile.mkdtemp(prefix="bench_trace_")
        jax.profiler.start_trace(logdir)
        i0 = len(driver.steps)
        t0 = time.monotonic()
        with jax.profiler.TraceAnnotation("bench.window"):
            t1 = driver.run(t0 + min(seconds, TRACE_SECONDS))
        i1 = len(driver.steps)
        jax.profiler.stop_trace()
        # serve on, untraced, to the end of a full window, so that `correct`
        # re-scores the same requests a run without the trace would
        t_end = driver.run(t0 + seconds)
    else:
        t1 = t_end = driver.run(t0 + seconds)
    counter.on = False
    compiles = counter.events + sum(
        engine.compile_counts()[k] - v for k, v in counts_before.items())
    mem = common.peak_bytes(jax, devices)
    if trace:
        timeline = xtrace.from_xspace(xtrace.find_xspace(logdir))
        shutil.rmtree(logdir, ignore_errors=True)

    stats = window_stats(driver.recs, t0, t1)
    stats["queued_at_end"] = len(engine.sched.queue)
    log(f"setup split: {split}")
    log(f"window: {stats}")

    sample = sample_finished(driver.recs, t_end, seed)
    driver.engine = engine = None
    gc.collect()
    t = time.monotonic()
    gaps = gap_stats(logit_gaps(cell.config, params, sample)["gap"])
    log(f"reference: {gaps} over {len(sample)} requests in {time.monotonic() - t:.1f} s")

    correct, check = decide(gaps, cell.config, compiles)

    device = {"platform": info["platform"], "kind": info["kind"],
              "count": info["count"], "memory_peak_bytes": mem}
    result = {"correct": correct, "attempted": stats["due"], "failed": 0}
    if trace:
        from bench import layers

        ctx = layers.Context(cell=cell, timeline=timeline,
                             steps=driver.steps[i0:i1], recs=driver.recs,
                             t0=t0, t1=t1, peak=peak,
                             m=common.reference_module(cell.config).dims(cell.config))
        metrics = layers.read_all(ctx, cell.per_layer)
        busy = [xtrace.busy_ns(timeline, d) for d in timeline.devices]
        device["busy_s"] = 1e-9 * sum(busy) / max(len(busy), 1)
        device["window_s"] = 1e-9 * (timeline.window[1] - timeline.window[0])
        result["metrics"] = metrics
        result["device"] = device
        result["breakdown"] = xtrace.breakdown(timeline)
        log(f"trace: {ctx.notes}")
    else:
        values = {**stats, "setup_s": setup_s}
        result["metrics"] = {m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
                             for m in cell.end_to_end}
        result["device"] = device
    result["check"] = check
    return result, check


def enable_compile_cache() -> str:
    """JAX's persistent cache in its one fixed directory, for every program
    however quick to compile, so that a cell's second run compiles nothing."""
    import jax

    from repro.launch.compile_cache import enable_compile_cache as enable

    path = enable()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def report(result: dict, check: dict) -> None:
    """Compared numbers as the last lines on stderr; the result as the last
    line on stdout."""
    import json

    for name, c in check.items():
        side = "at least" if c.get("at_least") else "limit"
        print(f"check {name}: {c['value']} ({side} {c['limit']})", file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
