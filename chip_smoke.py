"""Smoke run of the serving engine on a TPU: qwen3-1.7b at published widths.

    python chip_smoke.py [--seed 0]          # one chip: serve + reference
    python chip_smoke.py --chips 4 [--seed 0]  # four chips: TP/DP parity only

One chip (default).  ``serve_phase`` builds the full-width qwen3-1.7b engine
(28 layers, d_model 2048, 16/8 heads, vocab 151936, bf16 weights drawn from
``--seed``) on the packed MXFP4 paged pool and serves 16 requests (prompts
of 128-512 tokens from ``--seed``, 32 new tokens each) through
``launch.serve_engine.run_workload`` after a warm-up that compiles every
step.  It checks that every request retires with exactly its 32 tokens,
that every kept logit is finite and that the timed loop compiled nothing.
``reference_phase`` then serves two of the prompts on a dense-pool engine
and compares their first-token logits with the one-request forward that
``train.serve.greedy_generate`` runs (``make_prefill_step``), by relative
L2 error under ``REL_L2_BOUND`` — once with plain bf16 linears (a tight
check of the engine's pages, kernel and chunking) and once with Quartet's.

Four chips (``--chips 4``).  ``multichip_phase`` serves the same requests on
the dense pool three ways, all in this one process: a single-device engine
on device 0 (the baseline), a ``tp=4`` engine, and ``make_engine`` with
``dp=4, tp=1``.  It checks that both give the baseline's tokens (reporting
the first-token logit gap where they do not), that every TP pool leaf spans
four devices, and that the four DP replicas' pools and params sit on four
distinct devices.

The script refuses any backend other than TPU; it has no CPU fallback.  The
phase functions take a model config, so a CPU test can run them at reduced
width.  Wall-clock tokens/s is printed as a smoke figure, not a benchmark.
The last line of stdout is one JSON object:
``{"ok": ..., "device": {"platform": ..., "kind": ..., "count": ...}}``.
"""

from __future__ import annotations

import argparse
import dataclasses
import gc
import json
import sys
import time
import traceback
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.configs import get_config  # noqa: E402
from repro.launch.compile_cache import enable_compile_cache  # noqa: E402
from repro.launch.serve_engine import run_workload  # noqa: E402
from repro.models import build_model  # noqa: E402
from repro.serve import Engine, EngineConfig, ShardingConfig  # noqa: E402
from repro.serve.replica import make_engine  # noqa: E402
from repro.train.serve import init_cache, make_prefill_step  # noqa: E402

ARCH = "qwen3-1.7b"
# Relative L2 bound on first-token logits, dense-pool engine against the
# one-request reference forward, per linear-layer method.  Measured on one
# v5e at the full-width config with random weights (seed 0): moving only the
# reference's matmul precision (default -> highest) shifts its own logits by
# 0.017 with plain bf16 linears and by 0.44 with Quartet's FP4 activations,
# whose round-to-nearest codes flip under rounding changes and carry the
# flips through 28 layers.  The engine sat at 0.019 (bf16) and 0.40-0.42
# (quartet) from the reference; logits of an unrelated prompt sit at 1.40.
# So the bf16 bound is tight enough to catch a wrong page, head or
# position, and the quartet bound only gross breakage of the FP4 path.
REL_L2_BOUND = {"bf16": 0.05, "quartet": 0.7}


def device_info() -> dict:
    d = jax.devices()[0]
    return {"platform": d.platform, "kind": d.device_kind, "count": len(jax.devices())}


def peak_bytes(device=None):
    stats = (device or jax.devices()[0]).memory_stats()
    return None if stats is None else stats.get("peak_bytes_in_use")


def make_workload(vocab: int, seed: int, n: int, min_prompt: int,
                  max_prompt: int, max_new: int):
    """``n`` requests, all due at t=0: [(arrival, prompt, max_new)]."""
    rng = np.random.default_rng(seed)
    return [(0.0, rng.integers(0, vocab, int(rng.integers(min_prompt, max_prompt + 1)))
             .astype(np.int32), max_new) for _ in range(n)]


def build(cfg, seed: int):
    model = build_model(cfg)
    params = jax.jit(model.init)(jax.random.PRNGKey(seed))
    return model, params


def engine_config(kv_dtype: str, **kw) -> EngineConfig:
    base = dict(n_slots=8, page_size=16, max_len=1024, prefill_chunk=128,
                kv_dtype=kv_dtype, keep_logits=True)
    return EngineConfig(**{**base, **kw})


class CompileTimer:
    """Sums XLA backend-compile seconds while active."""

    def __init__(self):
        self.seconds = 0.0
        self.active = False
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event: str, duration: float, **_):
        if self.active and event == "/jax/core/compile/backend_compile_duration":
            self.seconds += duration


def _by_rid(reqs):
    return sorted(reqs, key=lambda r: r.rid)


def rel_l2(a, b) -> float:
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.linalg.norm(a - b) / max(np.linalg.norm(b), 1e-30))


def serve_phase(model, params, workload, ecfg: EngineConfig, timer: CompileTimer):
    """Warm up (compiles every step), then serve ``workload`` and check it."""
    failures = []
    engine = Engine(model, params, ecfg)
    warm = [(0.0, np.arange(8, dtype=np.int32), 2)]
    timer.active, t0 = True, time.perf_counter()
    run_workload(engine, warm, verbose=False)
    warm_s = time.perf_counter() - t0
    timer.active = False
    before = engine.compile_counts()
    t0 = time.perf_counter()
    done, _ = run_workload(engine, workload, verbose=False)
    wall = time.perf_counter() - t0
    after = engine.compile_counts()
    served = _by_rid(done)[len(warm):]
    if after != before:
        failures.append(f"recompiled in the timed loop: {before} -> {after}")
    if len(served) != len(workload):
        failures.append(f"{len(served)} of {len(workload)} requests retired")
    n_logits = 0
    for req, (_, _, max_new) in zip(served, workload):
        if len(req.tokens) != max_new:
            failures.append(f"request {req.rid}: {len(req.tokens)} tokens, want {max_new}")
        if not all(np.isfinite(row).all() for row in req.logits_trace):
            failures.append(f"request {req.rid}: non-finite logits")
        n_logits += len(req.logits_trace)
    tokens = sum(len(r.tokens) for r in served)
    report = {
        "warmup_s": warm_s, "compile_s": timer.seconds,
        "requests": len(served), "tokens": tokens, "logit_rows_checked": n_logits,
        "jit_compiled_before": before, "jit_compiled_after": after,
        "wall_s": wall, "smoke_tok_per_s": tokens / wall if wall else None,
    }
    del engine
    gc.collect()
    return report, failures


def reference_phase(model, params, prompts, ecfg: EngineConfig):
    """First-token logits of a dense-pool engine vs the one-request forward
    ``greedy_generate`` runs, for each prompt."""
    engine = Engine(model, params, ecfg)
    handles = [engine.submit(p, 1) for p in prompts]
    engine.drain()
    prefill = jax.jit(make_prefill_step(model, method=ecfg.method))
    refs = []
    for p in prompts:
        logits, _, _ = prefill(params, jnp.asarray(p)[None],
                               init_cache(model, 1, int(p.size)))
        refs.append(np.asarray(logits[0], np.float32))
    got = [h.logits_trace[0] for h in handles]
    gaps = [rel_l2(g, r) for g, r in zip(got, refs)]
    bound = REL_L2_BOUND[ecfg.method]
    report = {"method": ecfg.method, "rel_l2": gaps, "bound": bound,
              "control_rel_l2": rel_l2(got[0], refs[1]) if len(refs) > 1 else None,
              "argmax_equal": [int(np.argmax(g)) == int(np.argmax(r))
                               for g, r in zip(got, refs)]}
    failures = [f"{ecfg.method} prompt {i}: first-token rel L2 {g:.4g} > {bound}"
                for i, g in enumerate(gaps) if not g <= bound]
    del engine
    gc.collect()
    return report, failures


def _devices_of(tree) -> set:
    return {d for leaf in jax.tree.leaves(tree) for d in leaf.sharding.device_set}


def multichip_phase(model, params, workload, ecfg: EngineConfig, n_devices: int = 4):
    """Baseline on device 0, ``tp=n`` and ``dp=n`` engines on the same
    requests; tokens must match the baseline's, placements must span the
    devices."""
    failures = []
    report = {}

    def serve(engine):
        done, wall = run_workload(engine, workload, verbose=False)
        return _by_rid(done), wall

    base = Engine(model, params, ecfg)
    base_reqs, report["baseline_wall_s"] = serve(base)
    report["baseline_devices"] = len(_devices_of(base.cache.pool))
    del base
    gc.collect()

    def compare(name, reqs):
        mismatched = [i for i, (a, b) in enumerate(zip(reqs, base_reqs))
                      if a.tokens != b.tokens]
        if len(reqs) != len(base_reqs):
            failures.append(f"{name}: {len(reqs)} of {len(base_reqs)} requests retired")
        report[f"{name}_token_exact"] = not mismatched and len(reqs) == len(base_reqs)
        gaps = [float(np.max(np.abs(a.logits_trace[0] - b.logits_trace[0])))
                for a, b in zip(reqs, base_reqs)]
        report[f"{name}_first_logit_max_abs_gap"] = gaps
        if mismatched:
            failures.append(f"{name}: tokens differ from the baseline on requests "
                            f"{mismatched} (first-token max |logit gap| "
                            f"{[gaps[i] for i in mismatched]})")

    tp = Engine(model, params, dataclasses.replace(ecfg, sharding=ShardingConfig(tp=n_devices)))
    spans = {name: len(leaf.sharding.device_set) for name, leaf in tp.cache.pool.items()}
    report["tp_pool_leaf_devices"] = spans
    if set(spans.values()) != {n_devices}:
        failures.append(f"tp pool leaves span {spans} devices, want {n_devices}")
    tp_reqs, report["tp_wall_s"] = serve(tp)
    compare("tp", tp_reqs)
    del tp
    gc.collect()

    dp = make_engine(model, params, dataclasses.replace(
        ecfg, sharding=ShardingConfig(tp=1, dp=n_devices)))
    placed = [(_devices_of(e.cache.pool), _devices_of(e.params)) for e in dp.engines]
    report["dp_replica_devices"] = [sorted(str(d) for d in pool | prm)
                                    for pool, prm in placed]
    homes = [pool | prm for pool, prm in placed]
    if not (all(len(h) == 1 for h in homes) and len(set().union(*homes)) == n_devices):
        failures.append(f"dp replicas not on {n_devices} distinct devices: "
                        f"{report['dp_replica_devices']}")
    dp_reqs, report["dp_wall_s"] = serve(dp)
    report["dp_requests_per_replica"] = [len(e.completed) for e in dp.engines]
    compare("dp", dp_reqs)
    del dp
    gc.collect()
    return report, failures


def _print(label: str, value) -> None:
    print(f"{label}: {json.dumps(value, default=str)}", flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    if jax.default_backend() != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found {jax.default_backend()!r}",
              file=sys.stderr)
        return 2
    info = device_info()
    if info["count"] < args.chips:
        print(f"chip_smoke: --chips {args.chips} but JAX sees {info['count']}",
              file=sys.stderr)
        return 2
    cache = Path(enable_compile_cache())
    warm = len(list(cache.iterdir())) if cache.is_dir() else 0
    print(f"compile cache: {cache} ({warm} entries before this run)", flush=True)
    print(f"device_kind: {info['kind']} ({info['count']} device(s))", flush=True)

    cfg = get_config(ARCH)
    t0 = time.perf_counter()
    model, params = build(cfg, args.seed)
    jax.block_until_ready(params)
    _print("init_s", time.perf_counter() - t0)

    failures = []
    try:
        failures += run_phases(model, params, cfg, args)
    except Exception:  # noqa: BLE001 — a phase that raised has failed
        traceback.print_exc()
        failures.append("a phase raised (traceback on stderr)")
    _print("peak_bytes_in_use", peak_bytes())
    for f in failures:
        print(f"FAILED: {f}", flush=True)
    ok = not failures
    print(json.dumps({"ok": ok, "device": device_info()}), flush=True)
    return 0 if ok else 1


def run_phases(model, params, cfg, args) -> list[str]:
    failures = []
    if args.chips == 1:
        workload = make_workload(cfg.vocab_size, args.seed, 16, 128, 512, 32)
        timer = CompileTimer()
        report, fails = serve_phase(model, params, workload,
                                    engine_config("mxfp4"), timer)
        failures += fails
        for key in ("warmup_s", "compile_s", "requests", "tokens",
                    "logit_rows_checked", "jit_compiled_before",
                    "jit_compiled_after"):
            _print(key, report[key])
        _print("smoke wall tok/s (not a benchmark)", report["smoke_tok_per_s"])
        for method in REL_L2_BOUND:
            ref, fails = reference_phase(model, params, [w[1] for w in workload[:2]],
                                         engine_config("dense", method=method))
            failures += fails
            _print(f"reference {method}", ref)
    else:
        workload = make_workload(cfg.vocab_size, args.seed, 8, 128, 256, 16)
        report, fails = multichip_phase(model, params, workload,
                                        engine_config("dense"), args.chips)
        failures += fails
        _print("multichip", report)
    return failures


if __name__ == "__main__":
    sys.exit(main())
