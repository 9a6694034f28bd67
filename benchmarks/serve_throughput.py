"""Serving benchmark: continuous batching + paged FP4 KV cache.

``PYTHONPATH=src python benchmarks/serve_throughput.py --reduced`` runs a
fixed-seed mixed-length workload through the engine in four configurations —
{dense, mxfp4 cache} × {paged-kernel, gather-dense decode} — and prints a
JSON report:

* tokens/sec (decode throughput, wall clock, post-warmup) per configuration,
* p50/p95 request latency and TTFT on the virtual serving clock,
* tokens-per-decode-call and draft acceptance rate per configuration (plain
  decoding sits at exactly 1.0 token/call; speculative decoding amortizes
  each verify call over 1..k+1 emitted tokens),
* a speculative on/off A/B (``spec`` section): greedy self-speculation over
  the paged-kernel decode, dense + mxfp4 pools, with token-exactness vs the
  non-speculative engine asserted,
* a prefill A/B (``prefill`` section): a concurrent-arrival burst of
  prefill-dominated requests (max_new=1) through the batched paged prefill
  (ONE jitted call advances every prefilling slot per tick) vs the per-slot
  gather oracle — prompt tokens/sec, mean + p95 TTFT, and per-chunk KV
  bytes; batched paged prefill must stay token-exact vs the oracle,
* a shared-prefix A/B (``prefix_cache`` section, ``--shared-prefix`` /
  ``--smoke``): N users × one system prompt through the radix prefix cache
  (warm) vs the non-sharing engine (cold) — prefix hit rate, shared tokens,
  COW pages, prefill tok/s and mean/p95 TTFT cold-vs-warm, with warm-vs-cold
  token parity and pool page-conservation (no leaks) asserted,
* a state-pool family A/B (``families`` section, ``--family ARCH``,
  repeatable / ``--smoke``): each non-attention arch (ssm / hybrid /
  enc-dec / VLM) through the unified StatePool engine vs the dense-slot
  oracle — token parity (dense planes, asserted exact), pooled vs oracle
  decode tok/s, and per-decode-step state-byte traffic of the packed
  planes vs the oracle's dense per-slot caches,
* a multi-device A/B (``sharding`` section, ``--tp`` / ``--dp``): the
  TP-sharded engine (packed pool + paged-attention grid sharded over KV
  heads on the ``model`` mesh axis) and the DP-replicated engine
  (independent replicas on disjoint device groups) vs the single-device
  engine — token parity asserted, per-shard pool bytes, TTFT/TPOT deltas,
  per-replica and aggregate decode tok/s; null when ``tp == dp == 1`` or
  the process sees too few devices,
* persistent cache bytes dense vs FP4 and their ratio,
* decode-step HBM traffic model: KV bytes touched per batched decode step by
  the fused paged-attention kernel (O(packed KV): read the packed pages in
  place) vs the legacy gather-dequantize oracle (read packed + write dense +
  read dense), and their ratio — and the same model per prefill chunk,
* parity checks — dense-cache engine outputs must equal sequential
  ``greedy_generate`` token-for-token, and the paged-kernel decode must equal
  the gather-dense decode token-for-token in dense-cache mode.

CPU wall-clock caveat: the paged kernel runs in Pallas *interpret* mode here,
so its tok/s is a correctness-path number; the bytes model is the hardware
claim (the kernel's blocking moves 4.25-bit payload instead of bf16 KV).

Latency / pool / quantization-health numbers come from the engine's own
telemetry (``repro.serve.telemetry``): the benchmark enables a metrics
registry + tracer per configuration, resets it after warmup, and reads
TTFT/TPOT/queue-wait percentiles, tick wall-times, pool occupancy peaks and
kv_pack clip/scale gauges out of the final snapshot — it no longer re-derives
them from request objects.  ``--metrics-out`` streams the registry snapshots
of the primary (mxfp4/paged) run as JSON-lines.

The report is also persisted as a schema-versioned baseline:
``BENCH_serve.json`` at the repo root (``telemetry.schema.BENCH_SCHEMA``),
validated before writing, so the perf trajectory is tracked across PRs.

``run()`` adapts the same numbers to the ``benchmarks.run`` CSV driver.
"""

from __future__ import annotations

import argparse
import json
import pathlib
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

REPO_ROOT = pathlib.Path(__file__).resolve().parent.parent
BENCH_PATH = REPO_ROOT / "BENCH_serve.json"


def _build(arch: str, reduced: bool):
    from repro.configs import get_config, get_reduced_config
    from repro.models import build_model

    cfg = get_reduced_config(arch) if reduced else get_config(arch)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    return cfg, model, params


def _workload(cfg, n_requests: int, max_new: int, seed: int = 0):
    rng = np.random.default_rng(seed)
    out = []
    t = 0.0
    for _ in range(n_requests):
        t += rng.exponential(0.25)
        plen = int(rng.integers(6, 28))
        out.append((t, rng.integers(0, cfg.vocab_size, plen).astype(np.int32), max_new))
    return out


def _pct(xs, q):
    xs = sorted(xs)
    return xs[min(len(xs) - 1, int(q * len(xs)))]


def decode_kv_bytes_per_step(cache, backend: str) -> int:
    """KV bytes touched by one batched decode step (model, not measurement).

    Both paths see every slot's full page table (T = pages_per_slot·page_size
    positions per slot, all L layers).  The paged kernel streams the packed
    pages once; the gather oracle reads the packed pool, writes the dense
    [L, B, T, Hkv, hd] view, then attention reads it back.  Per-token scatter
    writes (4.25-bit payload for one token) are negligible and omitted.
    """
    hd, H, L = cache.head_dim, cache.kv_heads, cache.layers
    tokens = cache.n_slots * cache.pages_per_slot * cache.page_size
    if cache.kv_dtype == "dense":
        packed_per_tok = 2 * H * hd * jnp.dtype(cache._dtype).itemsize
    else:
        nb = cache.pool["k_scales"].shape[-1]  # scale bytes per head per token
        packed_per_tok = 2 * H * (hd // 2 + nb)
    packed = L * tokens * packed_per_tok
    if backend == "paged":
        return packed
    dense = L * tokens * 2 * H * hd * jnp.dtype(cache._dtype).itemsize
    return packed + 2 * dense  # read packed + write dense + read dense


def prefill_kv_bytes_per_chunk(cache, backend: str) -> int:
    """KV bytes touched per prefilling slot per chunk (model, not measurement).

    Prefill sweeps one slot's page table per chunk exactly as decode sweeps
    every slot's per step, so this is the decode model divided by the slot
    count (ONE shared byte model — keep any change to it in
    :func:`decode_kv_bytes_per_step`): the batched paged prefill streams the
    slot's packed pages once per chunk, the gather oracle reads the packed
    pages, writes the dense [L, T, Hkv, hd] view, and attention reads it
    back.  Batched prefill therefore moves O(packed KV) per chunk instead of
    O(dense KV), which is what keeps TTFT flat as concurrent arrivals stack
    up.
    """
    return decode_kv_bytes_per_step(cache, backend) // cache.n_slots


def _bench_shared_prefix(model, cfg, params, n_requests: int, n_slots: int) -> dict:
    """Shared-prefix A/B: radix prefix cache on (warm) vs off (cold).

    Every request carries the same ``prefix_len``-token system prompt plus a
    short unique tail (request 0 is the pure prefix, exercising the
    full-match eager-COW path).  A primer request publishes the prefix into
    the warm engine's radix index before the measured t=0 burst, so every
    burst admission aliases the shared pages and prefills only its tail —
    the cold engine re-prefills everything.  max_new=1 keeps the run
    prefill-dominated (TTFT is the whole story).  Both engines run with
    ``debug_cache`` on, and the warm run ends with a leak check: after all
    retires, evicting the whole index must return the pool to fully free
    (scratch page 0 aside).
    """
    from repro.launch.serve_engine import run_workload
    from repro.serve import Engine, EngineConfig

    prng = np.random.default_rng(7)
    page_size, prefix_len, tail_len = 8, 24, 6
    prefix = prng.integers(0, cfg.vocab_size, prefix_len).astype(np.int32)
    burst = []
    for i in range(n_requests):
        if i == 0:
            prompt = prefix.copy()  # pure-prefix request: full match + COW
        else:
            tail = prng.integers(0, cfg.vocab_size, tail_len).astype(np.int32)
            prompt = np.concatenate([prefix, tail])
        burst.append((0.0, prompt, 1))
    primer = np.concatenate(
        [prefix, prng.integers(0, cfg.vocab_size, tail_len).astype(np.int32)])
    prompt_toks = sum(len(p) for _, p, _ in burst)

    rep: dict = {"n_requests": n_requests, "prefix_len": prefix_len,
                 "prompt_tokens": prompt_toks}
    out = {}
    for label, on in (("warm", True), ("cold", False)):
        eng = Engine(model, params, EngineConfig(
            n_slots=n_slots, max_len=64, page_size=page_size, kv_dtype="mxfp4",
            prefill_chunk=page_size, decode_backend="paged",
            prefix_cache=on, debug_cache=True))
        # warmup compiles the step shapes; the primer publishes the shared
        # prefix into the warm engine's radix index — both are dropped from
        # the registry before the measured burst
        eng.submit(prng.integers(0, cfg.vocab_size, prefix_len).astype(np.int32),
                   1, arrival_time=0.0)
        eng.submit(primer, 1, arrival_time=0.0)
        eng.drain()
        # second warmup pass: a pure-prefix request now full-matches the
        # published prefix and eagerly COWs its last page, compiling the
        # copy_page kernel outside the timed region (the cold engine just
        # prefills it — keeps both branches' warmups identical)
        eng.submit(prefix.copy(), 1, arrival_time=0.0)
        eng.drain()
        eng.completed.clear()
        eng.telemetry.reset(eng)
        t0 = time.perf_counter()
        done, _ = run_workload(eng, burst, verbose=False)
        wall = time.perf_counter() - t0
        ttfts = [r.ttft() for r in done]
        rep[label] = {
            "prefill_tok_per_s": round(prompt_toks / wall, 2),
            "wall_sec": round(wall, 3),
            "ttft_mean_s": round(sum(ttfts) / len(ttfts), 4),
            "ttft_p95_s": round(_pct(ttfts, 0.95), 4),
        }
        out[label] = {r.rid: list(r.tokens) for r in done}
        c = eng.telemetry.finalize()["counters"]
        if on:
            rep["hit_rate"] = round(
                c["prefix_hit_requests"] / max(c["prefix_lookups"], 1), 4)
            rep["shared_tokens"] = c["prefix_shared_tokens"]
            rep["cow_pages"] = c["prefix_cow_pages"]
            rep["evicted_pages"] = c["prefix_evicted_pages"]
            # leak check: every request has retired, so the index holds the
            # only remaining references — dropping it must free every page
            eng.cache.check_invariants()
            eng.prefix.evict(eng.cache, eng.cache.n_pages)
            rep["no_leaks"] = bool(
                eng.cache.free_pages == eng.cache.n_pages - 1)
    # the prefix cache must be invisible at the tokens level
    rep["parity_warm_vs_cold"] = out["warm"] == out["cold"]
    return rep


def _bench_families(archs, n_requests: int, max_new: int, n_slots: int,
                    reduced: bool = True) -> dict:
    """State-pool A/B over the non-attention families (``--family``).

    Per arch, the same fixed workload runs through three engines:

    * the ``dense_slots`` oracle (per-slot dense caches) — reference tokens
      and oracle throughput,
    * the state pool with ``kv_dtype="dense"`` — planes hold bit-exact
      values, so its tokens must equal the oracle's (``token_parity``),
    * the state pool with ``kv_dtype="mxfp4"`` — the deployable config:
      pooled throughput plus the per-decode-step state-byte traffic of the
      packed planes vs the oracle's dense per-slot caches
      (``state_bytes_ratio``, the FP4 bytes win for recurrent state).

    Keys are arch slugs (``falcon_mamba_7b``); the dict fills the schema-v5
    nullable ``families`` block.
    """
    from repro.launch.serve_engine import make_extra, run_workload
    from repro.serve import Engine, EngineConfig

    out: dict = {}
    for arch in archs:
        cfg, model, params = _build(arch, reduced)
        extra = make_extra(cfg, jax.random.PRNGKey(2))
        workload = _workload(cfg, n_requests, max_new, seed=5)

        def run_one(kv, backend):
            eng = Engine(model, params, EngineConfig(
                n_slots=n_slots, max_len=64, page_size=8, kv_dtype=kv,
                prefill_chunk=8, decode_backend=backend, debug_cache=True))
            eng.submit(workload[0][1], 2, extra=extra, arrival_time=0.0)
            eng.drain()
            eng.completed.clear()
            eng.telemetry.reset(eng)
            t0 = time.perf_counter()
            done, _ = run_workload(eng, workload, extra=extra, verbose=False)
            wall = time.perf_counter() - t0
            toks = sum(len(r.tokens) for r in done)
            return eng, {r.rid: list(r.tokens) for r in done}, toks / wall

        oracle, o_out, o_rate = run_one("dense", "dense_slots")
        _, p_out, _ = run_one("dense", "statepool")
        pooled, _, p_rate = run_one("mxfp4", "statepool")
        pooled.cache.check_invariants()
        step_pool = pooled.cache.state_bytes_per_decode_step(64)
        step_dense = pooled.cache.dense_state_bytes_per_decode_step(64)
        out[arch.replace("-", "_").replace(".", "_")] = {
            "family": cfg.family,
            "token_parity": float(p_out == o_out),
            "pool_tok_per_s": round(p_rate, 2),
            "oracle_tok_per_s": round(o_rate, 2),
            "state_bytes_per_step_pool": step_pool,
            "state_bytes_per_step_dense": step_dense,
            "state_bytes_ratio": round(step_dense / step_pool, 2),
            "cache_bytes_pool": pooled.cache_bytes(),
            "cache_bytes_dense": oracle.cache_bytes(),
        }
    return out


def _bench_sharded(model, cfg, params, n_requests: int, n_slots: int,
                   tp: int, dp: int) -> dict | None:
    """Multi-device A/B: single-device vs TP-sharded vs DP-replicated.

    The TP engine shards the packed MXFP4 pool (and the paged-attention
    grid) over the KV-head axis; the DP engine runs independent replicas on
    disjoint device groups behind a shared request-id counter.  Both must be
    token-exact vs the single-device engine (sharding is head/expert slices
    + tiled all_gathers, never a cross-shard reduction), so parity is an
    equality check on the sorted-by-rid token lists.  DP aggregate
    throughput is total decode tokens over the critical-path replica's busy
    seconds — replicas tick sequentially on one host here but run
    concurrently in deployment.

    Returns ``None`` (reported as ``sharding: null``) when there is nothing
    to shard (``tp == dp == 1``), the family has no paged pool, or the
    process sees fewer than ``tp * dp`` devices (run under
    ``XLA_FLAGS=--xla_force_host_platform_device_count=8``).
    """
    from repro.launch.serve_engine import run_workload
    from repro.serve import (EngineConfig, ReplicatedEngine, ShardingConfig,
                             make_engine)

    n_dev = len(jax.devices())
    if cfg.family not in ("dense", "moe") or (tp <= 1 and dp <= 1) \
            or tp * dp > n_dev:
        return None

    prng = np.random.default_rng(11)
    max_new = 8
    burst = [(0.0,
              prng.integers(0, cfg.vocab_size,
                            int(prng.integers(8, 25))).astype(np.int32),
              max_new)
             for _ in range(n_requests)]

    def run_one(sh):
        eng = make_engine(model, params, EngineConfig(
            n_slots=n_slots, max_len=64, page_size=8, kv_dtype="mxfp4",
            prefill_chunk=8, decode_backend="paged", sharding=sh))
        engines = eng.engines if isinstance(eng, ReplicatedEngine) else [eng]
        # warmup: one submit per replica — the placer round-robins exact
        # inventory ties, so every replica compiles its steps untimed
        for _ in engines:
            eng.submit(burst[0][1], 2, arrival_time=0.0)
        eng.drain()
        for e in engines:
            e.completed.clear()
            e.telemetry.reset(e)
        if isinstance(eng, ReplicatedEngine):
            eng.busy_s = [0.0] * len(engines)
        t0 = time.perf_counter()
        done, _ = run_workload(eng, burst, verbose=False)
        wall = time.perf_counter() - t0
        toks = sum(len(r.tokens) for r in done)
        out = [list(r.tokens) for r in sorted(done, key=lambda r: r.rid)]
        return eng, engines, out, toks, wall

    def _latency(e):  # p50 TTFT/TPOT from the engine's own tracer
        h = e.telemetry.finalize()["histograms"]
        rnd = lambda v: None if v is None else round(v, 4)
        return rnd(h["ttft_s"].get("p50")), rnd(h["tpot_s"].get("p50"))

    s_eng, _, s_out, s_toks, s_wall = run_one(None)
    s_ttft, s_tpot = _latency(s_eng)
    s_rate = round(s_toks / s_wall, 2)
    rep: dict = {
        "tp": tp, "dp": dp, "devices": n_dev,
        "single": {"decode_tok_per_s": s_rate, "ttft_p50_s": s_ttft,
                   "tpot_p50_s": s_tpot, "wall_sec": round(s_wall, 3)},
        "tp_run": None, "dp_run": None,
    }

    if tp > 1:
        t_eng, _, t_out, t_toks, t_wall = run_one(ShardingConfig(tp=tp, dp=1))
        t_ttft, t_tpot = _latency(t_eng)
        rep["tp_run"] = {
            "decode_tok_per_s": round(t_toks / t_wall, 2),
            "ttft_p50_s": t_ttft,
            "tpot_p50_s": t_tpot,
            "wall_sec": round(t_wall, 3),
            "pool_bytes_per_shard": t_eng.cache_bytes() // tp,
            "parity_vs_single": float(t_out == s_out),
            "ttft_p50_delta_s": None if (t_ttft is None or s_ttft is None)
            else round(t_ttft - s_ttft, 4),
            "tpot_p50_delta_s": None if (t_tpot is None or s_tpot is None)
            else round(t_tpot - s_tpot, 4),
        }

    if dp > 1:
        d_eng, d_engines, d_out, d_toks, d_wall = run_one(
            ShardingConfig(tp=tp, dp=dp))
        busy = [max(b, 1e-9) for b in d_eng.busy_s]
        per_replica = [
            round(sum(len(q.tokens) for q in e.completed) / busy[r], 2)
            for r, e in enumerate(d_engines)]
        agg = round(d_toks / max(busy), 2)
        # DP scaling is measured against ONE identical replica: when the
        # replicas are tp-sharded, that baseline is the tp_run rate (a tp=1
        # baseline would conflate TP per-tick overhead with DP scaling)
        base_rate = rep["tp_run"]["decode_tok_per_s"] if tp > 1 else s_rate
        rep["dp_run"] = {
            "aggregate_decode_tok_per_s": agg,
            "per_replica_tok_per_s": per_replica,
            "busy_s": [round(b, 3) for b in busy],
            "speedup_vs_one_replica": round(agg / max(base_rate, 1e-9), 2),
            "parity_vs_single": float(d_out == s_out),
            "pool_bytes_per_shard": d_eng.cache_bytes() // (tp * dp),
            "wall_sec": round(d_wall, 3),
        }
    return rep


def bench(arch: str = "qwen3-1.7b", reduced: bool = True, n_requests: int = 8,
          max_new: int = 8, n_slots: int = 4, verify_parity: bool = True,
          spec_k: int = 3, spec_proposer: str = "self",
          metrics_out: str | None = None, shared_prefix: bool = True,
          tp: int = 1, dp: int = 1, profile_out: str | None = None,
          family_archs: list[str] | None = None) -> dict:
    from repro.launch.serve_engine import run_workload
    from repro.serve import Engine, EngineConfig, SpecConfig
    from repro.serve.spec import aggregate_stats
    from repro.serve.telemetry import TelemetryConfig
    from repro.train.serve import greedy_generate

    cfg, model, params = _build(arch, reduced)
    workload = _workload(cfg, n_requests, max_new)
    report: dict = {"arch": cfg.name, "family": cfg.family,
                    "n_requests": n_requests, "max_new": max_new,
                    "n_slots": n_slots}

    def run_config(kv, backend, spec=None, primary=False):
        # the primary (mxfp4/paged) configuration streams its registry
        # snapshots and samples pool quantization health every tick; the
        # others keep the in-memory registry only (NullSink).  --profile-out
        # additionally records the primary run's Chrome trace (the per-call
        # cost lowering happens during warmup, outside the timed region)
        tcfg = TelemetryConfig(
            metrics_path=metrics_out if primary else None,
            emit_every_ticks=5 if primary and metrics_out else 0,
            quant_stride=1 if primary else 0,
            profile_trace_path=profile_out if primary else None)
        eng = Engine(model, params, EngineConfig(
            n_slots=n_slots, max_len=64, page_size=16, kv_dtype=kv,
            prefill_chunk=16, decode_backend=backend, spec=spec,
            telemetry=tcfg))
        # warmup: compile the step shapes outside the timed region, then drop
        # the warmup traffic from the registry (schema survives the reset)
        eng.submit(workload[0][1], 2, arrival_time=0.0)
        eng.drain()
        eng.completed.clear()
        eng.telemetry.reset(eng)

        t0 = time.perf_counter()
        done, _ = run_workload(eng, workload, verbose=False)
        wall = time.perf_counter() - t0
        toks = sum(len(r.tokens) for r in done)
        agg = aggregate_stats(done)
        snap = eng.telemetry.finalize()
        g = snap["gauges"]

        def rnd(v, nd=4):
            return None if v is None else round(v, nd)

        def hp(name, q, nd=4):  # empty histograms summarize without quantiles
            return rnd(snap["histograms"][name].get(q), nd)

        stats = {
            "tokens_per_sec": round(toks / wall, 2),
            "wall_sec": round(wall, 3),
            # virtual-clock latencies, derived by the request tracer
            "latency_p50_s": hp("request_latency_s", "p50"),
            "latency_p95_s": hp("request_latency_s", "p95"),
            "ttft_p50_s": hp("ttft_s", "p50"),
            "ttft_p95_s": hp("ttft_s", "p95"),
            "tpot_p50_s": hp("tpot_s", "p50"),
            "tpot_p95_s": hp("tpot_s", "p95"),
            "queue_wait_p50_s": hp("queue_wait_s", "p50"),
            # real wall time per tick section
            "decode_tick_p50_s": hp("decode_tick_s", "p50", 6),
            "decode_tick_p95_s": hp("decode_tick_s", "p95", 6),
            "verify_tick_p50_s": hp("verify_tick_s", "p50", 6),
            "prefill_tick_p50_s": hp("prefill_tick_s", "p50", 6),
            # pool pressure over the run
            "pool_occupancy_peak": rnd(g["pool_occupancy_peak"]),
            "free_page_watermark": g["pool_pages_free_watermark"],
            "tokens_per_decode_call": agg["tokens_per_decode_call"],
            "acceptance_rate": agg["acceptance_rate"],
            "cache_bytes": eng.cache_bytes(),
            "bits_per_kv_elem": round(eng.cache.bits_per_element(), 2)
            if eng.paged else 16.0,
            "decode_kv_bytes_per_step":
            decode_kv_bytes_per_step(eng.cache, backend) if eng.paged else 0,
            "prefill_kv_bytes_per_chunk":
            prefill_kv_bytes_per_chunk(eng.cache, backend) if eng.paged else 0,
        }
        if primary:
            # per-phase device cost accounting: AOT-lower the engine's jitted
            # steps AFTER the timed region and pair the HLO FLOPs/bytes with
            # the measured phase wall-time histograms (schema v4 "profile")
            from repro.serve.telemetry.profiling import profile_report
            stats["profile"] = profile_report(eng, snap)
        if primary and snap["counters"]["quant_health_samples"]:
            stats["quant_health"] = {
                "clip_fraction_k": rnd(g["kv_clip_fraction_k"], 6),
                "clip_fraction_v": rnd(g["kv_clip_fraction_v"], 6),
                "zero_fraction_k": rnd(g["kv_zero_fraction_k"], 6),
                "scale_hist_nonzero_bins":
                snap["binned"]["kv_scale_hist_k"]["nonzero_bins"],
                "scale_code_min": snap["binned"]["kv_scale_hist_k"]["bin_min"],
                "scale_code_max": snap["binned"]["kv_scale_hist_k"]["bin_max"],
            }
        return stats, {r.rid: list(r.tokens) for r in done}

    outputs: dict = {}
    report["decode_backends"] = {}
    for kv, backend in (("dense", "paged"), ("dense", "gather"),
                        ("mxfp4", "paged"), ("mxfp4", "gather")):
        stats, outputs[(kv, backend)] = run_config(
            kv, backend, primary=(kv == "mxfp4" and backend == "paged"))
        if kv == "mxfp4" and backend == "paged":
            report["profile"] = stats.pop("profile", None)
        if backend == "paged":  # primary numbers, keyed by cache dtype
            report[kv] = stats
        report["decode_backends"][f"{kv}/{backend}"] = {
            k: stats[k] for k in
            ("tokens_per_sec", "wall_sec", "decode_kv_bytes_per_step",
             "prefill_kv_bytes_per_chunk")}

    # -- speculative on/off A/B (paged-kernel decode, both pool dtypes) -----
    report["spec"] = {"k": spec_k, "proposer": spec_proposer}
    if cfg.family in ("dense", "moe"):
        sc = SpecConfig(k=spec_k, proposer=spec_proposer)
        for kv in ("dense", "mxfp4"):
            stats, out = run_config(kv, "paged", spec=sc)
            stats["parity_vs_nonspec"] = out == outputs[(kv, "paged")]
            report["spec"][kv] = stats

    # -- batched-prefill A/B: concurrent arrival burst, prefill-dominated ----
    # Every request lands at t=0 with max_new=1, so the run is ~all prefill:
    # the batched path advances EVERY prefilling slot in one jitted call per
    # tick (and attends over the packed pool), the gather oracle runs one
    # [1, C] call per slot per tick plus [1, 1] remainder singles.
    if cfg.family in ("dense", "moe"):
        prng = np.random.default_rng(1)
        plens = [int(prng.integers(9, 33)) for _ in range(n_requests)]
        burst = [(0.0, prng.integers(0, cfg.vocab_size, pl).astype(np.int32), 1)
                 for pl in plens]
        prefill_rep: dict = {"n_requests": n_requests,
                             "prompt_tokens": sum(plens)}
        pf_out = {}
        for backend in ("paged", "gather"):
            eng = Engine(model, params, EngineConfig(
                n_slots=n_slots, max_len=64, page_size=16, kv_dtype="dense",
                prefill_chunk=16, decode_backend=backend))
            eng.submit(burst[0][1], 1, arrival_time=0.0)
            eng.drain()
            eng.completed.clear()
            t0 = time.perf_counter()
            done, _ = run_workload(eng, burst, verbose=False)
            wall = time.perf_counter() - t0
            ttfts = [r.ttft() for r in done]
            prefill_rep[backend] = {
                "prefill_tok_per_s": round(sum(plens) / wall, 2),
                "wall_sec": round(wall, 3),
                "ttft_mean_s": round(sum(ttfts) / len(ttfts), 4),
                "ttft_p95_s": round(_pct(ttfts, 0.95), 4),
            }
            pf_out[backend] = {r.rid: list(r.tokens) for r in done}
        # batched paged prefill must reproduce the per-slot gather oracle
        # token-for-token on the dense pool
        prefill_rep["parity_paged_vs_gather"] = pf_out["paged"] == pf_out["gather"]
        db_ = report["decode_backends"]
        pp = db_["mxfp4/paged"]["prefill_kv_bytes_per_chunk"]
        prefill_rep["kv_bytes_per_chunk_mxfp4"] = {
            "paged": pp,
            "gather": db_["mxfp4/gather"]["prefill_kv_bytes_per_chunk"],
            "ratio_gather_over_paged": round(
                db_["mxfp4/gather"]["prefill_kv_bytes_per_chunk"] / pp, 2)
            if pp else None,
        }
        report["prefill"] = prefill_rep

    # -- shared-prefix A/B: radix prefix cache warm vs cold ------------------
    if shared_prefix and cfg.family in ("dense", "moe"):
        report["prefix_cache"] = _bench_shared_prefix(
            model, cfg, params, n_requests, n_slots)

    # -- multi-device A/B: TP-sharded pool/kernels + DP engine replicas ------
    report["sharding"] = _bench_sharded(
        model, cfg, params, n_requests, n_slots, tp, dp)

    # -- state-pool family A/B: pooled serving vs the dense-slot oracle ------
    report["families"] = (
        _bench_families(family_archs, n_requests, max_new, n_slots, reduced)
        if family_archs else None)

    report["cache_ratio"] = round(
        report["dense"]["cache_bytes"] / report["mxfp4"]["cache_bytes"], 2)
    db = report["decode_backends"]
    paged_bytes = db["mxfp4/paged"]["decode_kv_bytes_per_step"]
    report["decode_bytes_ratio_gather_over_paged"] = round(
        db["mxfp4/gather"]["decode_kv_bytes_per_step"] / paged_bytes, 2
    ) if paged_bytes else None  # dense-slot families: no paged decode path
    # the paged kernel must reproduce the gather oracle exactly when the pool
    # stores the compute dtype (same values, same online-softmax math)
    report["parity_paged_vs_gather_dense"] = (
        outputs[("dense", "paged")] == outputs[("dense", "gather")])

    if verify_parity:
        ref_toks = []
        for _, prompt, mn in workload:
            ref = greedy_generate(model, params, jnp.asarray(prompt)[None],
                                  max_new=mn, max_len=int(prompt.size) + mn)
            ref_toks.append(ref[0].tolist())
        # rids are assigned in submission (arrival) order; the warmup request
        # is cleared, so sorted rids map 1:1 onto the workload — minus the
        # warmup's rid 0 offset
        dense_out = outputs[("dense", "paged")]
        eng_toks = [dense_out[rid] for rid in sorted(dense_out)]
        report["parity_dense_vs_sequential"] = eng_toks == ref_toks

    return report


def make_bench_baseline(rep: dict) -> dict:
    """Benchmark report → the schema-versioned ``BENCH_serve.json`` document
    (``telemetry.schema.BENCH_SCHEMA``).  Null-able fields go null on
    dense-slot families / configurations with nothing to measure."""
    from repro.serve.telemetry.schema import BENCH_SCHEMA

    m, d, db = rep["mxfp4"], rep["dense"], rep["decode_backends"]
    sp_m = rep.get("spec", {}).get("mxfp4")
    qh = m.get("quant_health", {})
    pf = rep.get("prefill", {}).get("kv_bytes_per_chunk_mxfp4", {})
    px = rep.get("prefix_cache", {})
    px_w, px_c = px.get("warm", {}), px.get("cold", {})
    return {
        "schema": BENCH_SCHEMA,
        "arch": rep["arch"],
        "family": rep["family"],
        "config": {"n_requests": rep["n_requests"], "max_new": rep["max_new"],
                   "n_slots": rep["n_slots"]},
        "throughput": {
            "mxfp4_paged_tok_per_s": m["tokens_per_sec"],
            "dense_paged_tok_per_s": d["tokens_per_sec"],
            "mxfp4_gather_tok_per_s": db["mxfp4/gather"]["tokens_per_sec"],
        },
        "latency": {
            "ttft_p50_s": m["ttft_p50_s"], "ttft_p95_s": m["ttft_p95_s"],
            "tpot_p50_s": m["tpot_p50_s"], "tpot_p95_s": m["tpot_p95_s"],
            "latency_p50_s": m["latency_p50_s"],
            "latency_p95_s": m["latency_p95_s"],
            "queue_wait_p50_s": m["queue_wait_p50_s"],
        },
        "tick": {
            "decode_p50_s": m["decode_tick_p50_s"],
            "decode_p95_s": m["decode_tick_p95_s"],
            "prefill_p50_s": m["prefill_tick_p50_s"],
        },
        "kv": {
            "cache_bytes_dense": d["cache_bytes"],
            "cache_bytes_mxfp4": m["cache_bytes"],
            "cache_ratio": rep["cache_ratio"],
            "bits_per_elem_mxfp4": m["bits_per_kv_elem"],
            "decode_bytes_ratio_gather_over_paged":
            rep["decode_bytes_ratio_gather_over_paged"],
            "prefill_bytes_ratio_gather_over_paged":
            pf.get("ratio_gather_over_paged"),
        },
        "pool": {
            "occupancy_peak": m["pool_occupancy_peak"] or 0,
            "free_page_watermark": m["free_page_watermark"] or 0,
        },
        "spec": {
            "k": rep["spec"]["k"],
            "proposer": rep["spec"]["proposer"],
            "acceptance_rate": sp_m["acceptance_rate"] if sp_m else None,
            "tokens_per_decode_call":
            sp_m["tokens_per_decode_call"] if sp_m else None,
        },
        "quant_health": {
            "clip_fraction_k": qh.get("clip_fraction_k"),
            "clip_fraction_v": qh.get("clip_fraction_v"),
            "zero_fraction_k": qh.get("zero_fraction_k"),
            "scale_hist_nonzero_bins": qh.get("scale_hist_nonzero_bins"),
            "scale_code_min": qh.get("scale_code_min"),
            "scale_code_max": qh.get("scale_code_max"),
        },
        "prefix": {
            "hit_rate": px.get("hit_rate"),
            "shared_tokens": px.get("shared_tokens"),
            "cow_pages": px.get("cow_pages"),
            "warm_ttft_mean_s": px_w.get("ttft_mean_s"),
            "cold_ttft_mean_s": px_c.get("ttft_mean_s"),
            "warm_ttft_p95_s": px_w.get("ttft_p95_s"),
            "cold_ttft_p95_s": px_c.get("ttft_p95_s"),
            "warm_prefill_tok_per_s": px_w.get("prefill_tok_per_s"),
            "cold_prefill_tok_per_s": px_c.get("prefill_tok_per_s"),
        },
        # null on single-device runs; the dict from _bench_sharded already
        # matches the schema's nullable "sharding" block
        "sharding": rep.get("sharding"),
        # per-phase cost accounting of the primary run (profiling.py) —
        # already shaped like the schema's nullable "profile" block
        "profile": rep.get("profile"),
        # state-pool family A/B (--family); already shaped like the schema's
        # nullable "families" map
        "families": rep.get("families"),
    }


def write_bench(rep: dict, path=BENCH_PATH) -> dict:
    """Validate + persist the baseline; raises before writing anything if
    the document doesn't conform to BENCH_SCHEMA."""
    from repro.serve.telemetry.schema import validate_bench

    doc = make_bench_baseline(rep)
    errors = validate_bench(doc)
    if errors:
        raise ValueError("refusing to write invalid BENCH_serve.json:\n  "
                         + "\n  ".join(errors))
    with open(path, "w") as fh:
        json.dump(doc, fh, indent=2)
        fh.write("\n")
    return doc


def run():
    """benchmarks.run driver hook → (name, us_per_call, derived) rows.
    Also persists the BENCH_serve.json baseline."""
    rep = bench()
    write_bench(rep)
    per_tok = max(rep["n_requests"] * rep["max_new"], 1)
    db = rep["decode_backends"]
    rows = [
        ("serve_bench_baseline", 0.0, str(BENCH_PATH)),
        ("serve_fp4_tok_per_s", rep["mxfp4"]["wall_sec"] * 1e6 / per_tok,
         f"{rep['mxfp4']['tokens_per_sec']}tok/s"),
        ("serve_dense_tok_per_s", rep["dense"]["wall_sec"] * 1e6 / per_tok,
         f"{rep['dense']['tokens_per_sec']}tok/s"),
        ("serve_gather_decode_tok_per_s",
         db["mxfp4/gather"]["wall_sec"] * 1e6 / per_tok,
         f"{db['mxfp4/gather']['tokens_per_sec']}tok/s"),
        ("serve_cache_ratio", 0.0, f"{rep['cache_ratio']}x"),
        ("serve_decode_bytes_ratio", 0.0,
         f"{rep['decode_bytes_ratio_gather_over_paged']}x"),
        ("serve_parity", 0.0, str(rep.get("parity_dense_vs_sequential", "skipped"))),
        ("serve_parity_paged_vs_gather", 0.0,
         str(rep["parity_paged_vs_gather_dense"])),
    ]
    if "mxfp4" in rep.get("spec", {}):
        sp = rep["spec"]["mxfp4"]
        rows += [
            ("serve_spec_tok_per_decode_call", 0.0,
             f"{sp['tokens_per_decode_call']}tok/call"),
            ("serve_spec_acceptance", 0.0, f"{sp['acceptance_rate']}"),
            ("serve_spec_parity", 0.0, str(sp["parity_vs_nonspec"])),
        ]
    if "prefill" in rep:
        pf = rep["prefill"]
        rows += [
            ("serve_prefill_tok_per_s", 0.0,
             f"{pf['paged']['prefill_tok_per_s']}tok/s"),
            ("serve_prefill_ttft_mean", 0.0,
             f"{pf['paged']['ttft_mean_s']}s"),
            ("serve_prefill_bytes_ratio", 0.0,
             f"{pf['kv_bytes_per_chunk_mxfp4']['ratio_gather_over_paged']}x"),
            ("serve_prefill_parity", 0.0, str(pf["parity_paged_vs_gather"])),
        ]
    if "prefix_cache" in rep:
        px = rep["prefix_cache"]
        rows += [
            ("serve_prefix_hit_rate", 0.0, f"{px['hit_rate']}"),
            ("serve_prefix_warm_ttft_mean", 0.0, f"{px['warm']['ttft_mean_s']}s"),
            ("serve_prefix_cold_ttft_mean", 0.0, f"{px['cold']['ttft_mean_s']}s"),
            ("serve_prefix_cow_pages", 0.0, f"{px['cow_pages']}"),
            ("serve_prefix_parity", 0.0, str(px["parity_warm_vs_cold"])),
            ("serve_prefix_no_leaks", 0.0, str(px["no_leaks"])),
        ]
    if rep.get("families"):
        for slug, fb in rep["families"].items():
            rows += [
                (f"serve_family_{slug}_parity", 0.0,
                 str(fb["token_parity"] == 1.0)),
                (f"serve_family_{slug}_state_bytes_ratio", 0.0,
                 f"{fb['state_bytes_ratio']}x"),
            ]
    if rep.get("sharding"):
        sh = rep["sharding"]
        if sh["tp_run"]:
            rows += [
                ("serve_tp_parity", 0.0,
                 str(sh["tp_run"]["parity_vs_single"] == 1.0)),
                ("serve_tp_pool_bytes_per_shard", 0.0,
                 f"{sh['tp_run']['pool_bytes_per_shard']}"),
            ]
        if sh["dp_run"]:
            rows += [
                ("serve_dp_parity", 0.0,
                 str(sh["dp_run"]["parity_vs_single"] == 1.0)),
                ("serve_dp_aggregate_tok_per_s", 0.0,
                 f"{sh['dp_run']['aggregate_decode_tok_per_s']}tok/s"),
                ("serve_dp_speedup", 0.0,
                 f"{sh['dp_run']['speedup_vs_one_replica']}x"),
            ]
    return rows


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen3-1.7b")
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--max-new", type=int, default=8)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--no-parity", action="store_true")
    ap.add_argument("--spec-k", type=int, default=3,
                    help="drafted tokens per verify call in the spec A/B")
    ap.add_argument("--spec-proposer", default="self",
                    choices=["self", "ngram"],
                    help="proposer for the spec A/B ('self' = parity oracle)")
    ap.add_argument("--shared-prefix", action="store_true",
                    help="run the shared-prefix A/B section (radix prefix "
                         "cache warm vs cold: hit rate, prefill tok/s, "
                         "mean/p95 TTFT); implied by --smoke")
    ap.add_argument("--smoke", action="store_true",
                    help="small fixed workload + assert the paged-kernel "
                         "decode metrics, spec-mode parity, "
                         "tokens-per-decode-call > 1, prefix-cache "
                         "hit/TTFT/parity/leak checks, and the telemetry "
                         "stream/baseline artifacts (CI)")
    ap.add_argument("--tp", type=int, default=1,
                    help="tensor-parallel degree for the sharding A/B "
                         "(shards the packed KV pool + paged-attention grid "
                         "over the 'model' mesh axis; needs tp*dp devices — "
                         "force them on CPU with "
                         "XLA_FLAGS=--xla_force_host_platform_device_count=8)")
    ap.add_argument("--dp", type=int, default=1,
                    help="data-parallel engine-replica count for the "
                         "sharding A/B (independent replicas on disjoint "
                         "device groups)")
    ap.add_argument("--family", action="append", dest="family_archs",
                    default=None, metavar="ARCH",
                    help="repeatable: run the state-pool A/B for this "
                         "non-attention arch (pooled engine vs dense-slot "
                         "oracle: token parity, tok/s, state bytes/step); "
                         "fills the schema-v5 'families' block (smoke "
                         "default: falcon-mamba-7b + whisper-tiny)")
    ap.add_argument("--metrics-out", default=None,
                    help="stream the primary run's registry snapshots as "
                         "JSON-lines to this path (smoke default: "
                         "benchmarks/out/metrics_serve.jsonl)")
    ap.add_argument("--profile-out", default=None,
                    help="write the primary run's Chrome trace-event JSON "
                         "(open in Perfetto / chrome://tracing) to this "
                         "path (smoke default: benchmarks/out/"
                         "trace_serve.json)")
    ap.add_argument("--bench-out", default=str(BENCH_PATH),
                    help="where to write the schema-versioned benchmark "
                         "baseline ('' to skip)")
    args = ap.parse_args()
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    if args.smoke:
        args.reduced, args.requests, args.max_new, args.slots = True, 4, 4, 2
        args.shared_prefix = True
        if args.family_archs is None:
            args.family_archs = ["falcon-mamba-7b", "whisper-tiny"]
        out_dir = REPO_ROOT / "benchmarks" / "out"
        out_dir.mkdir(parents=True, exist_ok=True)
        if args.metrics_out is None:
            args.metrics_out = str(out_dir / "metrics_serve.jsonl")
        if args.profile_out is None:
            args.profile_out = str(out_dir / "trace_serve.json")
    rep = bench(args.arch, args.reduced, args.requests, args.max_new,
                args.slots, verify_parity=not args.no_parity,
                spec_k=args.spec_k, spec_proposer=args.spec_proposer,
                metrics_out=args.metrics_out, shared_prefix=args.shared_prefix,
                tp=args.tp, dp=args.dp, profile_out=args.profile_out,
                family_archs=args.family_archs)
    print(json.dumps(rep, indent=2))
    if (args.tp > 1 or args.dp > 1) and rep.get("sharding") is None:
        print(f"sharding section skipped: {args.tp * args.dp} devices needed, "
              f"{len(jax.devices())} visible (set XLA_FLAGS="
              "--xla_force_host_platform_device_count=8)", file=sys.stderr)
    if args.bench_out:
        write_bench(rep, args.bench_out)
        print(f"wrote {args.bench_out}", file=sys.stderr)
    if args.smoke:
        from repro.serve.telemetry.schema import (validate_bench_file,
                                                  validate_metrics_file)
        # the telemetry stream must exist, parse, and carry real signal
        n_snaps = validate_metrics_file(args.metrics_out)
        assert n_snaps >= 1, "empty metrics stream"
        m = rep["mxfp4"]
        assert m["pool_occupancy_peak"] > 0, "pool occupancy never nonzero"
        assert m["decode_tick_p50_s"] > 0, "no decode tick latency recorded"
        assert m["ttft_p50_s"] > 0 and m["ttft_p95_s"] > 0
        assert m["tpot_p50_s"] is not None and m["tpot_p50_s"] > 0
        assert m["latency_p50_s"] > 0
        qh = m.get("quant_health")
        assert qh is not None, "quant health never sampled on the mxfp4 pool"
        assert qh["scale_hist_nonzero_bins"] >= 1
        assert qh["clip_fraction_k"] is not None and qh["clip_fraction_k"] >= 0
        # per-phase cost accounting: the paged primary run must produce a
        # non-null profile block with real decode FLOPs/bytes and a
        # utilization in (0, 1] territory (interpret-mode caveat: the Pallas
        # kernel's internals are undercounted, never zero)
        prof = rep.get("profile")
        assert prof is not None, "profile block missing on a paged family"
        assert prof["decode"] is not None
        assert prof["decode"]["flops_per_call"] > 0
        assert prof["decode"]["hbm_bytes_per_call"] > 0
        assert prof["decode"]["roofline_util_mean"] > 0
        # the Chrome trace must load structurally and carry tick-phase,
        # request-lifecycle, and compile events
        if args.profile_out:
            from repro.serve.telemetry.profiling import validate_trace_file
            tdoc = validate_trace_file(args.profile_out)
            cats = {e.get("cat") for e in tdoc["traceEvents"]}
            assert {"tick", "phase", "request"} <= cats, \
                f"trace missing span categories: {cats}"
        # the persisted baseline must round-trip its schema validator
        doc = validate_bench_file(args.bench_out)
        assert doc["spec"]["acceptance_rate"] is None or \
            0.0 <= doc["spec"]["acceptance_rate"] <= 1.0
        for key in ("mxfp4/paged", "mxfp4/gather", "dense/paged"):
            assert key in rep["decode_backends"], f"missing decode metrics {key}"
            assert rep["decode_backends"][key]["decode_kv_bytes_per_step"] > 0
        assert rep["decode_bytes_ratio_gather_over_paged"] > 1.0
        # batched paged prefill: token-exact vs the per-slot gather oracle,
        # O(packed KV) per chunk, and real throughput/TTFT numbers reported
        # (section exists only for paged families, like the spec A/B)
        pf = rep.get("prefill")
        if pf is not None:
            assert pf["parity_paged_vs_gather"], \
                "PARITY FAILURE: batched paged prefill != per-slot gather prefill"
            assert pf["kv_bytes_per_chunk_mxfp4"]["ratio_gather_over_paged"] > 1.0
            for backend in ("paged", "gather"):
                assert pf[backend]["prefill_tok_per_s"] > 0
                assert pf[backend]["ttft_mean_s"] > 0
        # shared-prefix section: the radix cache must actually hit, COW must
        # be exercised (the pure-prefix request), warm admission must beat
        # cold TTFT strictly, and no pool page may leak past all retires
        px = rep.get("prefix_cache")
        if px is not None:
            assert px["parity_warm_vs_cold"], \
                "PARITY FAILURE: prefix-cached engine != cold engine"
            assert px["hit_rate"] > 0, "prefix cache never hit"
            assert px["shared_tokens"] > 0, "no prompt tokens were aliased"
            assert px["cow_pages"] >= 1, "full-match COW never exercised"
            assert px["warm"]["ttft_mean_s"] < px["cold"]["ttft_mean_s"], \
                "prefix cache did not improve mean TTFT"
            assert px["no_leaks"], "pool pages leaked by the prefix cache"
        # sharding A/B: TP and DP engines must be token-exact vs the
        # single-device engine, and dp >= 2 replicas must actually scale —
        # aggregate decode throughput >= 1.5x the single-replica rate
        sh = rep.get("sharding")
        if sh is not None:
            if sh["tp_run"] is not None:
                assert sh["tp_run"]["parity_vs_single"] == 1.0, \
                    "PARITY FAILURE: TP-sharded engine != single-device engine"
                assert sh["tp_run"]["pool_bytes_per_shard"] > 0
            if sh["dp_run"] is not None:
                assert sh["dp_run"]["parity_vs_single"] == 1.0, \
                    "PARITY FAILURE: DP-replicated engine != single-device engine"
                assert sh["dp_run"]["speedup_vs_one_replica"] >= 1.5, \
                    "DP aggregate decode throughput below 1.5x one replica"
        # state-pool family A/B: pooled serving must be token-exact vs the
        # dense-slot oracle on every benchmarked family (dense planes), and
        # the packed pool must cut per-decode-step state traffic >= 4x on at
        # least the pure-SSM family (f32 recurrent state packs to 4.25-bit)
        fams = rep.get("families")
        if fams is not None:
            for slug, fb in fams.items():
                assert fb["token_parity"] == 1.0, \
                    f"PARITY FAILURE: state-pool {slug} != dense-slot oracle"
                assert fb["state_bytes_ratio"] > 1.0, slug
            if "falcon_mamba_7b" in fams:
                assert fams["falcon_mamba_7b"]["state_bytes_ratio"] >= 4.0, \
                    "SSM state bytes/step reduction below 4x vs dense slots"
        # non-spec decode emits exactly one token per batched call
        assert rep["mxfp4"]["tokens_per_decode_call"] == 1.0
        # spec A/B only exists for paged (dense/moe) families
        for kv in ("dense", "mxfp4"):
            if kv not in rep["spec"]:
                continue
            sp = rep["spec"][kv]
            assert sp["parity_vs_nonspec"], \
                f"PARITY FAILURE: spec({kv}) != non-spec engine"
            assert sp["tokens_per_decode_call"] > 1.0, \
                f"spec({kv}) tokens_per_decode_call not > 1"
            assert 0.0 <= sp["acceptance_rate"] <= 1.0
    if rep.get("parity_dense_vs_sequential") is False:
        raise SystemExit("PARITY FAILURE: dense-cache engine != sequential greedy")
    if not rep["parity_paged_vs_gather_dense"]:
        raise SystemExit("PARITY FAILURE: paged-kernel decode != gather-dense decode")
    if rep.get("prefill", {}).get("parity_paged_vs_gather") is False:
        raise SystemExit("PARITY FAILURE: batched paged prefill != gather prefill")
    if rep.get("prefix_cache", {}).get("parity_warm_vs_cold") is False:
        raise SystemExit("PARITY FAILURE: prefix-cached engine != cold engine")
    if "dense" in rep["spec"] and not rep["spec"]["dense"]["parity_vs_nonspec"]:
        raise SystemExit("PARITY FAILURE: speculative engine != non-speculative engine")
    if rep["cache_ratio"] < 3.0:
        raise SystemExit(f"cache ratio {rep['cache_ratio']} < 3x")


if __name__ == "__main__":
    main()
