"""Serving example: the continuous-batching engine on mixed prompt lengths
with staggered arrivals, for any architecture family.

  PYTHONPATH=src python examples/serve_batched.py                      # dense arch, FP4 KV pages
  PYTHONPATH=src python examples/serve_batched.py --kv dense           # parity mode
  PYTHONPATH=src python examples/serve_batched.py --arch falcon-mamba-7b   # SSM → dense slots
  PYTHONPATH=src python examples/serve_batched.py --spec ngram --spec-k 4  # speculative decoding

Requests arrive over the first few engine steps (not all at once), prompts
range from 6 to 30 tokens, and there are more requests than decode slots —
so the run exercises queueing, chunked prefill riding alongside in-flight
decodes, retirement, and slot/page recycling.  The dense-cache engine output
is checked token-for-token against sequential ``greedy_generate``.
"""

import argparse
import contextlib
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_reduced_config
from repro.models import build_model
from repro.serve import Engine, EngineConfig, SpecConfig, TelemetryConfig
from repro.serve.spec import aggregate_stats
from repro.train.serve import greedy_generate


def make_extra(cfg, key):
    if cfg.family == "encdec":
        return {"source_embeds": jax.random.normal(
            key, (1, cfg.max_source_len, cfg.d_model), jnp.bfloat16)}
    if cfg.family == "vlm":
        return {"image_embeds": jax.random.normal(
            key, (1, cfg.num_image_tokens, cfg.d_model), jnp.bfloat16)}
    return None


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen3-1.7b")
    ap.add_argument("--requests", type=int, default=6)
    ap.add_argument("--slots", type=int, default=3)
    ap.add_argument("--max-new", type=int, default=10)
    ap.add_argument("--kv", default="mxfp4", choices=["mxfp4", "dense"])
    ap.add_argument("--prefix-cache", action="store_true",
                    help="paged families: radix prefix cache with "
                         "copy-on-write — prompts get a shared 16-token "
                         "system prefix so later admissions alias its pages "
                         "and prefill only their unique tail")
    ap.add_argument("--spec", default=None, choices=["self", "ngram"],
                    help="speculative decoding proposer (paged families)")
    ap.add_argument("--spec-k", type=int, default=4)
    ap.add_argument("--metrics-out", default=None,
                    help="stream telemetry snapshots as JSON-lines here")
    ap.add_argument("--trace-out", default=None,
                    help="write per-request span traces as JSON-lines here")
    ap.add_argument("--profile-out", default=None, metavar="DIR",
                    help="profile the run: roofline/bandwidth gauges + a "
                         "JAX profiler trace under DIR (serve.* spans and "
                         "device ops; open its perfetto_trace.json.gz in "
                         "Perfetto)")
    args = ap.parse_args()

    cfg = get_reduced_config(args.arch)
    model = build_model(cfg)
    key = jax.random.PRNGKey(0)
    params = model.init(key)
    rng = np.random.default_rng(0)
    extra = make_extra(cfg, key)

    spec = (SpecConfig(k=args.spec_k, proposer=args.spec)
            if args.spec is not None else None)
    engine = Engine(model, params, EngineConfig(
        n_slots=args.slots, max_len=48, page_size=8, kv_dtype=args.kv,
        prefill_chunk=8, prefix_cache=args.prefix_cache, spec=spec,
        telemetry=TelemetryConfig(metrics_path=args.metrics_out,
                                  trace_path=args.trace_out,
                                  profile=args.profile_out is not None,
                                  quant_stride=4)))

    # mixed prompt lengths, arrivals staggered over the first steps
    prompts = [rng.integers(0, cfg.vocab_size, int(rng.integers(6, 31)))
               .astype(np.int32) for _ in range(args.requests)]
    if args.prefix_cache:
        # shared system prefix (two full pages): the first request to retire
        # publishes its pages into the radix index, later admissions alias
        # them and prefill only their unique tail
        system = rng.integers(0, cfg.vocab_size, 16).astype(np.int32)
        prompts = [np.concatenate([system, p]) for p in prompts]
    arrive_at_step = sorted(int(rng.integers(0, 4)) for _ in range(args.requests))

    trace = (jax.profiler.trace(args.profile_out, create_perfetto_trace=True)
             if args.profile_out else contextlib.nullcontext())
    t0 = time.time()
    handles, next_req = [], 0
    with trace:
        while next_req < len(prompts) or engine.sched.pending:
            while next_req < len(prompts) and arrive_at_step[next_req] <= engine.steps:
                handles.append(engine.submit(prompts[next_req], args.max_new, extra=extra,
                                             arrival_time=float(engine.steps)))
                next_req += 1
            info = engine.step(now=float(engine.steps))
            print(f"step {info['step']:3d}: queued={info['queued']} "
                  f"prefill={info['prefilling']} decode={info['decoding']}")
    dt = time.time() - t0

    total = sum(len(h.tokens) for h in handles)
    print(f"\n{cfg.name} [{cfg.family}] kv={args.kv if engine.paged else 'dense-slots'}: "
          f"{len(handles)} requests ({min(p.size for p in prompts)}–"
          f"{max(p.size for p in prompts)} prompt tokens) → {total} tokens "
          f"in {dt:.2f}s ({total / dt:.1f} tok/s)")
    # the engine's own telemetry replaces hand-rolled stats: queue depths,
    # TTFT/TPOT percentiles, pool occupancy and FP4 clip/scale health all
    # came along for free with the run
    engine.telemetry.finalize()
    print(engine.telemetry.summary())
    if engine.prefix is not None:
        c = engine.telemetry.registry.counter
        print(f"prefix cache: {c('prefix_hit_requests').value}/"
              f"{c('prefix_lookups').value} admissions hit, "
              f"{c('prefix_shared_tokens').value} prompt tokens aliased, "
              f"{c('prefix_cow_pages').value} COW pages, "
              f"{engine.prefix.cached_pages()} pages cached")
    for label, path in (("metrics", args.metrics_out), ("traces", args.trace_out),
                        ("profile trace", args.profile_out)):
        if path:
            print(f"{label} → {path}")
    if spec is not None:
        agg = aggregate_stats(handles)
        print(f"spec[{args.spec}, k={args.spec_k}]: "
              f"{agg['tokens_per_decode_call']} tokens/verify-call, "
              f"acceptance {agg['acceptance_rate']}")
    for h in handles[:3]:
        print(f"  req {h.rid}: prompt[{h.prompt_len}] -> {h.tokens}")

    if args.kv == "dense" or not engine.paged:
        ok = all(
            h.tokens == greedy_generate(
                model, params, jnp.asarray(h.prompt)[None], max_new=args.max_new,
                max_len=h.prompt_len + args.max_new, extra=extra)[0].tolist()
            for h in handles)
        print("token-for-token parity vs sequential greedy_generate:", ok)


if __name__ == "__main__":
    main()
