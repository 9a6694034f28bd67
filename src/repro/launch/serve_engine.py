"""Continuous-batching serving launcher: Poisson arrival workload.

``python -m repro.launch.serve_engine --arch qwen3-1.7b --reduced --requests 12
--rate 4 --kv mxfp4`` samples request arrival times from a Poisson process
(exponential inter-arrival gaps), prompt lengths uniformly from
``[--min-prompt, --max-prompt]``, and drives the engine on a virtual clock:
each ``Engine.step`` advances time by its measured wall duration, and
requests are submitted the moment the clock passes their arrival time —
so queueing behaviour is faithful even though steps are synchronous.

``--spec {self,ngram,draft} --spec-k 4`` turns on speculative decoding
(paged families; ``--draft-arch`` selects the draft model for the ``draft``
proposer) and reports tokens-per-verify-call and draft acceptance;
``--temperature/--top-k/--top-p/--sample-seed`` enable per-request sampling.

Observability: ``--metrics-out m.jsonl`` streams registry snapshots as
JSON-lines, ``--trace-out t.jsonl`` writes one line per retired request
(spans + derived TTFT/TPOT), ``--quant-stride N`` samples the MXFP4 pool's
clip/scale health every N ticks, ``--profile-out DIR`` records the run as a
JAX profiler trace (the engine's ``serve.*`` spans beside the device ops, on
one clock; ``DIR/plugins/profile/<run>/`` holds the ``.xplane.pb`` and a
``perfetto_trace.json.gz``), and the run ends with the telemetry summary table
(see ``serve/README.md#observability``).
"""

from __future__ import annotations

import argparse
import contextlib
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_config, get_reduced_config
from repro.distributed.context import activate_mesh
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import make_local_mesh
from repro.models import build_model
from repro.serve import (Engine, EngineConfig, SamplingParams, SpecConfig,
                         TelemetryConfig)
from repro.serve.spec import aggregate_stats


def make_extra(cfg, key, batch: int = 1):
    if cfg.family == "encdec":
        return {"source_embeds": jax.random.normal(
            key, (batch, cfg.max_source_len, cfg.d_model), jnp.bfloat16)}
    if cfg.family == "vlm":
        return {"image_embeds": jax.random.normal(
            key, (batch, cfg.num_image_tokens, cfg.d_model), jnp.bfloat16)}
    return None


def poisson_workload(rng: np.random.Generator, n: int, rate: float,
                     min_prompt: int, max_prompt: int, max_new: int, vocab: int):
    """[(arrival_time, prompt, max_new)] with exponential inter-arrival gaps."""
    t, out = 0.0, []
    for _ in range(n):
        t += rng.exponential(1.0 / rate)
        plen = int(rng.integers(min_prompt, max_prompt + 1))
        out.append((t, rng.integers(0, vocab, plen).astype(np.int32), max_new))
    return out


def run_workload(engine: Engine, workload, extra=None, verbose: bool = True,
                 sampling=None):
    """Drive the engine on a virtual clock; returns (requests, elapsed)."""
    pending = list(workload)
    clock, t0 = 0.0, time.perf_counter()
    while pending or engine.sched.pending:
        while pending and pending[0][0] <= clock:
            at, prompt, max_new = pending.pop(0)
            engine.submit(prompt, max_new, extra=extra, arrival_time=at,
                          sampling=sampling)
        if not engine.sched.pending:  # idle gap: jump to the next arrival
            clock = pending[0][0]
            continue
        s0 = time.perf_counter()
        info = engine.step(now=clock)
        clock += time.perf_counter() - s0
        if verbose and info["step"] % 20 == 0:
            print(f"  step {info['step']:4d} t={clock:7.2f}s queued={info['queued']} "
                  f"prefill={info['prefilling']} decode={info['decoding']}")
    return engine.completed, time.perf_counter() - t0


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen3-1.7b")
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--requests", type=int, default=12)
    ap.add_argument("--rate", type=float, default=4.0, help="arrivals per second")
    ap.add_argument("--min-prompt", type=int, default=8)
    ap.add_argument("--max-prompt", type=int, default=32)
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--page-size", type=int, default=16)
    ap.add_argument("--max-len", type=int, default=64)
    ap.add_argument("--prefill-chunk", type=int, default=16)
    ap.add_argument("--kv", default="mxfp4", choices=["mxfp4", "dense"])
    ap.add_argument("--decode-backend", default=None,
                    choices=["paged", "gather", "statepool", "dense_slots"],
                    help="paged families: fused paged-attention kernel "
                         "(default) vs gather-dequantize oracle; state "
                         "families (ssm/hybrid/encdec/vlm): unified packed "
                         "state pool (default) vs dense-slot oracle")
    ap.add_argument("--prefix-cache", action="store_true",
                    help="paged families: radix prefix cache — admissions "
                         "alias pages of previously-served shared prefixes "
                         "(copy-on-write, LRU-evicted) and prefill only the "
                         "unshared tail; token-exact vs the non-sharing "
                         "engine")
    ap.add_argument("--debug-cache", action="store_true",
                    help="run the PagedCache invariant checker after every "
                         "pool mutation (slow; refcount/conservation audit)")
    ap.add_argument("--method", default="quartet")
    ap.add_argument("--seed", type=int, default=0)
    # speculative decoding (paged families)
    ap.add_argument("--spec", default=None,
                    choices=["self", "ngram", "draft"],
                    help="enable speculative decoding with this proposer")
    ap.add_argument("--spec-k", type=int, default=4,
                    help="drafted tokens per verify call")
    ap.add_argument("--ngram", type=int, default=2,
                    help="ngram proposer: suffix length to match")
    ap.add_argument("--draft-arch", default=None,
                    help="draft proposer: registry arch of the draft model")
    # per-request sampling (greedy argmax when temperature is 0)
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--top-k", type=int, default=0)
    ap.add_argument("--top-p", type=float, default=1.0)
    ap.add_argument("--sample-seed", type=int, default=0)
    # observability (repro.serve.telemetry)
    ap.add_argument("--metrics-out", default=None,
                    help="stream registry snapshots as JSON-lines here")
    ap.add_argument("--trace-out", default=None,
                    help="write per-request span traces as JSON-lines here")
    ap.add_argument("--quant-stride", type=int, default=0,
                    help="sample MXFP4 pool clip/scale health every N ticks "
                         "(0 = off)")
    ap.add_argument("--profile-out", default=None, metavar="DIR",
                    help="profile the run: per-phase roofline/bandwidth "
                         "gauges + a JAX profiler trace written under DIR "
                         "(serve.* spans and device ops on one clock; open "
                         "its perfetto_trace.json.gz in Perfetto)")
    args = ap.parse_args()
    enable_compile_cache()

    cfg = (get_reduced_config(args.arch) if args.reduced else get_config(args.arch))
    model = build_model(cfg)
    key = jax.random.PRNGKey(args.seed)
    params = model.init(key)
    rng = np.random.default_rng(args.seed)
    workload = poisson_workload(rng, args.requests, args.rate, args.min_prompt,
                                args.max_prompt, args.max_new, cfg.vocab_size)

    spec = None
    if args.spec is not None:
        spec = SpecConfig(k=args.spec_k, proposer=args.spec, ngram=args.ngram,
                          draft_arch=args.draft_arch)
    sampling = None
    if args.temperature > 0:
        sampling = SamplingParams(temperature=args.temperature, top_k=args.top_k,
                                  top_p=args.top_p, seed=args.sample_seed)
    elif args.top_k or args.top_p < 1.0 or args.sample_seed:
        ap.error("--top-k/--top-p/--sample-seed require --temperature > 0 "
                 "(temperature 0 is greedy argmax and ignores them)")

    telemetry = TelemetryConfig(metrics_path=args.metrics_out,
                                trace_path=args.trace_out,
                                quant_stride=args.quant_stride,
                                profile=args.profile_out is not None)
    trace = (jax.profiler.trace(args.profile_out, create_perfetto_trace=True)
             if args.profile_out else contextlib.nullcontext())
    with trace, activate_mesh(make_local_mesh()):
        engine = Engine(model, params, EngineConfig(
            n_slots=args.slots, max_len=args.max_len, page_size=args.page_size,
            kv_dtype=args.kv, prefill_chunk=args.prefill_chunk, method=args.method,
            decode_backend=args.decode_backend, prefix_cache=args.prefix_cache,
            debug_cache=args.debug_cache, spec=spec, telemetry=telemetry))
        done, elapsed = run_workload(engine, workload, extra=make_extra(cfg, key),
                                     sampling=sampling)

    # final telemetry summary table (the registry + tracer collected every
    # number the old hand-rolled prints derived from request objects)
    total_tokens = sum(len(r.tokens) for r in done)
    engine.telemetry.finalize()
    print(f"\n{cfg.name} [{cfg.family}] "
          f"kv={'dense-slots' if engine.backend == 'dense_slots' else args.kv}"
          f" decode={engine.decode_backend} slots={args.slots}"
          + (f" spec={args.spec}(k={args.spec_k})" if spec else ""))
    print(f"  {len(done)} requests, {total_tokens} tokens in {elapsed:.2f}s wall "
          f"→ {total_tokens / elapsed:.1f} tok/s")
    print(engine.telemetry.summary())
    if spec is not None:
        agg = aggregate_stats(done)
        print(f"  spec: {agg['tokens_per_decode_call']} tok/verify-call, "
              f"acceptance {agg['acceptance_rate']} "
              f"({agg['drafts_accepted']}/{agg['drafts_proposed']} drafts)")
    for label, path in (("metrics", args.metrics_out), ("traces", args.trace_out),
                        ("profile trace", args.profile_out)):
        if path:
            print(f"  {label} → {path}")


if __name__ == "__main__":
    main()
