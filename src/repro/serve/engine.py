"""Continuous-batching inference engine.

``Engine`` multiplexes many generation requests over a fixed set of decode
slots:

* ``submit(prompt, max_new, sampling=...) -> Request`` queues work (the
  returned object is the handle; ``.tokens`` fills in as the engine runs),
* ``step()`` advances the world by one scheduler tick: admit queued requests
  into free slots, advance every prefilling request by one chunk — paged
  families in **one** batched jitted call over the packed pool — then step
  every decoding slot in **one** jitted call,
* ``drain()`` steps until nothing is queued or active.

Model families with positional attention KV (``dense``/``moe``) store their
cache in :class:`PagedCache` pages — optionally MXFP4-packed (4.25
bits/element) with quantize-on-write.  Batched decode AND batched prefill
attend *directly over the packed pool* via the fused Pallas paged-attention
kernel (the raw pool + int32 page tables are operands of the jitted steps;
no dense [L, B, T, Hkv, hd] gather is ever materialized).  Prefill advances
ALL prefilling slots per tick in one ``[n_slots, prefill_chunk]`` call:
each slot's chunk is quantize-scattered into its own pages at its own start
offset, ragged tails are padded and write-masked onto the scratch sentinel
column, and the multi-query kernel applies per-row causal bounds — so
prefill HBM traffic is O(packed KV) and TTFT no longer degrades linearly
with concurrent arrivals.  The legacy gather-dequantize decode and
per-slot-gather prefill survive together as a parity oracle behind
``EngineConfig(decode_backend="gather")``.  The OTHER families (ssm /
hybrid / encdec / vlm) now pool their per-slot decode state too, behind
:class:`~repro.serve.state_pool.StatePool`: positional self-KV in paged
planes, enc-dec/VLM cross-KV encoded ONCE at admission into a static
refcounted plane (shareable across requests with identical conditioning
when ``prefix_cache`` is on), and SSM recurrent/conv state in quantized
double-buffer page rings.  They schedule identically to the paged path but
keep per-slot chunk-then-single-token prefill, since an SSM recurrence
must never consume a padding token; the old per-slot dense caches survive
as the parity oracle behind ``decode_backend="dense_slots"``.

**Speculative decoding** (``EngineConfig(spec=SpecConfig(...))``, paged
families): each decode tick becomes draft → verify → accept.  A pluggable
proposer (``serve.spec.proposers``) drafts ``k`` tokens per slot; ONE jitted
verify call scores all ``k + 1`` tokens per slot directly over the packed
pool (multi-query paged-attention with per-row causal bounds); the host
accepts the longest draft prefix the target model itself reproduces and
emits 1..k+1 tokens.  Rollback is purely *logical*: the slot's length
shrinks on the host and the rejected suffix's positions become unreachable
(causal bounds + rewrite-before-read), while the pages themselves stay
mapped — admission reserved them for ``prompt + max_new`` and nothing a
speculative tick does may map beyond that reservation, so a full pool can
never raise "out of pages" mid-flight.  Draft positions past the token
budget redirect their writes to the scratch page (their KV is never read
by any emittable row).  Greedy self-speculation is token-exact against the
non-speculative engine (the extended parity-oracle contract).

Sampling is per request (:class:`~repro.serve.sampling.SamplingParams`):
greedy argmax by default; temperature / top-k / top-p draws use stateless
per-token keys, which is also what lets the speculative verifier re-draw any
drafted position independently.

Both paths reuse the same step builders as ``train.serve.greedy_generate``
(``make_chunk_prefill_step`` / ``make_decode_step`` / ``make_verify_step``
via :func:`repro.serve.steps.build_paged_steps`), so engine outputs are
token-for-token those of the reference loop in dense-cache mode.  On the
default paged backend exactly three shapes compile per engine: the
``[n_slots]`` decode, the ``[n_slots, k+1]`` verify, and the
``[n_slots, prefill_chunk]`` batched prefill (ragged tails are padded into
it — there is no ``[1, 1]`` remainder shape).  The gather oracle and the
dense-slot families keep the per-slot ``[1, prefill_chunk]`` + ``[1, 1]``
prefill shapes.
"""

from __future__ import annotations

import dataclasses
import time
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from repro.models.registry import Model
from repro.serve import paged_cache as P
from repro.serve.placement import Placement, ShardingConfig
from repro.serve.prefix_cache import PrefixIndex
from repro.serve.sampling import SamplingParams, get_sampler
from repro.serve.scheduler import Request, RequestState, Scheduler
from repro.serve.spec.config import SpecConfig
from repro.serve.spec.proposers import build_proposer
from repro.serve.spec.verify import accept_tokens
from repro.serve.state_pool import STATE_FAMILIES, StatePool, cross_key
from repro.serve.steps import (build_paged_steps, build_state_steps,
                               jit_cache_size, marshal_prefill_batch)
from repro.serve.telemetry import EngineTelemetry, TelemetryConfig
from repro.serve.telemetry.tracing import span
from repro.train.serve import make_chunk_prefill_step, make_decode_step

PAGED_FAMILIES = ("dense", "moe")
_EMBED_KEY = {"encdec": "source_embeds", "vlm": "image_embeds"}


@dataclasses.dataclass(frozen=True)
class EngineConfig:
    n_slots: int = 4
    max_len: int = 128  # per-slot token capacity (prompt + generation)
    page_size: int = 16  # paged families only
    kv_dtype: str = "mxfp4"  # "mxfp4" | "dense" (paged families only)
    prefill_chunk: int = 16
    method: str = "quartet"
    eos_id: int | None = None
    keep_logits: bool = False  # record per-step logits on each Request (tests)
    # backend selection.  Paged families (decode, verify AND prefill):
    #   None     — follow ModelConfig.attn_backend ("paged" unless overridden)
    #   "paged"  — fused Pallas kernel directly over the packed pool (default);
    #              prefill runs batched across all prefilling slots
    #   "gather" — legacy gather-dequantize-to-dense oracle (parity testing);
    #              prefill stays the per-slot [1, C] + [1, 1] chunk loop
    # Non-attention families (ssm / hybrid / encdec / vlm):
    #   None / "statepool" — unified StatePool planes (default): paged
    #              self-KV, encode-once cross-KV, quantized state rings
    #   "dense_slots"      — per-slot dense caches; survives as the
    #              state-pool parity oracle
    decode_backend: str | None = None
    # pool size override (pages incl. the scratch page).  None → one full
    # reservation (ceil(max_len / page_size) pages) per slot + scratch.
    # Admission reserves prompt + max_new pages up front and NOTHING maps
    # beyond a reservation mid-flight, so a pool sized exactly to the
    # reservations it admits can never raise "out of pages".
    n_pages: int | None = None
    # prefix sharing: on paged families, radix-index prompt token ids at
    # admission, alias every fully-covered cached page into the new slot's
    # table (refcounted; copy-on-write before any divergent write) and
    # prefill only the unshared tail; LRU-evict refcount-one cached prefixes
    # under pool pressure.  Token-exact vs the non-sharing engine — aliasing
    # is safe because MXFP4 quantize-on-write is deterministic, so a shared
    # prefix's packed pages are bit-identical to what a cold prefill would
    # have produced.  On state-pool enc-dec/VLM engines the same flag turns
    # on CROSS-KV sharing: requests whose conditioning tensors are byte-
    # identical alias one encoded cross page set (state_pool.CrossIndex) and
    # skip the encode entirely — warm is token-exact vs cold because both
    # read the same pooled pages.  ssm/hybrid have no shareable pages.
    prefix_cache: bool = False
    # run PagedCache.check_invariants after EVERY allocator mutate (page
    # conservation, refcount consistency, free-list hygiene) — tests/debug
    debug_cache: bool = False
    # speculative decoding (paged families only); None → plain decode
    spec: SpecConfig | None = None
    # observability (serve.telemetry).  None → metrics + tracing still
    # collected in-memory (host dicts, no sinks, no device sampling); set a
    # TelemetryConfig to stream JSONL metrics / traces, expose Prometheus
    # text, or sample pool quantization health at a tick stride.
    telemetry: TelemetryConfig | None = None
    # multi-device serving (paged families only).  ``tp`` shards the pool /
    # paged-attention / MoE experts over a ``('model',)`` mesh inside this
    # engine's jitted steps; ``dp > 1`` is only honored by
    # ``serve.replica.make_engine`` (data-parallel replicas) — constructing a
    # bare Engine with dp > 1 raises.  Token-exact vs single-device.
    sharding: ShardingConfig | None = None


class Engine:
    def __init__(self, model: Model, params, config: EngineConfig | None = None,
                 *, placement: Placement | None = None, ids=None):
        self.model, self.params = model, params
        self.config = cfg = config or EngineConfig()
        family = model.cfg.family
        self.paged = family in PAGED_FAMILIES
        if self.paged:
            self.backend = "paged"
        elif cfg.decode_backend in (None, "statepool"):
            self.backend = "statepool"
        elif cfg.decode_backend == "dense_slots":
            self.backend = "dense_slots"
        else:
            raise ValueError(
                f"decode_backend for {family!r} must be 'statepool' (default) "
                f"or 'dense_slots' (parity oracle), got {cfg.decode_backend!r}")
        self.spec = cfg.spec
        if self.spec is not None and not self.paged:
            raise ValueError(
                f"speculative decoding needs a paged family (dense/moe): "
                f"{family!r} serving has no multi-token verify step — an SSM "
                f"recurrence scores one token per state transition and the "
                f"state rings hold no positional history to roll back")
        if cfg.prefix_cache and not self.paged:
            if self.backend != "statepool" or family not in ("encdec", "vlm"):
                raise ValueError(
                    f"prefix caching needs shareable pages: a paged family "
                    f"(dense/moe, radix prompt prefixes) or a state-pool "
                    f"enc-dec/VLM engine (cross-KV sharing); "
                    f"{family!r} with backend {self.backend!r} has neither")
        if placement is None:
            if cfg.sharding is not None and cfg.sharding.dp > 1:
                raise ValueError(
                    "dp > 1 needs data-parallel replicas — build via "
                    "serve.replica.make_engine / ReplicatedEngine")
            placement = Placement(cfg.sharding.tp if cfg.sharding else 1)
        if placement.tp > 1 and not self.paged:
            if self.backend != "statepool" or family not in ("encdec", "vlm"):
                raise ValueError(
                    f"tensor-parallel serving shards pooled KV on the head "
                    f"axis: paged families and state-pool enc-dec/VLM only; "
                    f"{family!r} with backend {self.backend!r} keeps "
                    f"recurrent-state rings, which have no head axis to shard")
        self.placement = placement
        self.telemetry = EngineTelemetry(cfg.telemetry)
        self.sched = Scheduler(cfg.n_slots, cfg.max_len, cfg.prefill_chunk,
                               tracer=self.telemetry.tracer, ids=ids)
        self.completed: list[Request] = []
        self._dtype = jnp.dtype(model.cfg.dtype)
        self.steps = 0
        self.replica: int | None = None  # set by ReplicatedEngine; tags serve.step
        self._tick: tuple[float, span] | None = None  # (now, serve.step) of a tick

        if self.paged:
            # sizing (table width vs pool pages) is the shared reservation-
            # contract rule — see paged_cache.reservation_sizing
            spec_k = self.spec.k if self.spec else 0
            pages_per_slot, n_pages = P.reservation_sizing(
                cfg.n_slots, cfg.max_len, cfg.page_size, spec_k)
            if cfg.n_pages is not None:
                # fail fast: a pool that cannot hold even one maximal
                # reservation would wedge admission forever (can_admit False
                # on every tick) instead of erroring
                min_pages = 1 + (-(-cfg.max_len // cfg.page_size))
                if cfg.n_pages < min_pages:
                    raise ValueError(
                        f"n_pages={cfg.n_pages} cannot hold one max_len="
                        f"{cfg.max_len} reservation plus the scratch page "
                        f"(need >= {min_pages})")
                n_pages = cfg.n_pages
            self.cache = P.PagedCache(
                model, n_slots=cfg.n_slots, pages_per_slot=pages_per_slot,
                page_size=cfg.page_size, n_pages=n_pages, kv_dtype=cfg.kv_dtype,
                debug=cfg.debug_cache)
            if placement.mesh is not None:
                # pool shards on the KV-head axis over the placement mesh;
                # params replicate (serving TP = KV/attention/expert
                # parallelism, not weight sharding — see serve/README.md).
                # A one-device mesh (DP replica) pins both to its device.
                self.cache.pool = placement.shard_pool(self.cache.pool)
                self.params = placement.replicate(self.params)
            self.decode_backend = cfg.decode_backend or (
                "paged" if model.cfg.attn_backend == "paged" else "gather")
            self._steps = build_paged_steps(
                model, method=cfg.method, page_size=cfg.page_size,
                n_layers=self.cache.layers, decode_backend=self.decode_backend,
                placement=placement if placement.tp > 1 else None,
                pool_example=self.cache.pool)
            self._decode_all = self._steps.decode_all
            self._prefill_chunk = self._steps.prefill_chunk
            self._verify_all = self._steps.verify_all
            self._prefill_all = self._steps.prefill_all  # None on gather
            self._encode_cross = None
        elif self.backend == "statepool":
            self.cache = StatePool(
                model, n_slots=cfg.n_slots, max_len=cfg.max_len,
                page_size=cfg.page_size, kv_dtype=cfg.kv_dtype,
                debug=cfg.debug_cache)
            if placement.mesh is not None:
                # both paged planes shard on the KV-head axis (same mesh
                # contract as the dense/moe pool); params replicate
                self.cache.set_pools(placement.shard_pool(self.cache.pools()))
                self.params = placement.replicate(self.params)
            self.decode_backend = "statepool"
            self._steps = build_state_steps(
                model, method=cfg.method, pool=self.cache,
                placement=placement if placement.tp > 1 else None)
            self._decode_all = self._steps.decode_all
            self._prefill_chunk = self._steps.prefill_chunk
            self._encode_cross = self._steps.encode_cross
            self._prefill_all = None  # per-slot chunks: no padding into rings
        else:
            self.cache = P.DenseSlotCache(model, n_slots=cfg.n_slots,
                                          max_len=cfg.max_len)
            self.decode_backend = "dense_slots"
            decode = make_decode_step(model, method=cfg.method)
            chunk = make_chunk_prefill_step(model, method=cfg.method)

            def decode_all(params, tokens, positions, caches, mask):
                pos_safe = jnp.where(mask, positions, 0)
                logits, new_caches, _ = decode(params, tokens, pos_safe, caches)
                return logits, P.merge_masked(caches, new_caches, mask)

            def prefill_chunk(params, tokens, start, slot, caches, extra=None):
                sub = P.slice_slot(caches, slot)
                logits, new_sub, _ = chunk(
                    params, tokens, jnp.full((1,), start, jnp.int32), sub, extra)
                return logits, P.write_slot(caches, new_sub, slot)

            self._decode_all = jax.jit(decode_all)
            self._prefill_chunk = jax.jit(prefill_chunk)
            self._prefill_all = None  # dense slots: SSM state must never see padding
            self._encode_cross = None

        self.prefix = (PrefixIndex(cfg.page_size)
                       if (self.paged and cfg.prefix_cache) else None)
        # cross-KV sharing: the state-pool analogue of the prefix cache
        self.cross_share = (self.backend == "statepool" and cfg.prefix_cache
                            and self.cache.cross is not None)
        self._admit_plan: dict[int, list[int]] = {}  # rid -> matched page ids
        self._cross_plan: dict[int, tuple] = {}  # rid -> (content key, pages)
        self.proposer = (build_proposer(self, self.spec)
                         if self.spec is not None else None)
        self.telemetry.attach(self)

    # ------------------------------------------------------------------ API

    def submit(self, prompt, max_new: int, extra: Any = None,
               arrival_time: float | None = None,
               sampling: SamplingParams | None = None) -> Request:
        now = time.monotonic() if arrival_time is None else arrival_time
        if self.backend == "statepool" and self.cache.cross is not None:
            key = _EMBED_KEY[self.model.cfg.family]
            if extra is None or extra.get(key) is None:
                raise ValueError(
                    f"state-pool {self.model.cfg.family!r} serving encodes "
                    f"cross-KV once at admission: submit() needs "
                    f"extra[{key!r}]")
        req = self.sched.submit(prompt, max_new, extra=extra, arrival_time=now,
                                sampling=sampling)
        self.telemetry.registry.counter("requests_submitted").inc()
        return req

    def step(self, now: float | None = None) -> dict:
        """One scheduler tick: admit → chunked prefill → batched decode (or
        draft/verify/accept with speculation on) → retire.  Returns a small
        summary dict (counts) for driver loops.

        Every section runs under a ``serve.*`` span (``telemetry.tracing``):
        in the profiler's host plane when a trace runs, and in the
        process-wide span ring always."""
        now = time.monotonic() if now is None else now
        sched = self.sched
        tags = {} if self.replica is None else {"replica": self.replica}
        with span("serve.step", step=self.steps + 1, queued=len(sched.queue),
                  prefilling=len(sched.prefilling()),
                  decoding=len(sched.decoding()), **tags) as tick:
            self._tick = (now, tick)
            with span("serve.admit") as sp:
                admitted, blocked = self._admit(now)
                sp.set(admitted=len(admitted), blocked_pages=int(blocked))

            # -- chunked prefill: ALL prefilling paged slots in one jitted
            #    call (gather oracle / dense slots: one per-slot call each)
            if self._prefill_all is not None:
                batch = sched.prefill_batch()
                if batch:
                    with span("serve.prefill") as sp:
                        self._prefill_tick(batch, now)
                    self.telemetry.phase("prefill", sp.seconds)
            elif (reqs := sched.prefilling()):
                with span("serve.prefill") as sp:
                    for req in reqs:
                        self._advance_prefill(req, now)
                self.telemetry.phase("prefill", sp.seconds)

            # -- one batched decode/verify over all decoding slots -----------
            decoding = sched.decoding()
            if decoding:
                phase = "decode" if self.spec is None else "verify"
                with span(f"serve.{phase}") as sp:
                    if self.spec is None:
                        self._decode_tick(decoding)
                    else:
                        self._spec_tick(decoding)
                self.telemetry.phase(phase, sp.seconds)

            self.steps += 1
            with span("serve.telemetry"):
                self.telemetry.end_tick(self, now, tick.seconds)
        self._tick = None
        return {"admitted": len(admitted), "prefilling": len(sched.prefilling()),
                "decoding": len(sched.decoding()),
                "queued": len(sched.queue), "step": self.steps}

    def _admit(self, now: float) -> tuple[list[Request], bool]:
        """Admit queued requests into free slots; returns them and whether
        the queue head had a slot but no pages."""
        reg = self.telemetry.registry

        def can_admit(req: Request) -> bool:
            if self.backend == "dense_slots":
                return True
            if self.backend == "statepool":
                key = cross_key(req.extra) if self.cross_share else None
                match = self.cache.cross_match(key, now)
                ok = self.cache.can_admit(req.prompt_len + req.max_new,
                                          cross_shared=bool(match))
                if ok and self.cross_share:
                    self._cross_plan[req.rid] = (key, match)
                return ok
            if self.prefix is None:
                return self.cache.can_alloc(req.prompt_len + req.max_new)
            match = self.prefix.match(req.prompt, now)
            ok = self._fresh_pages_needed(req, match) <= (
                self.cache.free_pages
                + self.prefix.evictable_pages(self.cache, exclude=match))
            if ok:
                self._admit_plan[req.rid] = match
            return ok

        admitted = self.sched.admit(
            can_admit, on_admit=lambda req: self._on_admit(req, now))
        for req in admitted:
            if self.proposer is not None:
                self.proposer.on_admit(req)
            self.telemetry.tracer.event(req.rid, "admit", now)
        reg.counter("requests_admitted").inc(len(admitted))
        blocked = bool(self.sched.queue and self.sched.free_slots)
        if blocked:
            # a slot is free but the FIFO head didn't fit: page pressure
            reg.counter("admission_blocked_pages").inc()
        return admitted, blocked

    def _emit_time(self) -> float:
        """Engine-clock stamp for a token emitted now: the tick's ``now``
        plus the time the tick has taken so far."""
        now, tick = self._tick
        return now + tick.seconds

    def _fetch_logits(self, phase: str, logits) -> np.ndarray:
        """Wait for the step's logits, copy them to the host as float32.  The
        copy is queued first, so it starts when the step ends, as a plain
        ``np.asarray`` would; ``wait`` then ends when the step's outputs are
        ready and ``fetch`` holds what is left of the transfer."""
        logits.copy_to_host_async()
        with span(f"serve.{phase}.wait"):
            jax.block_until_ready(logits)
        with span(f"serve.{phase}.fetch", bytes=int(logits.nbytes)):
            out = np.asarray(logits, np.float32)
        self.telemetry.registry.counter("logits_bytes_fetched").inc(int(logits.nbytes))
        return out

    def drain(self, max_steps: int = 100_000) -> list[Request]:
        """Step until every submitted request has finished."""
        while self.sched.pending:
            self.step()
            if self.steps > max_steps:
                raise RuntimeError("drain exceeded max_steps — engine wedged?")
        return self.completed

    def cache_bytes(self) -> int:
        return self.cache.cache_bytes()

    def compile_counts(self) -> dict[str, int]:
        """Compiled-variant count per jitted step — the one-compile-per-shape
        contract made observable (exported as ``jit_compiled_*`` gauges and
        pinned by the telemetry no-recompile test)."""
        if self.backend in ("paged", "statepool"):
            return self._steps.compile_counts()
        return {"decode_all": jit_cache_size(self._decode_all),
                "prefill_chunk": jit_cache_size(self._prefill_chunk),
                "verify_all": 0, "prefill_all": 0}

    # ------------------------------------------------------------- internals

    def _sample(self, req: Request, logits_row, token_idx: int) -> int:
        """One token draw for ``req`` (greedy argmax unless the request set
        SamplingParams) — the single sampling call site for prefill, decode,
        drafting, and verification, keyed by generated-token index."""
        sp = req.sampling if req.sampling is not None else SamplingParams()
        return get_sampler(sp)(logits_row, token_idx)

    # -- prefix sharing ------------------------------------------------------

    def _fresh_pages_needed(self, req: Request, match: list[int]) -> int:
        """Free-list pages this admission must produce beyond the aliased
        prefix ``match``: the reservation's uncovered tail, plus one
        copy-on-write target when the hit covers the ENTIRE prompt (the final
        prompt token is then re-prefilled into the shared tail page, which
        must first be detached)."""
        need = self.cache.pages_needed(req.prompt_len + req.max_new)
        full = len(match) * self.config.page_size == req.prompt_len
        return need - len(match) + (1 if full else 0)

    def _on_admit(self, req: Request, now: float) -> None:
        """Commit the cache side of one admission.  Runs INLINE inside
        ``Scheduler.admit`` — before the next head's ``can_admit`` — so page
        allocation, prefix aliasing, eviction, and the eager full-hit COW are
        transactional against the pool the next admission is judged on."""
        if self.backend == "dense_slots":
            self.cache.reset_slot(req.slot)
            return
        total = req.prompt_len + req.max_new
        if self.backend == "statepool":
            reg = self.telemetry.registry
            key, match = self._cross_plan.pop(req.rid, (None, []))
            self.cache.alloc(req.slot, total, cross_shared=match)
            if self.cache.cross is None:
                return
            if self.cross_share:
                reg.counter("prefix_lookups").inc()
            if match:
                # warm: the slot's cross row aliases the cached page set —
                # no encode, and decode reads bit-identical pages to cold
                reg.counter("prefix_hit_requests").inc()
                reg.counter("prefix_shared_tokens").inc(self.cache.cross_tokens)
                return
            embeds = req.extra[_EMBED_KEY[self.model.cfg.family]]
            cross_row = jnp.asarray(self.cache.cross.tables[req.slot])
            self.cache.cross.pool = self._encode_cross(
                self.params, jnp.asarray(embeds), cross_row,
                self.cache.cross.pool)
            reg.counter("cross_encode_calls").inc()
            if self.cross_share and key is not None:
                reg.counter("prefix_inserted_pages").inc(
                    self.cache.cross_publish(key, req.slot, now))
            return
        if self.prefix is None:
            self.cache.alloc(req.slot, total)
            return
        reg = self.telemetry.registry
        match = self._admit_plan.pop(req.rid, [])
        shortfall = self._fresh_pages_needed(req, match) - self.cache.free_pages
        if shortfall > 0:
            reg.counter("prefix_evicted_pages").inc(
                self.prefix.evict(self.cache, shortfall, exclude=match))
        self.cache.alloc(req.slot, total, shared=match)
        reg.counter("prefix_lookups").inc()
        if not match:
            return
        reg.counter("prefix_hit_requests").inc()
        covered = len(match) * self.config.page_size
        if covered == req.prompt_len:
            # full-prefix hit: skip everything but the final prompt token,
            # whose logits must be recomputed to sample the first generated
            # token.  That one-token re-prefill rewrites (bit-identically)
            # into the last shared page — detach it NOW so the free-list
            # accounting above stays exact.
            req.prefill_pos = req.prompt_len - 1
            reg.counter("prefix_cow_pages").inc(
                self.cache.cow_range(req.slot, req.prefill_pos, 1))
            covered -= 1
        else:
            req.prefill_pos = covered
        reg.counter("prefix_shared_tokens").inc(covered)

    def _cow_guard(self, reqs_spans) -> None:
        """Copy-on-write safety net before a write phase: for each
        ``(slot, start_tok, n_tokens)`` span about to be written, detach any
        still-shared page in range (``PagedCache.cow_range``).  Normally a
        no-op — slots only write past their aliased prefix, and the one real
        divergence (full-hit re-prefill) is COWed eagerly at admission — but
        it makes "a slot never writes into a page another holder can see"
        locally true at every write site rather than a global argument."""
        if self.prefix is None:
            return
        cow = self.telemetry.registry.counter("prefix_cow_pages")
        for slot, start, n in reqs_spans:
            cow.inc(self.cache.cow_range(slot, start, n))

    def _prefix_insert(self, req: Request, tokens: np.ndarray, now: float) -> None:
        """Publish ``req``'s fully-written pages under token chain ``tokens``
        into the radix index (partial tail pages are never published)."""
        if self.prefix is None:
            return
        added = self.prefix.insert(self.cache, tokens,
                                   self.cache.tables[req.slot], now)
        self.telemetry.registry.counter("prefix_inserted_pages").inc(added)

    def _run_prefill_call(self, req: Request, tokens_np: np.ndarray):
        n = int(tokens_np.shape[0])
        with span("serve.prefill.marshal"):
            start = jnp.int32(req.prefill_pos)
            tokens = jnp.asarray(tokens_np[None, :], jnp.int32)
            if self.paged:
                self._cow_guard([(req.slot, req.prefill_pos, n)])
                rows = (jnp.asarray(self.cache.tables[req.slot]),)
            elif self.backend == "statepool":
                sp = self.cache
                rows = (jnp.asarray(sp.kv.tables[req.slot]) if sp.kv is not None else None,
                        jnp.asarray(sp.cross.tables[req.slot])
                        if sp.cross is not None else None,
                        jnp.asarray(np.array([sp.ring_read[req.slot]], np.int32)),
                        jnp.asarray(np.array([sp.ring_write_id(req.slot)], np.int32)))
        # a [1, n] call: every row it computes is a prompt token
        with span("serve.prefill.dispatch", slots=1, rows_valid=n, rows_computed=n):
            if self.paged:
                logits, self.cache.pool = self._prefill_chunk(
                    self.params, tokens, start, *rows, self.cache.pool, req.extra)
            elif self.backend == "statepool":
                logits, state = self._prefill_chunk(
                    self.params, tokens, start, sp.pools(), *rows, req.extra)
                sp.set_pools(state)
                if sp.rings:
                    one = np.zeros((self.config.n_slots,), bool)
                    one[req.slot] = True
                    sp.ring_advance(one)
            else:
                logits, self.cache.caches = self._prefill_chunk(
                    self.params, tokens, start, jnp.int32(req.slot),
                    self.cache.caches, req.extra)
        req.prefill_pos += n
        reg = self.telemetry.registry
        reg.counter("prefill_calls").inc()
        reg.counter("prompt_tokens_prefilled").inc(n)
        reg.counter("prefill_rows_computed").inc(n)
        return logits

    def _prefill_tick(self, batch, now: float) -> None:
        """Advance EVERY prefilling slot by one chunk in ONE jitted call over
        the packed pool (paged backend).  Rows are ``[n_slots, C]`` with
        ragged tails padded; the step write-masks padding onto the scratch
        sentinel column and returns each row's last-valid-token logits, from
        which slots that just consumed their whole prompt sample their first
        token."""
        with span("serve.prefill.marshal"):
            self._cow_guard([(req.slot, pos, n) for req, pos, n in batch])
            tokens, start, n_valid, mask = marshal_prefill_batch(
                self.config.n_slots, self.config.prefill_chunk,
                ((req.slot, pos, req.prompt[pos:pos + n]) for req, pos, n in batch))
            args = (jnp.asarray(tokens), jnp.asarray(start), jnp.asarray(n_valid),
                    self.cache.pool, jnp.asarray(self.cache.tables), jnp.asarray(mask))
        valid = int(sum(n for _, _, n in batch))
        with span("serve.prefill.dispatch", slots=len(batch), rows_valid=valid,
                  rows_computed=int(tokens.size),
                  **self._page_walk([pos + 1 for _, pos, _ in batch],
                                    self.config.prefill_chunk)):
            logits, self.cache.pool = self._prefill_all(self.params, *args)
        reg = self.telemetry.registry
        reg.counter("prefill_calls").inc()
        reg.counter("prompt_tokens_prefilled").inc(valid)
        reg.counter("prefill_rows_computed").inc(int(tokens.size))
        finished = []
        for req, pos, n in batch:
            req.prefill_pos = pos + n
            if req.prefill_pos == req.prompt_len:
                finished.append(req)
        if not finished:
            return  # [B, V] logits are fetched only if some slot finished
        logits_np = self._fetch_logits("prefill", logits)
        t = self._emit_time()
        with span("serve.prefill.sample"):
            for req in finished:
                row = logits_np[req.slot]
                tok = self._sample(req, row, 0)
                if self.config.keep_logits:
                    req.logits_trace.append(row)
                req.tokens.append(tok)
                req.state = RequestState.DECODE
                self._prefix_insert(req, req.prompt, now)
                self._record_first_token(req, t)
                self._maybe_finish(req, t)

    def _advance_prefill(self, req: Request, now: float) -> None:
        """Per-slot prefill: the gather parity oracle and the dense-slot
        families (whose SSM recurrences must never see padding)."""
        C = self.config.prefill_chunk
        remaining = req.prompt_len - req.prefill_pos
        if remaining >= C:
            logits = self._run_prefill_call(
                req, req.prompt[req.prefill_pos:req.prefill_pos + C])
        else:
            # remainder (< C tokens): single-token chunks — never pad, so SSM
            # recurrences and MoE routing only ever see real tokens
            for _ in range(remaining):
                logits = self._run_prefill_call(
                    req, req.prompt[req.prefill_pos:req.prefill_pos + 1])
        if req.prefill_pos == req.prompt_len:
            logits_np = self._fetch_logits("prefill", logits[0])
            t = self._emit_time()
            with span("serve.prefill.sample"):
                tok = self._sample(req, logits_np, 0)
                if self.config.keep_logits:
                    req.logits_trace.append(logits_np)
                req.tokens.append(tok)
                req.state = RequestState.DECODE
                if self.paged:
                    self._prefix_insert(req, req.prompt, now)
                self._record_first_token(req, t)
                self._maybe_finish(req, t)

    def _record_first_token(self, req: Request, t: float) -> None:
        """Prefill just produced the request's first token at engine time
        ``t``: stamp it, trace the span boundary and count the emission (it
        rides on a prefill call, so it counts toward ``tokens_generated``
        but NOT ``decode_tokens``)."""
        req.first_token_time = t
        self.telemetry.tracer.event(req.rid, "first_token", t)
        self.telemetry.tracer.tokens(req.rid, t, 1)
        self.telemetry.registry.counter("tokens_generated").inc()

    def _page_walk(self, lengths, n_q: int) -> dict:
        """Span attributes of a paged-attention dispatch: ``pages_live``, the
        page columns the kernel walks (``ceil((len + n_q - 1) / ps)`` for
        each dispatched slot, ``len`` the tokens its first query row sees),
        beside ``page_columns``, the ``n_slots x pages_per_slot`` of the page
        tables.  Empty unless the kernel does the attention."""
        if self.decode_backend != "paged":
            return {}
        ps = self.config.page_size
        return {"pages_live": sum(-(-(ln + n_q - 1) // ps) for ln in lengths),
                "page_columns": int(self.cache.tables.size)}

    def _decode_tick(self, decoding: list[Request]) -> None:
        B = self.config.n_slots
        with span("serve.decode.marshal"):
            tokens = np.zeros((B, 1), np.int32)
            positions = np.zeros((B,), np.int32)
            mask = np.zeros((B,), bool)
            for req in decoding:
                tokens[req.slot, 0] = req.tokens[-1]
                positions[req.slot] = req.prompt_len + len(req.tokens) - 1
                mask[req.slot] = True
            args = (self.params, jnp.asarray(tokens), jnp.asarray(positions))
            if self.paged:
                self._cow_guard([(r.slot, int(positions[r.slot]), 1) for r in decoding])
                args += (self.cache.pool, jnp.asarray(self.cache.tables))
            elif self.backend == "statepool":
                sp = self.cache
                ring_read, ring_write = sp.ring_ids(mask)
                args += (sp.pools(),
                         jnp.asarray(sp.kv.tables) if sp.kv is not None else None,
                         jnp.asarray(sp.cross.tables) if sp.cross is not None else None,
                         jnp.asarray(ring_read), jnp.asarray(ring_write))
            else:
                args += (self.cache.caches,)
            args += (jnp.asarray(mask),)
        with span("serve.decode.dispatch", slots=len(decoding),
                  **self._page_walk([int(positions[r.slot]) + 1 for r in decoding], 1)):
            if self.paged:
                logits, self.cache.pool = self._decode_all(*args)
            elif self.backend == "statepool":
                logits, state = self._decode_all(*args)
                sp.set_pools(state)
                sp.ring_advance(mask)
            else:
                logits, self.cache.caches = self._decode_all(*args)
        logits_np = self._fetch_logits("decode", logits)
        t = self._emit_time()
        reg = self.telemetry.registry
        reg.counter("decode_calls").inc()
        with span("serve.decode.sample"):
            for req in decoding:
                tok = self._sample(req, logits_np[req.slot], len(req.tokens))
                if self.config.keep_logits:
                    req.logits_trace.append(logits_np[req.slot])
                req.tokens.append(tok)
                req.decode_calls += 1
                self.telemetry.tracer.tokens(req.rid, t, 1)
                self._maybe_finish(req, t)
        reg.counter("tokens_generated").inc(len(decoding))
        reg.counter("decode_tokens").inc(len(decoding))

    def _spec_tick(self, decoding: list[Request]) -> None:
        """Draft → one batched verify → accept/rollback.

        Per slot with last accepted token t at position p0 and drafts
        d1..dk: the verify call feeds [t, d1..dk] at positions p0..p0+k
        (writing all k+1 tokens' KV before attending — the usual
        write-before-read causal invariant) and returns k+1 logit rows;
        row i is the target's distribution after consuming token i.  The
        host accepts the longest draft prefix the target's own draws
        reproduce and emits the correction/bonus draw.

        No pages are mapped for the burst: admission already reserved
        ``prompt + max_new`` and the engine never maps beyond that
        reservation ("reserved up front so decode never OOMs" — mapping
        speculative headroom on demand from a full pool is exactly how the
        old ``ensure(p0 + k + 1)`` could raise "out of pages" mid-flight).
        Draft positions past the budget fall on unmapped (zero) table
        columns, so their quantize-on-write redirects to the scratch page;
        every row whose draw can be EMITTED attends only to reserved,
        properly-written positions, so emitted tokens never see the
        garbage.  Rollback is likewise logical-only: the host shrinks the
        slot's length and the rejected positions become unreachable (causal
        bounds + rewrite-before-read), with no page traffic.
        """
        k, B = self.spec.k, self.config.n_slots
        with span("serve.verify.draft"):
            drafts = self.proposer.propose(decoding)  # [n_slots, k] int32

        with span("serve.verify.marshal"):
            tokens = np.zeros((B, k + 1), np.int32)
            start = np.zeros((B,), np.int32)
            mask = np.zeros((B,), bool)
            for req in decoding:
                tokens[req.slot, 0] = req.tokens[-1]
                tokens[req.slot, 1:] = drafts[req.slot]
                start[req.slot] = req.prompt_len + len(req.tokens) - 1
                mask[req.slot] = True
            self._cow_guard([(r.slot, int(start[r.slot]), k + 1) for r in decoding])
            args = (jnp.asarray(tokens), jnp.asarray(start), self.cache.pool,
                    jnp.asarray(self.cache.tables), jnp.asarray(mask))
        with span("serve.verify.dispatch", slots=len(decoding),
                  **self._page_walk([int(start[r.slot]) + 1 for r in decoding], k + 1)):
            logits, self.cache.pool = self._verify_all(self.params, *args)
        logits_np = self._fetch_logits("verify", logits)  # [B, k+1, V]
        t = self._emit_time()
        reg = self.telemetry.registry
        reg.counter("verify_calls").inc()
        with span("serve.verify.sample"):
            self._accept_bursts(decoding, drafts, logits_np, t)
        if (total := reg.counter("drafts_proposed").value):
            reg.gauge("spec_acceptance_rate").set(
                reg.counter("drafts_accepted").value / total)

    def _accept_bursts(self, decoding, drafts, logits_np, t: float) -> None:
        """Per slot: the target's draws over the k+1 rows, the accepted draft
        prefix and the emitted burst, stamped at engine time ``t``."""
        k, eos = self.spec.k, self.config.eos_id
        reg = self.telemetry.registry
        for req in decoding:
            base = len(req.tokens)
            target = [self._sample(req, logits_np[req.slot, i], base + i)
                      for i in range(k + 1)]
            n_acc, emitted = accept_tokens(drafts[req.slot].tolist(), target)
            req.decode_calls += 1
            n_emit, stopped = 0, False
            for i, tok in enumerate(emitted):
                if self.config.keep_logits:
                    req.logits_trace.append(logits_np[req.slot, i])
                req.tokens.append(tok)
                n_emit += 1
                if ((eos is not None and tok == eos)
                        or len(req.tokens) >= req.max_new):
                    stopped = True
                    break  # emission stops at EOS / budget even mid-burst
            # acceptance accounting counts only drafts at EMITTABLE
            # positions: when emission stops mid-burst (EOS / budget) the
            # drafts past the stop could never have been emitted, and
            # counting them as proposed-but-not-accepted skews
            # acceptance_rate low for short-tail requests (the self-proposer
            # oracle must report exactly 1.0 even on a request that hits its
            # budget mid-burst).  A burst that ends by REJECTION still
            # counts all k drafts — the rejected draft's unreached
            # successors were honestly proposed and scored, and dropping
            # them would bias acceptance upward for real proposers.
            proposed = min(n_emit if stopped else k, k)
            req.draft_proposed += proposed
            req.draft_accepted += min(n_acc, proposed)
            reg.counter("tokens_generated").inc(n_emit)
            reg.counter("decode_tokens").inc(n_emit)
            reg.counter("drafts_proposed").inc(proposed)
            reg.counter("drafts_accepted").inc(min(n_acc, proposed))
            self.telemetry.tracer.tokens(req.rid, t, n_emit)
            self._maybe_finish(req, t)
            if not req.done:
                # rollback is logical: the rejected suffix's positions are
                # simply beyond the new length — pages stay mapped within the
                # admission reservation and every position is rewritten
                # before it is next read
                self.proposer.on_accept(req)

    def _maybe_finish(self, req: Request, now: float) -> None:
        eos = self.config.eos_id
        reason = None
        if eos is not None and req.tokens and req.tokens[-1] == eos:
            reason = "eos"
        elif len(req.tokens) >= req.max_new:
            reason = "max_tokens"
        if reason is not None:
            self.sched.retire(req, reason, now)  # fires the "retire" span
            if self.paged:
                if self.prefix is not None:
                    # publish the whole conversation before releasing the
                    # slot — a continuation request (this prompt + these
                    # tokens + more) aliases it later.  The final token is
                    # excluded: it was emitted but never consumed, so its KV
                    # was never written; every position up to it holds the
                    # correct token's KV at retirement (a speculative
                    # correction is rewritten by the next burst's first row,
                    # and a retiring burst's correction IS the final token).
                    chain = np.concatenate(
                        [req.prompt, np.asarray(req.tokens[:-1], np.int32)])
                    self._prefix_insert(req, chain, now)
                self.cache.free(req.slot)
            elif self.backend == "statepool":
                # frees KV reservation + cross mapping + deactivates the
                # ring; CrossIndex pins keep a published cross page set
                # alive past this release
                self.cache.free(req.slot)
            if self.proposer is not None:
                self.proposer.on_retire(req)
            self.completed.append(req)
            reg = self.telemetry.registry
            reg.counter(f"requests_retired_{reason}").inc()
            if (tpc := req.tokens_per_decode_call()) is not None:
                reg.histogram("tokens_per_decode_call").observe(tpc)
