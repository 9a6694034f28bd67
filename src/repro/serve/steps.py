"""Jitted device-step builders for paged-KV serving.

Extracted from ``Engine`` so the speculative draft-model proposer can run the
*same* decode / chunk-prefill / multi-token-verify machinery over its own
:class:`~repro.serve.paged_cache.PagedCache` without duplicating the masking
and scatter plumbing.  Each builder closes over a model and returns pure
functions of ``(params, …, pool, tables, mask)`` — device state in, device
state out; the caller owns the pool.

Four step kinds per paged model:

* ``decode_all``    — one token for every slot in one call (S == 1),
* ``prefill_all``   — one ``[n_slots, C]`` chunk for EVERY prefilling slot in
  one call, quantize-scattering each slot's tokens into its own pages and
  attending *directly over the packed pool* with per-slot start offsets and
  per-row causal bounds (paged backend only; ragged tails are padded and
  write-masked onto the scratch sentinel column —
  ``kernels.paged_attention.prefill_chunk_layout``),
* ``prefill_chunk`` — one slot's ``[1, C]`` prompt chunk via gather-
  dequantize to a dense view; survives as the ``decode_backend="gather"``
  prefill parity oracle (and the dense-slot families' shape),
* ``verify_all``    — S = k+1 tokens for every slot in one call: the
  speculative verify.  With ``decode_backend="paged"`` the drafted suffix is
  scored *directly over the packed MXFP4 pool* (multi-query paged-attention
  kernel, per-row causal bounds); ``"gather"`` materializes the dense view
  and survives as the parity oracle.

``prefill_all`` and ``verify_all`` are the same device computation at
different S: both feed the rows-unshared model with explicit per-token
positions and let the multi-query paged kernel apply per-row bounds.

Masked lanes follow the engine invariants: positions are clamped to 0 and
table rows zeroed, so writes land on the reserved scratch page and the
lane's logits are garbage that the host never reads; every step also passes
``token_valid`` into the model so padding lanes never compete for MoE expert
capacity (a garbage lane with a lucky router score must not displace a real
token from an expert's top-c selection).
"""

from __future__ import annotations

from typing import Any, Callable, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.models.registry import Model
from repro.serve import paged_cache as P
from repro.train.serve import (
    _cast_params,
    make_chunk_prefill_step,
    make_decode_step,
    make_verify_step,
)


def marshal_prefill_batch(n_slots: int, chunk: int, items):
    """Host-side operand marshalling for one ``prefill_all`` call.

    ``items`` yields ``(slot, start, tokens_np)`` with
    ``1 <= len(tokens_np) <= chunk``; returns padded numpy operands
    ``(tokens [n_slots, chunk], start [n_slots], n_valid [n_slots],
    mask [n_slots])``.  The ONE definition of the padding/masking convention
    shared by the engine's prefill tick and the draft proposer's context
    sync — the device step relies on rows past ``n_valid`` being ignorable
    and on masked lanes being all-zero, so both callers must marshal
    identically.
    """
    tokens = np.zeros((n_slots, chunk), np.int32)
    start = np.zeros((n_slots,), np.int32)
    n_valid = np.zeros((n_slots,), np.int32)
    mask = np.zeros((n_slots,), bool)
    for slot, s0, toks in items:
        n = len(toks)
        tokens[slot, :n] = toks
        start[slot], n_valid[slot], mask[slot] = s0, n, True
    return tokens, start, n_valid, mask


class PagedSteps(NamedTuple):
    decode_all: Callable  # (params, tokens [B,1], positions [B], pool, tables, mask) -> (logits [B,V], pool)
    prefill_chunk: Callable  # (params, tokens [1,C], start, table_row, pool, extra) -> (logits [1,V], pool)
    verify_all: Callable  # (params, tokens [B,S], start [B], pool, tables, mask) -> (logits [B,S,V], pool)
    # (params, tokens [B,C], start [B], n_valid [B], pool, tables, mask)
    #   -> (last-valid-token logits [B,V], pool); None on the gather backend
    prefill_all: Callable | None

    def compile_counts(self) -> dict[str, int]:
        """Compiled-variant count per step function — the engine's
        one-compile-per-shape contract made observable (telemetry exports
        these as ``jit_compiled_*`` gauges; a compile storm shows up as a
        count > 1 on a fixed-shape step)."""
        return {name: jit_cache_size(fn) for name, fn in zip(self._fields, self)}


def jit_cache_size(fn) -> int:
    """Number of compiled variants a ``jax.jit`` callable holds (0 for None
    or when the private counter is unavailable on this jax version)."""
    if fn is None:
        return 0
    try:
        return int(fn._cache_size())
    except AttributeError:
        return 0


def build_paged_steps(model: Model, *, method: str, page_size: int,
                      n_layers: int, decode_backend: str = "paged",
                      placement=None, pool_example=None) -> PagedSteps:
    """``placement`` (serve.placement.Placement, tp > 1) makes every returned
    step a ``jax.jit(shard_map(...))`` over the placement's ``('model',)``
    mesh: the pool enters head-sharded (``pool_example`` supplies the leaf
    shapes for the PartitionSpecs), everything else replicated, and the model
    is rebuilt with ``cfg.tp_axis/tp_size`` set so its shape-based detection
    slices heads/experts inside the shard_map body.  ``check_rep=False``
    because GSPMD cannot see through the Pallas kernel; exactness is by
    construction (slices + tiled all_gather concats, no reductions)."""
    if decode_backend not in ("paged", "gather"):
        raise ValueError(f"decode_backend must be 'paged' or 'gather', "
                         f"got {decode_backend!r}")
    tp = placement.tp if placement is not None else 1
    if tp > 1:
        if pool_example is None:
            raise ValueError("tp > 1 needs pool_example for pool PartitionSpecs")
        import dataclasses

        from repro.models.registry import build_model

        model = build_model(dataclasses.replace(
            model.cfg, tp_axis=type(placement).AXIS, tp_size=tp))
    decode = make_decode_step(model, method=method)
    chunk = make_chunk_prefill_step(model, method=method)
    verify = make_verify_step(model, method=method)
    dtype = jnp.dtype(model.cfg.dtype)
    ps = page_size

    def _broadcast_tables(tables, mask):
        tbl = jnp.where(mask[:, None], tables, 0)
        return jnp.broadcast_to(tbl[None], (n_layers, *tbl.shape))

    if decode_backend == "paged":

        def decode_all(params, tokens, positions, pool, tables, mask):
            """One decode step for every slot, attending directly over the
            packed pool (no dense gather).  Masked lanes get an all-zero
            table row, so their quantize-on-write lands on the scratch page
            and their (meaningless) logits are discarded."""
            pos_safe = jnp.where(mask, positions, 0)
            paged = P.PagedKV(pool=pool, tables=_broadcast_tables(tables, mask))
            logits, new_caches, _ = decode(params, tokens, pos_safe, paged,
                                           token_valid=mask[:, None])
            return logits, new_caches.pool

        def verify_all(params, tokens, start, pool, tables, mask):
            """Score S = k+1 tokens per slot (last accepted + drafted suffix)
            in one call, directly over the packed pool: the multi-query paged
            kernel applies per-row causal bounds, so draft i only sees
            positions ≤ start + i."""
            pos_safe = jnp.where(mask, start, 0)
            paged = P.PagedKV(pool=pool, tables=_broadcast_tables(tables, mask))
            logits, new_caches = verify(
                params, tokens, pos_safe, paged,
                token_valid=jnp.broadcast_to(mask[:, None], tokens.shape))
            return logits, new_caches.pool

        def prefill_all(params, tokens, start, n_valid, pool, tables, mask):
            """Advance EVERY prefilling slot by one ragged [B, C] chunk in a
            single call over the packed pool — no dense gather, no per-slot
            loop, no [1, 1] remainder shape.  Tokens past a row's ``n_valid``
            are padding: ``prefill_chunk_layout`` positions them on the
            scratch sentinel column, so their quantize-on-write never touches
            live pages and their output rows are garbage the host ignores;
            ``token_valid`` keeps those padding lanes out of MoE expert-
            capacity competition, so routing (and therefore drop patterns at
            capacity-bound scale) is independent of batch padding.
            Returns each row's LAST VALID token logits (the only column the
            engine ever reads — it samples the first generated token from the
            final chunk)."""
            tbl = jnp.where(mask[:, None], tables, 0)
            C = tokens.shape[1]
            tbl_ext, positions = P.prefill_chunk_layout(
                tbl, start, n_valid, C, ps, mask)
            pos_safe = jnp.where(mask, start, 0)
            paged = P.PagedKV(
                pool=pool,
                tables=jnp.broadcast_to(tbl_ext[None], (n_layers, *tbl_ext.shape)))
            valid = mask[:, None] & (jnp.arange(C, dtype=jnp.int32)[None, :]
                                     < n_valid[:, None])
            logits, new_caches = verify(params, tokens, pos_safe, paged,
                                        positions=positions, token_valid=valid)
            last = logits[jnp.arange(tokens.shape[0]),
                          jnp.clip(n_valid - 1, 0, C - 1)]
            return last, new_caches.pool
    else:

        def decode_all(params, tokens, positions, pool, tables, mask):
            """Gather-dequantize parity oracle: materializes the dense
            [L, B, T, Hkv, hd] KV view each step."""
            pos_safe = jnp.where(mask, positions, 0)
            kv = P.gather_pages(pool, tables, dtype)
            logits, (k2, v2), _ = decode(params, tokens, pos_safe, kv,
                                         token_valid=mask[:, None])
            bidx = jnp.arange(tokens.shape[0])
            k_new = k2[:, bidx, pos_safe]  # [L, B, Hkv, hd]
            v_new = v2[:, bidx, pos_safe]
            page_ids = tables[bidx, pos_safe // ps]
            page_ids = jnp.where(mask, page_ids, 0)
            pool = P.scatter_tokens(pool, page_ids, pos_safe % ps, k_new, v_new)
            return logits, pool

        def verify_all(params, tokens, start, pool, tables, mask):
            """Gather-path verify oracle: dense view in, S written tokens
            scattered back per slot."""
            B, S = tokens.shape
            pos_safe = jnp.where(mask, start, 0)
            kv = P.gather_pages(pool, tables, dtype)
            logits, (k2, v2) = verify(
                params, tokens, pos_safe, kv,
                token_valid=jnp.broadcast_to(mask[:, None], tokens.shape))
            bidx = jnp.arange(B)
            positions = pos_safe[:, None] + jnp.arange(S, dtype=jnp.int32)[None, :]
            k_new = k2[:, bidx[:, None], positions]  # [L, B, S, Hkv, hd]
            v_new = v2[:, bidx[:, None], positions]
            page_ids = tables[bidx[:, None], positions // ps]
            page_ids = jnp.where(mask[:, None], page_ids, 0)
            L_ = k_new.shape[0]
            pool = P.scatter_tokens(
                pool, page_ids.reshape(-1), (positions % ps).reshape(-1),
                k_new.reshape(L_, B * S, *k_new.shape[3:]),
                v_new.reshape(L_, B * S, *v_new.shape[3:]))
            return logits, pool

    def prefill_chunk(params, tokens, start, table_row, pool, extra=None):
        """tokens [1, C] at absolute positions start..start+C for the slot
        mapped by ``table_row`` → (last-token logits, pool)."""
        kv = P.gather_pages(pool, table_row[None], dtype)
        logits, (k2, v2), _ = chunk(
            params, tokens, jnp.full((1,), start, jnp.int32), kv, extra)
        C = tokens.shape[1]
        k_c = jax.lax.dynamic_slice_in_dim(k2, start, C, axis=2)[:, 0]
        v_c = jax.lax.dynamic_slice_in_dim(v2, start, C, axis=2)[:, 0]
        pos = start + jnp.arange(C)
        pool = P.scatter_tokens(pool, table_row[pos // ps], pos % ps, k_c, v_c)
        return logits, pool

    if tp > 1:
        from jax.experimental.shard_map import shard_map
        from jax.sharding import PartitionSpec as PS

        R = PS()  # replicated (pytree-prefix spec for params and scalars)
        pspec = placement.pool_specs(pool_example)

        def smap(fn, in_specs):
            return jax.jit(shard_map(fn, mesh=placement.mesh,
                                     in_specs=in_specs, out_specs=(R, pspec),
                                     check_rep=False))

        # pool position differs per step; everything else is replicated
        decode_sm = smap(decode_all, (R, R, R, pspec, R, R))
        verify_sm = smap(verify_all, (R, R, R, pspec, R, R))
        chunk_sm = smap(lambda p, t, s, tr, pool, extra:
                        prefill_chunk(p, t, s, tr, pool, extra),
                        (R, R, R, R, pspec, R))
        chunk_fn = lambda p, t, s, tr, pool, extra=None: chunk_sm(
            p, t, s, tr, pool, extra)
        if decode_backend == "paged":
            prefill_sm = smap(prefill_all, (R, R, R, R, pspec, R, R))
            return PagedSteps(decode_sm, chunk_fn, verify_sm, prefill_sm)
        return PagedSteps(decode_sm, chunk_fn, verify_sm, None)

    if decode_backend == "paged":
        return PagedSteps(jax.jit(decode_all), jax.jit(prefill_chunk),
                          jax.jit(verify_all), jax.jit(prefill_all))
    # gather oracle: prefill stays the per-slot [1, C] + [1, 1] chunk loop
    return PagedSteps(jax.jit(decode_all), jax.jit(prefill_chunk),
                      jax.jit(verify_all), None)


class StateSteps(NamedTuple):
    """Jitted steps for :class:`~repro.serve.state_pool.StatePool` serving —
    the non-attention families' counterpart of :class:`PagedSteps`.  One
    uniform signature per step regardless of which planes the family has
    (absent planes ride through as ``None`` operands)."""

    # (params, tokens [B,1], positions [B], state, kv_tables, cross_tables,
    #  ring_read [B], ring_write [B], mask [B]) -> (logits [B,V], state)
    decode_all: Callable
    # (params, tokens [1,C], start, state, kv_row, cross_row, ring_read [1],
    #  ring_write [1], extra) -> (last-token logits [1,V], state)
    prefill_chunk: Callable
    # (params, embeds [1,T,D], cross_row, cross_pool) -> cross_pool;
    # None for families without a cross plane (ssm / hybrid)
    encode_cross: Callable | None

    def compile_counts(self) -> dict[str, int]:
        """Same key set as :meth:`PagedSteps.compile_counts` so the telemetry
        ``jit_compiled_*`` gauge catalog is backend-independent: state-pool
        engines have no verify step, and the once-per-admission encode-cross
        step reports under the otherwise-unused ``prefill_all`` key."""
        return {"decode_all": jit_cache_size(self.decode_all),
                "prefill_chunk": jit_cache_size(self.prefill_chunk),
                "verify_all": 0,
                "prefill_all": jit_cache_size(self.encode_cross)}


def build_state_steps(model: Model, *, method: str, pool,
                      placement=None) -> StateSteps:
    """Step builders over a :class:`~repro.serve.state_pool.StatePool`.

    Each step assembles the family's dense cache tree FROM the pool planes
    (gather-dequantize KV/cross pages, gather state-ring pages), runs the
    unmodified ``train.serve`` decode/chunk step, and scatters the updated
    state back: the written KV token(s) quantize into their pages, each
    lane's whole recurrent state quantizes into its ring WRITE page, and the
    cross plane is never written outside :func:`encode_cross`.  Gathered
    views are sliced to their exact logical lengths (``max_len`` self-KV,
    ``cross_tokens`` cross-KV) before the model sees them — cross attention
    is non-causal, so an unsliced page-granular tail would be attended.

    Exactness contract: with ``kv_dtype="dense"`` planes hold bit-exact
    values, so every family is token-exact against the ``DenseSlotCache``
    oracle; enc-dec prefill runs ``build_cross=False`` (reads the pooled
    cross-KV written once at admission instead of re-running the encoder per
    chunk), while VLM prefill passes ``extra`` through so its cross k/v are
    recomputed fresh exactly like the oracle (``attention`` ropes q iff
    ``kv_source is None`` — reading the pool during VLM prefill would change
    the q rotation) and the returned cross cache is discarded.

    ``placement`` (tp > 1, enc-dec/VLM only) wraps every step in
    ``jax.jit(shard_map(...))`` with both paged planes sharded on the
    KV-head axis — same mesh contract as :func:`build_paged_steps`; the
    recurrent-state rings have no head axis and are rejected upstream by the
    engine."""
    family = model.cfg.family
    tp = placement.tp if placement is not None else 1
    if tp > 1:
        import dataclasses

        from repro.models.registry import build_model

        model = build_model(dataclasses.replace(
            model.cfg, tp_axis=type(placement).AXIS, tp_size=tp))
    decode = make_decode_step(model, method=method)
    chunk = make_chunk_prefill_step(model, method=method, build_cross=False)
    compute_dtype = jnp.dtype(model.cfg.dtype)
    ps = pool.page_size
    max_len, Ts = pool.max_len, pool.cross_tokens
    rings = pool.rings
    has_kv, has_cross = pool.kv is not None, pool.cross is not None

    def _gather_kv(state, tables):
        k, v = P.gather_pages(state["kv"], tables, compute_dtype)
        return k[:, :, :max_len], v[:, :, :max_len]

    def _gather_cross(state, tables):
        k, v = P.gather_pages(state["cross"], tables, compute_dtype)
        return k[:, :, :Ts], v[:, :, :Ts]

    def _gather_rings(state, read_ids):
        return pool.unflatten_rings(
            r.gather(p, read_ids) for r, p in zip(rings, state["rings"]))

    def assemble(state, kv_tables, cross_tables, ring_read):
        if family == "ssm":
            return _gather_rings(state, ring_read)
        if family == "hybrid":
            return {"attn": _gather_kv(state, kv_tables),
                    "mamba": _gather_rings(state, ring_read)}
        return {"self": _gather_kv(state, kv_tables),
                "cross": _gather_cross(state, cross_tables)}

    def kv_of(new_caches):
        return new_caches["attn"] if family == "hybrid" else new_caches["self"]

    def rings_of(new_caches):
        return new_caches if family == "ssm" else new_caches["mamba"]

    def _scatter_rings(state, new_sub, write_ids):
        pools = tuple(
            r.scatter(p, write_ids, leaf)
            for r, p, leaf in zip(rings, state["rings"], jax.tree.leaves(new_sub)))
        return {**state, "rings": pools}

    def decode_all(params, tokens, positions, state, kv_tables, cross_tables,
                   ring_read, ring_write, mask):
        """One decode token for every slot: dense views gathered from the
        planes, the family's unmodified decode step, then quantize-on-write
        scatter-back.  Masked lanes read the zero ring sentinel / their stale
        tables and write to page 0 (KV) and ring page 0 (state) — the host
        never advances their ring cursor, so their logical state is
        untouched, the exact analogue of the dense path's ``merge_masked``."""
        pos_safe = jnp.where(mask, positions, 0)
        caches = assemble(state, kv_tables, cross_tables, ring_read)
        # no token_valid: none of the state families has MoE capacity routing
        # (the ssm block does not even accept it), matching the dense oracle
        logits, new_caches, _ = decode(params, tokens, pos_safe, caches)
        if has_kv:
            k2, v2 = kv_of(new_caches)
            bidx = jnp.arange(tokens.shape[0])
            k_new = k2[:, bidx, pos_safe]  # [L_kv, B, Hkv, hd]
            v_new = v2[:, bidx, pos_safe]
            page_ids = jnp.where(mask, kv_tables[bidx, pos_safe // ps], 0)
            state = {**state, "kv": P.scatter_tokens(
                state["kv"], page_ids, pos_safe % ps, k_new, v_new)}
        if rings:
            state = _scatter_rings(state, rings_of(new_caches), ring_write)
        return logits, state

    def prefill_chunk(params, tokens, start, state, kv_row, cross_row,
                      ring_read, ring_write, extra=None):
        """One slot's [1, C] prompt chunk: self-KV for positions
        start..start+C quantize-scatters into the slot's pages, the whole
        updated recurrent state lands in the ring write page, and any cross
        cache the model returned is discarded (the pooled cross plane was
        written at admission and is read-only afterwards)."""
        caches = assemble(state,
                          None if kv_row is None else kv_row[None],
                          None if cross_row is None else cross_row[None],
                          ring_read)
        logits, new_caches, _ = chunk(
            params, tokens, jnp.full((1,), start, jnp.int32), caches, extra)
        C = tokens.shape[1]
        if has_kv:
            k2, v2 = kv_of(new_caches)
            k_c = jax.lax.dynamic_slice_in_dim(k2, start, C, axis=2)[:, 0]
            v_c = jax.lax.dynamic_slice_in_dim(v2, start, C, axis=2)[:, 0]
            pos = start + jnp.arange(C)
            state = {**state, "kv": P.scatter_tokens(
                state["kv"], kv_row[pos // ps], pos % ps, k_c, v_c)}
        if rings:
            state = _scatter_rings(state, rings_of(new_caches), ring_write)
        return logits, state

    encode_cross = None
    if has_cross:
        if family == "encdec":
            from repro.models.encdec import encode_cross_kv as ckv
        else:
            from repro.models.vlm import encode_cross_kv as ckv
        mcfg = model.cfg

        def encode_cross(params, embeds, cross_row, cross_pool):
            """Write one request's cross-KV into its cross-plane pages, ONCE
            (admission time): [1, T_src, D] conditioning → stacked per-layer
            (k, v) → quantize-scatter over the slot's cross page row.  Params
            are cast exactly as the chunk/decode steps cast them, so a dense
            plane round-trips bit-identically to what a ``build_cross=True``
            prefill would have attended over."""
            cparams = _cast_params(params, compute_dtype)
            ks, vs = ckv(cparams, embeds, mcfg, jnp.uint32(0), method)
            if tp > 1:
                # the plane shard holds Hkv/tp local heads; the projection
                # above computed all of them — keep this shard's slice
                local = next(iter(cross_pool.values())).shape[2]
                if local != ks.shape[3]:
                    r = jax.lax.axis_index(type(placement).AXIS)
                    ks = jax.lax.dynamic_slice_in_dim(ks, r * local, local, axis=3)
                    vs = jax.lax.dynamic_slice_in_dim(vs, r * local, local, axis=3)
            T = ks.shape[2]
            pos = jnp.arange(T)
            return P.scatter_tokens(cross_pool, cross_row[pos // ps], pos % ps,
                                    ks[:, 0], vs[:, 0])

    if tp > 1:
        from jax.experimental.shard_map import shard_map
        from jax.sharding import PartitionSpec as PS

        R = PS()
        sspec = placement.pool_specs(pool.pools())
        cspec = sspec["cross"]

        def smap(fn, in_specs, out_specs):
            return jax.jit(shard_map(fn, mesh=placement.mesh,
                                     in_specs=in_specs, out_specs=out_specs,
                                     check_rep=False))

        decode_sm = smap(decode_all, (R, R, R, sspec, R, R, R, R, R), (R, sspec))
        chunk_sm = smap(lambda p, t, s, st, kr, cr, rr, rw, extra:
                        prefill_chunk(p, t, s, st, kr, cr, rr, rw, extra),
                        (R, R, R, sspec, R, R, R, R, R), (R, sspec))
        chunk_fn = lambda p, t, s, st, kr, cr, rr, rw, extra=None: chunk_sm(
            p, t, s, st, kr, cr, rr, rw, extra)
        enc_sm = smap(encode_cross, (R, R, R, cspec), cspec)
        return StateSteps(decode_sm, chunk_fn, enc_sm)

    return StateSteps(jax.jit(decode_all), jax.jit(prefill_chunk),
                      jax.jit(encode_cross) if encode_cross else None)
