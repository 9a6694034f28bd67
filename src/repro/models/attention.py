"""GQA attention behind one pluggable backend dispatch.

Every family (dense/MoE/hybrid/enc-dec/VLM) routes its attention through
:func:`attention` → :func:`dispatch_attention`, which selects the backend
from ``ModelConfig.attn_backend``:

* ``"blocked"`` — the differentiable jnp reference below: an online-softmax
  ``lax.scan`` over KV chunks; the S×S score matrix is never materialized,
  which is what makes the 32k-prefill and 500k-decode shapes lowerable.
* ``"flash"``   — the Pallas flash kernel (``kernels/flash_attention``), used
  for from-scratch self-attention (S == T); cached/offset shapes fall back
  to ``blocked``.
* ``"paged"``   — batched decode attends *directly over packed MXFP4 pages*
  via ``kernels/paged_attention`` whenever the cache operand is a
  :class:`~repro.kernels.paged_attention.PagedKV`; dense (non-decode) call
  sites behave as ``blocked``.

All four projections (QKV + output) go through the Quartet linear.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.kernels.paged_attention import PagedKV, paged_attention, scatter_token
from repro.models import layers as L

NEG_INF = -1e30


def init_attention(key, cfg: ModelConfig, dtype, d_kv_source: int | None = None):
    d, hd = cfg.d_model, cfg.head_dim_
    nq, nkv = cfg.num_heads, cfg.num_kv_heads
    dsrc = d_kv_source or d
    ks = jax.random.split(key, 6)
    p = {
        "wq": L.init_dense(ks[0], d, nq * hd, dtype, cfg.use_bias),
        "wk": L.init_dense(ks[1], dsrc, nkv * hd, dtype, cfg.use_bias),
        "wv": L.init_dense(ks[2], dsrc, nkv * hd, dtype, cfg.use_bias),
        "wo": L.init_dense(ks[3], nq * hd, d, dtype, cfg.use_bias),
    }
    if cfg.qk_norm:
        p["q_norm"] = L.init_rmsnorm(hd, dtype)
        p["k_norm"] = L.init_rmsnorm(hd, dtype)
    return p


def _split_heads(x, n, hd):
    return x.reshape(*x.shape[:-1], n, hd)


def blocked_attention(
    q: jnp.ndarray,  # [B, S, Hq, hd]
    k: jnp.ndarray,  # [B, T, Hkv, hd]
    v: jnp.ndarray,  # [B, T, Hkv, hd]
    q_positions: jnp.ndarray,  # [B, S] absolute positions
    causal: bool,
    kv_chunk: int,
    shared_mask: bool = True,  # rows share q_positions (train/prefill); False
    #                            for batched multi-token verify (per-slot
    #                            starts → genuinely per-row masks)
) -> jnp.ndarray:
    B, S, Hq, hd = q.shape
    T, Hkv = k.shape[1], k.shape[2]
    group = Hq // Hkv
    scale = 1.0 / np.sqrt(hd)
    # pad T up to a chunk multiple instead of shrinking the chunk: a 1500-
    # frame encoder would otherwise degrade to ck=4 → a 375-step scan whose
    # saved backward carries cost ~14 GB/device.  Padded keys are masked.
    T_orig = T
    ck = min(kv_chunk, T)
    pad_t = (-T) % ck
    if pad_t:
        k = jnp.pad(k, ((0, 0), (0, pad_t), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad_t), (0, 0), (0, 0)))
        T = T + pad_t
    nck = T // ck
    need_pad_mask = pad_t > 0

    # For S > 1 (train/prefill) every batch row uses the same arange
    # positions; building the mask per-row would materialize a [B,S,ck] pred
    # that XLA hoists out of the layer scan as a multi-GB loop invariant.
    # Row-shared masks are [S, ck] — 1000× smaller.  Decode (S == 1) and the
    # spec-verify burst (shared_mask=False: each slot starts at its own
    # position) keep genuinely per-row positions; both are small shapes.
    shared_rows = S > 1 and shared_mask
    mpos = q_positions[:1] if shared_rows else q_positions  # [1|B, S]

    qf = (q.astype(jnp.float32) * scale).reshape(B, S, Hkv, group, hd)
    # keep the KV stream in its storage dtype; casting the WHOLE cache to f32
    # up-front would materialize 2× the cache (16 GB for a 32k MHA decode) —
    # each chunk is cast in VMEM-sized pieces inside the scan body
    kc = k.reshape(B, nck, ck, Hkv, hd)
    vc = v.reshape(B, nck, ck, Hkv, hd)

    def body(carry, inp):
        m, l, acc = carry
        j, kj, vj = inp  # kj/vj: [B, ck, Hkv, hd]
        kj = kj.astype(jnp.float32)
        vj = vj.astype(jnp.float32)
        s = jnp.einsum("bskgd,bckd->bskgc", qf, kj,
                       preferred_element_type=jnp.float32)  # k=Hkv, g=group
        kv_pos = j * ck + jnp.arange(ck)
        if causal:
            mask = mpos[:, :, None, None, None] >= kv_pos[None, None, None, None, :]
            s = jnp.where(mask, s, NEG_INF)
        elif need_pad_mask:
            s = jnp.where(kv_pos[None, None, None, None, :] < T_orig, s, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        p = jnp.exp(s - m_new[..., None])
        corr = jnp.exp(m - m_new)
        l_new = l * corr + jnp.sum(p, axis=-1)
        pv = jnp.einsum("bskgc,bckd->bskgd", p, vj,
                        preferred_element_type=jnp.float32)
        acc_new = acc * corr[..., None] + pv
        return (m_new, l_new, acc_new), None

    m0 = jnp.full((B, S, Hkv, group), NEG_INF, jnp.float32)
    l0 = jnp.zeros((B, S, Hkv, group), jnp.float32)
    a0 = jnp.zeros((B, S, Hkv, group, hd), jnp.float32)
    (m, l, acc), _ = jax.lax.scan(
        body, (m0, l0, a0),
        (jnp.arange(nck), jnp.moveaxis(kc, 1, 0), jnp.moveaxis(vc, 1, 0)),
    )
    out = acc / jnp.maximum(l, 1e-30)[..., None]
    return out.reshape(B, S, Hq, hd).astype(q.dtype)


def dispatch_attention(
    q: jnp.ndarray,  # [B, S, Hq, hd]
    k: jnp.ndarray,  # [B, T, Hkv, hd]
    v: jnp.ndarray,  # [B, T, Hkv, hd]
    q_positions: jnp.ndarray,  # [B, S]
    *,
    causal: bool,
    cfg: ModelConfig,
    backend: str | None = None,
    shared_mask: bool = True,
) -> jnp.ndarray:
    """Single dense-attention call site: backend from ``cfg.attn_backend``.

    ``"flash"`` applies to from-scratch self-attention (S == T, where query
    row i sits at absolute position i — true for every no-cache forward in
    this codebase); cached/offset shapes fall back to the blocked reference.
    ``"paged"`` concerns decode-over-pages only (handled in :func:`attention`
    via the ``PagedKV`` cache type), so dense call sites treat it as
    ``blocked``.  ``shared_mask=False`` forces per-row causal masks (batched
    multi-token verify, where every slot starts at its own position).
    """
    backend = backend or cfg.attn_backend
    if backend == "flash" and q.shape[1] == k.shape[1] and shared_mask:
        from repro.kernels.flash_attention import mha_flash

        return mha_flash(q, k, v, causal=causal)
    return blocked_attention(q, k, v, q_positions, causal=causal,
                             kv_chunk=cfg.attn_kv_chunk,
                             shared_mask=shared_mask)


def _tp_slice_heads(q, k, v, cfg: ModelConfig, local_kv_heads: int):
    """Tensor-parallel head slicing inside a shard_map body: the KV cache
    operand carries ``local_kv_heads = Hkv / tp`` heads, so keep only this
    shard's contiguous KV-head block of the freshly-projected k/v — and its
    GQA query group (q's head ordering is kv-head-major: head ``h`` of group
    ``g`` sits at ``g * group + h``, the same ``reshape(B, S, Hkv, group,
    hd)`` layout both attention backends use).  Exactness-preserving: no
    arithmetic happens here, only a slice; the matching ``all_gather(...,
    tiled=True)`` on the attention output is a pure concat."""
    r = jax.lax.axis_index(cfg.tp_axis)
    group = cfg.num_heads // cfg.num_kv_heads
    k = jax.lax.dynamic_slice_in_dim(k, r * local_kv_heads, local_kv_heads, axis=2)
    v = jax.lax.dynamic_slice_in_dim(v, r * local_kv_heads, local_kv_heads, axis=2)
    q = jax.lax.dynamic_slice_in_dim(q, r * local_kv_heads * group,
                                     local_kv_heads * group, axis=2)
    return q, k, v


def _paged_decode(params, x, q, positions, seed, cfg: ModelConfig,
                  paged: PagedKV, method):
    """Batched decode/verify/prefill directly over the packed pool:
    quantize-scatter the S new tokens' KV (positions[b, s] drives the page
    lookup), then run the fused paged-attention kernel with per-row causal
    bounds.  S == 1 is plain decode; S > 1 is the speculative verify step
    (last accepted token + drafted suffix) or a batched prefill chunk (every
    prefilling slot's next S prompt tokens) scored in one call.  Positions
    fully drive write masking: the serve-side layout redirects padding /
    out-of-budget tokens to a page-table column holding the scratch page, so
    this function needs no mask operand."""
    hd, nkv = cfg.head_dim_, cfg.num_kv_heads
    qc = cfg.quartet
    k = _split_heads(L.dense(params["wk"], x, L.seed_fold(seed, 2), qc, method), nkv, hd)
    v = _split_heads(L.dense(params["wv"], x, L.seed_fold(seed, 3), qc, method), nkv, hd)
    if cfg.qk_norm:
        k = L.rmsnorm(params["k_norm"], k, cfg.norm_eps)
    if cfg.pos_embed == "rope":
        # slots sit at genuinely different offsets — never share row 0's angles
        k = L.apply_rope(k, positions, cfg.rope_theta, shared=False)

    kleaf = next(iter(paged.pool.values()))  # [n_pages, Hkv, ps, payload]
    local_heads, ps = kleaf.shape[1], kleaf.shape[2]
    # tp: a head-sharded pool slice announces itself by shape — each shard
    # quantize-scatters and attends over its local Hkv/tp heads only, then
    # all_gathers the group outputs (exact concat) before the wo projection
    tp_sharded = cfg.tp_axis is not None and local_heads != nkv
    if tp_sharded:
        q, k, v = _tp_slice_heads(q, k, v, cfg, local_heads)
    B, S = x.shape[0], x.shape[1]
    bidx = jnp.arange(B)
    page_ids = paged.tables[bidx[:, None], positions // ps]  # [B, S]
    pool = scatter_token(paged.pool, page_ids, positions % ps, k, v)
    lengths = positions[:, 0] + 1  # visible to the first query row
    if S == 1:
        out = paged_attention(q[:, 0], pool, paged.tables, lengths)[:, None]
    else:
        out = paged_attention(q, pool, paged.tables, lengths)
    if tp_sharded:
        out = jax.lax.all_gather(out, cfg.tp_axis, axis=2, tiled=True)
    return out, PagedKV(pool, paged.tables)


def attention(
    params,
    x: jnp.ndarray,  # [B, S, D]
    positions: jnp.ndarray,  # [B, S]
    seed: jnp.ndarray,
    cfg: ModelConfig,
    *,
    causal: bool = True,
    kv_source: jnp.ndarray | None = None,  # cross-attention source
    kv_cache=None,  # (k,v) [B,T,Hkv,hd] | PagedKV | None
    cache_index: jnp.ndarray | None = None,  # [B] write position for decode
    write_kv: bool = False,  # (re)build a full KV cache from kv_source (prefill)
    method: str = "quartet",
    backend: str | None = None,  # override cfg.attn_backend per call
):
    """Returns (out [B,S,D], new_kv_cache | None)."""
    hd, nq, nkv = cfg.head_dim_, cfg.num_heads, cfg.num_kv_heads
    qc = cfg.quartet
    # rows share positions (one arange) in training/prefill forwards; the
    # speculative verify scores rows at per-slot offsets and opts out via
    # its own model build (make_verify_step → attn_rows_shared=False)
    rows_shared = cfg.attn_rows_shared

    q = _split_heads(L.dense(params["wq"], x, L.seed_fold(seed, 1), qc, method), nq, hd)
    if cfg.qk_norm:
        q = L.rmsnorm(params["q_norm"], q, cfg.norm_eps)
    if cfg.pos_embed == "rope" and kv_source is None:
        q = L.apply_rope(q, positions, cfg.rope_theta, shared=rows_shared)

    if isinstance(kv_cache, PagedKV):
        # positions alone drives the paged path: page lookup, quantize-
        # scatter, and the kernel's per-row causal bounds (cache_index is
        # redundant with positions[:, 0] here)
        out, new_cache = _paged_decode(params, x, q, positions, seed, cfg,
                                       kv_cache, method)
        out = out.reshape(*x.shape[:-1], nq * hd)
        return L.dense(params["wo"], out, L.seed_fold(seed, 4), qc, method), new_cache

    new_cache = None
    tp_sharded = False  # head-sharded KV cache under a shard_map tp axis
    if kv_cache is not None and cache_index is None and not write_kv:
        # reuse fully-precomputed KV (e.g. cached cross-attention memory)
        k, v = kv_cache
        new_cache = kv_cache
        # tp: a head-sharded cached cross-KV announces itself by shape, like
        # the decode/insert branch below — but here k/v are ALREADY local, so
        # only q's matching GQA group needs slicing before the tiled
        # all_gather of the outputs
        tp_sharded = cfg.tp_axis is not None and k.shape[2] != nkv
        if tp_sharded:
            local = k.shape[2]
            group = nq // nkv
            r = jax.lax.axis_index(cfg.tp_axis)
            q = jax.lax.dynamic_slice_in_dim(
                q, r * local * group, local * group, axis=2)
    else:
        src = kv_source if kv_source is not None else x
        k = _split_heads(L.dense(params["wk"], src, L.seed_fold(seed, 2), qc, method), nkv, hd)
        v = _split_heads(L.dense(params["wv"], src, L.seed_fold(seed, 3), qc, method), nkv, hd)
        if cfg.qk_norm:
            k = L.rmsnorm(params["k_norm"], k, cfg.norm_eps)
        if cfg.pos_embed == "rope" and kv_source is None:
            k = L.apply_rope(k, positions, cfg.rope_theta, shared=rows_shared)
        if write_kv:  # build a full cache from kv_source (cross-attn prefill)
            new_cache = (k, v)
        elif kv_cache is not None:  # decode/prefill: insert S new entries at index
            ck_, cv_ = kv_cache
            # tp: a head-sharded dense cache (gather oracle under shard_map)
            # announces itself by shape, exactly like the paged pool
            tp_sharded = cfg.tp_axis is not None and ck_.shape[2] != nkv
            if tp_sharded:
                q, k, v = _tp_slice_heads(q, k, v, cfg, ck_.shape[2])
            upd = lambda c, n: jax.vmap(
                lambda cb, nb, i: jax.lax.dynamic_update_slice(cb, nb, (i, 0, 0))
            )(c, n.astype(c.dtype), cache_index)
            ck_, cv_ = upd(ck_, k), upd(cv_, v)
            k, v = ck_, cv_
            new_cache = (ck_, cv_)

    # note: a causal mask on q_positions subsumes the cache-validity mask
    # (queries at position p never look past p), so no kv_valid is needed.
    # Rows share one mask except when a batch of cached sequences is scored
    # at per-slot offsets (gather-backend spec verify): B > 1 ∧ cache writes.
    out = dispatch_attention(
        q, k, v, positions, causal=causal and kv_source is None,
        cfg=cfg, backend=backend, shared_mask=rows_shared,
    )
    if tp_sharded:
        out = jax.lax.all_gather(out, cfg.tp_axis, axis=2, tiled=True)
    out = out.reshape(*x.shape[:-1], nq * hd)
    out = L.dense(params["wo"], out, L.seed_fold(seed, 4), qc, method)
    return out, new_cache
