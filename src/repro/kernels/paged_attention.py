"""Fused MXFP4 paged-attention decode kernel (Pallas).

Batched decode attends *directly over the packed KV pool*: the per-slot page
table (scalar-prefetched, so it is available before the kernel body runs)
drives the KV block fetch via the ``BlockSpec`` index map — page ``p`` of
slot ``b`` pulls pool page ``tables[b, p]`` into VMEM.  E2M1 nibble codes and
E8M0 scale bytes are unpacked/dequantized per tile *inside* the kernel
(``kv_pack.unpack_dequant`` — pure arithmetic, VPU-friendly), so decode HBM
traffic is O(packed KV) = 4.25 bits/element instead of the O(dense KV)
gather-dequantize round-trip the engine previously paid.

Blocking is GQA-native: the grid is ``(B, Hkv, pages_per_slot)`` with pages
innermost; each (slot, KV-head) program streams that head's pages once and
computes all ``Hq/Hkv`` query heads of the group against it — KV heads are
read in place, never materialized ``group×`` (no ``jnp.repeat``).  Online
softmax state (m, l, acc) lives in VMEM scratch across the page loop;
per-slot valid-length masking (``lengths[b]``, i.e. decode position + 1)
handles ragged batches, and fully-invalid pages are skipped with ``pl.when``
(their DMA fetches the scratch page the allocator parks unmapped table
entries on).

Queries may carry a token axis (``q [B, S, Hq, hd]``): the speculative
verify scores the last accepted token plus S-1 drafted tokens per slot in
the same single pass — the query block grows to ``S·group`` rows and each
row's causal bound is offset by its token index (row ``r`` sees positions
≤ ``lengths[b] - 1 + r // group``), so drafts never attend past themselves.
Plain decode is the S == 1 special case of the same kernel.  **Batched paged
prefill** is the S == prefill_chunk case: every prefilling slot's chunk is
scored in one grid pass over the packed pool, with ragged tails handled by
:func:`prefill_chunk_layout` — padding tokens are positioned on a sentinel
scratch column appended to the page table, so their quantize-on-write lands
on page 0 and their (garbage) output rows carry per-row causal bounds past
every valid row's, never contaminating real tokens.

``PagedKV`` is the pytree that threads this state through the model's
layer scan: pool leaves carry a leading ``[L]`` axis and are consumed one
layer-slice per scan step; ``tables`` is broadcast to ``[L, B, P]`` so each
slice sees the same page mapping.  ``models.attention`` dispatches to this
kernel whenever the decode cache is a ``PagedKV`` (see its backend matrix).

Validated in interpret mode against ``models.attention.blocked_attention``
over page-size / GQA / ragged-length / dense-vs-mxfp4 sweeps in
tests/test_paged_attention.py.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core import formats as F
from repro.core import quantizers as Q
from repro.kernels.backend import interpret_mode
from repro.kernels.kv_pack import unpack_dequant

GROUP = 32
NEG_INF = -1e30


class PagedKV(NamedTuple):
    """Paged decode-attention state (a pytree, scannable over layers).

    ``pool``   — dict of pool leaves.  Packed mode: ``k_codes``/``v_codes``
                 u8 [..., n_pages, Hkv, ps, hd/2] + ``k_scales``/``v_scales``
                 u8 [..., n_pages, Hkv, ps, hd/block]; dense mode: ``k``/``v``
                 in the compute dtype [..., n_pages, Hkv, ps, hd].  The KV-head
                 axis sits ahead of the token axis so one (page, head) block
                 is a whole ``[ps, payload]`` tile — the last two dims of the
                 array, which is what Mosaic requires of a block.
    ``tables`` — int32 [..., B, pages_per_slot] page table (masked lanes'
                 rows zeroed by the engine so their writes land on the
                 reserved scratch page 0).

    Leaves carry a leading ``[L]`` axis when used as a layer-scan xs.
    """

    pool: dict
    tables: jnp.ndarray


def quant_block(hd: int) -> int:
    """MXFP4 scale-block size clamped to the head dim (blocks never straddle
    heads; reduced configs use hd=32, full configs 128 — both divide)."""
    return GROUP if hd % GROUP == 0 else hd


def quant_fmt(hd: int) -> F.Format:
    return dataclasses.replace(F.MXFP4, block=quant_block(hd))


def scatter_token(pool: dict, page_ids: jnp.ndarray, offsets: jnp.ndarray,
                  k_new: jnp.ndarray, v_new: jnp.ndarray) -> dict:
    """Write tokens into a single layer's pool slice.

    page_ids/offsets share any leading shape ``[...]`` (``[B]`` for decode,
    ``[B, S]`` for a speculative verify burst); k_new/v_new are
    ``[..., Hkv, hd]``.  Quantize-on-write in packed mode.  Duplicate
    (page, offset) pairs (masked lanes redirected to the scratch page)
    resolve arbitrarily — scratch contents are never read.

    The index ``[page_ids, :, offsets]`` puts its broadcast ``[...]`` dims
    first (the two index arrays are split by the head slice), so the
    ``[..., Hkv, payload]`` values land as they come.
    """
    if "k" in pool:
        return {
            "k": pool["k"].at[page_ids, :, offsets].set(k_new.astype(pool["k"].dtype)),
            "v": pool["v"].at[page_ids, :, offsets].set(v_new.astype(pool["v"].dtype)),
        }
    fmt = quant_fmt(k_new.shape[-1])
    kq, vq = Q.kv_quantize(k_new, fmt), Q.kv_quantize(v_new, fmt)
    return {
        "k_codes": pool["k_codes"].at[page_ids, :, offsets].set(kq.codes),
        "k_scales": pool["k_scales"].at[page_ids, :, offsets].set(kq.scales),
        "v_codes": pool["v_codes"].at[page_ids, :, offsets].set(vq.codes),
        "v_scales": pool["v_scales"].at[page_ids, :, offsets].set(vq.scales),
    }


def prefill_chunk_layout(
    tables: jnp.ndarray,  # [B, P] int32 (masked lanes' rows already zeroed)
    start: jnp.ndarray,  # [B] int32 — absolute position of each chunk's row 0
    n_valid: jnp.ndarray,  # [B] int32 — real tokens in each row (1..C)
    chunk: int,  # C, the (static) padded chunk width
    page_size: int,
    mask: jnp.ndarray,  # [B] bool — slot actively prefilling this tick
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Per-row write masking for a ragged batched-prefill chunk.

    Returns ``(tables_ext [B, P+1], positions [B, C])``:

    * ``tables_ext`` appends one all-zero **sentinel column** to the page
      tables.  Page-table reads clamp out-of-range columns, so without the
      sentinel an overlong position could clamp onto the *last mapped* page
      and clobber live KV; with it, every out-of-range column lands on the
      reserved scratch page 0.
    * ``positions[b, s]`` is ``start[b] + s`` for valid tokens.  Padding
      tokens of active rows are positioned at ``P * page_size`` — exactly the
      sentinel column — so their quantize-on-write goes to scratch; inactive
      lanes sit at position 0 of their zeroed table row (also scratch) and
      keep the page loop's per-slot trip count at one.

    The kernel needs no other change: per-row causal bounds come from
    ``positions[:, 0] + r // group``, and a valid token at ``start + s``
    never sees a padding position (``start + s' > start + s`` for every
    padding ``s'``), so garbage flows only into garbage rows.
    """
    B, P = tables.shape
    tables_ext = jnp.concatenate(
        [tables, jnp.zeros((B, 1), tables.dtype)], axis=1)
    s = jnp.arange(chunk, dtype=jnp.int32)[None, :]
    valid = mask[:, None] & (s < n_valid[:, None])
    start_safe = jnp.where(mask, start, 0).astype(jnp.int32)
    sentinel = jnp.int32(P * page_size)
    positions = jnp.where(
        valid, start_safe[:, None] + s,
        jnp.where(mask[:, None], sentinel, 0))
    return tables_ext, positions


# ---------------------------------------------------------------------------
# kernel bodies
# ---------------------------------------------------------------------------


def _online_softmax_tile(q, k, v, kv_pos, q_pos, m_ref, l_ref, acc_ref):
    """One [rows, ps] score tile folded into the running (m, l, acc);
    ``q_pos`` is [rows, 1] — each query row carries its own causal bound."""
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)  # [rows, ps]
    s = jnp.where(kv_pos <= q_pos, s, NEG_INF)
    m_prev = m_ref[...]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
    p = jnp.exp(s - m_new)
    corr = jnp.exp(m_prev - m_new)
    l_ref[...] = l_ref[...] * corr + jnp.sum(p, axis=-1, keepdims=True)
    acc_ref[...] = acc_ref[...] * corr + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
    m_ref[...] = m_new


def _paged_kernel(tbl_ref, len_ref, q_ref, *rest,
                  load_kv, ps: int, n_pp: int, group: int, n_q: int,
                  scale: float):
    """One (slot, KV-head, page) step; ``load_kv(kv_refs)`` materializes the
    page's [ps, hd] f32 K/V tiles (pool-dtype-specific — the only part that
    differs between the packed and dense pools).

    The query block is [n_q·group, hd]: ``n_q`` consecutive decode/verify
    tokens × the KV head's GQA group.  Row ``r`` belongs to query token
    ``r // group`` sitting at absolute position ``len_ref[b] - 1 + r//group``
    — speculative verify scores all drafted tokens in one pass with per-row
    causal bounds; plain decode is the n_q == 1 special case."""
    *kv_refs, o_ref, m_ref, l_ref, acc_ref = rest
    b, p = pl.program_id(0), pl.program_id(2)

    @pl.when(p == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    length = len_ref[b]  # tokens visible to the FIRST query row

    @pl.when(p * ps < length + n_q - 1)
    def _body():
        q = q_ref[0, 0].astype(jnp.float32) * scale  # [n_q*group, hd]
        k, v = load_kv(kv_refs)
        kv_pos = p * ps + jax.lax.broadcasted_iota(jnp.int32, (1, ps), 1)
        rows = jax.lax.broadcasted_iota(jnp.int32, (n_q * group, 1), 0)
        q_pos = length - 1 + rows // group
        _online_softmax_tile(q, k, v, kv_pos, q_pos, m_ref, l_ref, acc_ref)

    @pl.when(p == n_pp - 1)
    def _flush():
        o_ref[0, 0] = (acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)).astype(o_ref.dtype)


def _load_kv_mxfp4(block: int):
    def load(kv_refs):
        kc, ks, vc, vs = kv_refs
        return (unpack_dequant(kc[0, 0], ks[0, 0], block),
                unpack_dequant(vc[0, 0], vs[0, 0], block))
    return load


def _load_kv_dense(kv_refs):
    k_ref, v_ref = kv_refs
    return k_ref[0, 0].astype(jnp.float32), v_ref[0, 0].astype(jnp.float32)


# ---------------------------------------------------------------------------
# entry point
# ---------------------------------------------------------------------------


@functools.partial(jax.jit, static_argnames=("interpret",))
def paged_attention(
    q: jnp.ndarray,  # [B, Hq, hd] (one decode query per slot) or [B, S, Hq, hd]
    pool: dict,  # one layer's pool slice (packed or dense leaves)
    tables: jnp.ndarray,  # [B, pages_per_slot] int32
    lengths: jnp.ndarray,  # [B] int32 — tokens visible to the FIRST query
    #                        (its position + 1); query s sees lengths + s
    interpret: bool | None = None,
) -> jnp.ndarray:
    """Decode/verify attention directly over the paged pool.

    ``q`` may carry a token axis S > 1 (speculative verify: the last accepted
    token plus the drafted suffix) — all S tokens of a slot are scored in one
    grid pass with per-row causal bounds.  Returns [B, Hq, hd] for 3-d ``q``,
    [B, S, Hq, hd] for 4-d."""
    multi = q.ndim == 4
    if not multi:
        q = q[:, None]
    B, S, Hq, hd = q.shape
    quantized = "k_codes" in pool
    kleaf = pool["k_codes"] if quantized else pool["k"]
    Hkv, ps = kleaf.shape[1], kleaf.shape[2]
    group = Hq // Hkv
    n_pp = tables.shape[1]
    scale = 1.0 / np.sqrt(hd)
    # [B, S, Hkv, group, hd] → [B, Hkv, S·group, hd]: row r = token r//group,
    # query head (r%group) of the program's KV head
    qg = (q.reshape(B, S, Hkv, group, hd)
          .transpose(0, 2, 1, 3, 4)
          .reshape(B, Hkv, S * group, hd))

    def kv_idx(b, h, p, tbl, ln):
        del ln
        return (tbl[b, p], h, 0, 0)

    def q_idx(b, h, p, tbl, ln):
        del p, tbl, ln
        return (b, h, 0, 0)

    if quantized:
        block = quant_block(hd)
        load_kv = _load_kv_mxfp4(block)
        kv_specs = [
            pl.BlockSpec((1, 1, ps, hd // 2), kv_idx),
            pl.BlockSpec((1, 1, ps, hd // block), kv_idx),
            pl.BlockSpec((1, 1, ps, hd // 2), kv_idx),
            pl.BlockSpec((1, 1, ps, hd // block), kv_idx),
        ]
        operands = (pool["k_codes"], pool["k_scales"],
                    pool["v_codes"], pool["v_scales"])
    else:
        load_kv = _load_kv_dense
        kv_specs = [
            pl.BlockSpec((1, 1, ps, hd), kv_idx),
            pl.BlockSpec((1, 1, ps, hd), kv_idx),
        ]
        operands = (pool["k"], pool["v"])
    rows = S * group
    kern = functools.partial(_paged_kernel, load_kv=load_kv, ps=ps, n_pp=n_pp,
                             group=group, n_q=S, scale=scale)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B, Hkv, n_pp),
        in_specs=[pl.BlockSpec((1, 1, rows, hd), q_idx), *kv_specs],
        out_specs=pl.BlockSpec((1, 1, rows, hd), q_idx),
        scratch_shapes=[
            pltpu.VMEM((rows, 1), jnp.float32),  # running max
            pltpu.VMEM((rows, 1), jnp.float32),  # running denom
            pltpu.VMEM((rows, hd), jnp.float32),  # running numerator
        ],
    )
    out = pl.pallas_call(
        kern,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, Hkv, rows, hd), q.dtype),
        interpret=interpret_mode(interpret),
    )(tables, lengths, qg, *operands)
    out = (out.reshape(B, Hkv, S, group, hd)
           .transpose(0, 2, 1, 3, 4)
           .reshape(B, S, Hq, hd))
    return out if multi else out[:, 0]
