"""Fused MXFP4 paged attention (Pallas): decode, verify and prefill over the
packed KV pool.

Batched decode attends *directly over the packed KV pool*: the per-slot page
table and lengths are scalar-prefetched (in SMEM before the body runs) and
drive the KV fetch through the ``BlockSpec`` index maps.  E2M1 nibble codes
and E8M0 scale bytes are unpacked/dequantized per block *inside* the kernel
(``kv_pack.unpack_dequant_split`` — pure arithmetic on the VPU), so HBM
traffic is O(packed KV) = 4.25 bits/element instead of the O(dense KV)
gather-dequantize round-trip.

**The walk.**  The grid is ``(B, Hkv / hb, ceil(P / ppb))`` for ``P`` page
columns: one step per (slot, head block, block of ``ppb`` pages).  A step
reads ``ppb`` pages × ``hb`` KV heads — per page and pool leaf one
``[hb, ps, payload]`` slab, a single DMA — and the pipeline issues the next
step's whole wave while this one computes (double-buffered).  Each slot's
walk stops at its live pages, ``ceil((lengths[b] + n_q - 1) / ps)``: past
the last live block every page index repeats, so the pipeline fetches
nothing and the body is skipped; a dead column inside the last live block
re-uses a page the step already holds.  No dead page is ever read (a page
table may point dead columns anywhere).  A slot at length 1 — a masked lane
on the scratch page — walks one block.

**Block sizes follow the shapes** (:func:`block_sizes`): a wave carries
about ``WAVE_TOKENS`` = 256 tokens (``ppb = 256 / ps`` pages, at most the
table's width), and ``hb`` is the largest divisor of Hkv whose VMEM
footprint (q and output blocks, the running m/l/acc, both wave buffers, one
head's scores and K/V) fits ``VMEM_BUDGET``: every head in decode, fewer
where wide prefill rows need the room.  No option chooses them.

GQA is native: each head of a block scores all ``Hq/Hkv`` query heads of its
group against that head's K/V, read in place (no ``jnp.repeat``), with f32
scores, ``1/sqrt(hd)`` scaling and an online softmax (m, l, acc in VMEM
scratch) over the blocks; the output is cast to ``q.dtype``.  In the packed
pool the K/V values come out in *split order* (the even head dims in the
first half of the lanes, the odd in the second: no lane interleave), so the
wrapper puts q in the same order (q·k is the same sum) and the output back.

Queries may carry a token axis (``q [B, S, Hq, hd]``): the speculative
verify scores the last accepted token plus S-1 drafted tokens per slot in
the same single pass — the query block grows to ``S·group`` rows and each
row's causal bound is offset by its token index (row ``r`` sees positions
≤ ``lengths[b] - 1 + r // group``), so drafts never attend past themselves.
Plain decode is the S == 1 special case of the same kernel.  **Batched paged
prefill** is the S == prefill_chunk case: every prefilling slot's chunk is
scored in one pass over the packed pool, with ragged tails handled by
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
over page-size / GQA / ragged-length / multi-block / dense-vs-mxfp4 sweeps,
and with every dead page poisoned, in tests/test_paged_attention.py; the
Mosaic lowering at the benchmark cells' shapes in tests/test_tpu_compile.py.
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
from repro.kernels.kv_pack import interleave_order, split_order, unpack_dequant_split

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
# block sizes (from static shapes only)
# ---------------------------------------------------------------------------

WAVE_TOKENS = 256  # KV tokens a DMA wave aims to carry (pages_per_block · ps)
VMEM_LIMIT = 32 * 2**20  # scoped VMEM the kernel asks Mosaic for
VMEM_BUDGET = 24 * 2**20  # what the block choice may fill of it


def _tile_bytes(shape, itemsize: int) -> int:
    """VMEM bytes of an array whose last two dims are padded to the TPU tile:
    128 lanes by 8 sublanes of 32 bits (16 rows of bf16, 32 of u8)."""
    *lead, r, c = shape
    sub = 8 * (4 // itemsize)
    return (int(np.prod(lead, dtype=np.int64)) * -(-r // sub) * sub
            * -(-c // 128) * 128 * itemsize)


def block_sizes(ps: int, n_heads: int, hd: int, rows: int, n_pp: int,
                leaves, q_itemsize: int) -> tuple[int, int]:
    """``(pages_per_block, heads_per_block)`` for one kernel call.

    A wave carries about ``WAVE_TOKENS`` tokens (never more pages than a
    slot has).  The head block is the largest divisor of ``n_heads`` whose
    VMEM footprint fits ``VMEM_BUDGET``: the q and output blocks (double-
    buffered by the pipeline), the running (m, l, acc), both buffers of every
    leaf's wave, and one head's scores and dequantized K/V.  ``leaves`` is
    ``[(payload, itemsize), ...]`` of the pool leaves.  Decode rows are few,
    so every head fits; wide prefill rows may split the heads."""
    ppb = max(1, min(n_pp, WAVE_TOKENS // ps))
    T = ppb * ps
    for hb in sorted((d for d in range(1, n_heads + 1) if n_heads % d == 0),
                     reverse=True):
        need = (4 * _tile_bytes((hb, rows, hd), q_itemsize)
                + 2 * _tile_bytes((hb, rows, 1), 4)
                + _tile_bytes((hb, rows, hd), 4)
                + sum(2 * _tile_bytes((ppb * hb, ps, w), isz) for w, isz in leaves)
                + 4 * _tile_bytes((rows, T), 4)
                + 8 * _tile_bytes((T, hd), 4))
        if need <= VMEM_BUDGET:
            return ppb, hb
    return ppb, 1


def walk_pages(tables: jnp.ndarray, lengths: jnp.ndarray, n_q: int, ps: int,
               ppb: int) -> jnp.ndarray:
    """The pool page each step of the walk reads: [B, n_blocks · ppb], entry
    ``j·ppb + c`` for column ``c`` of page block ``j``.

    A slot's live columns are ``ceil((lengths[b] + n_q - 1) / ps)`` (at least
    one).  Past its last live block every block repeats that block's pages,
    so the pipeline fetches nothing there.  A dead column inside the last
    live block keeps the page its buffer holds from the block before (or, in
    a one-block walk, takes the last live page).  So no dead page is ever
    read, wherever the table points its dead columns."""
    B, n_pp = tables.shape
    n_blk = -(-n_pp // ppb)
    n_live = jnp.maximum(-(-(lengths + n_q - 1) // ps), 1)[:, None, None]
    j = jnp.arange(n_blk, dtype=jnp.int32)[None, :, None]
    c = jnp.arange(ppb, dtype=jnp.int32)[None, None, :]
    jj = jnp.minimum(j, -(-n_live // ppb) - 1)
    col = jj * ppb + c
    col = jnp.where(col < n_live, col, jnp.where(jj > 0, col - ppb, n_live - 1))
    col = jnp.minimum(col, n_pp - 1).reshape(B, n_blk * ppb)
    return jnp.take_along_axis(tables, col, axis=1)


# ---------------------------------------------------------------------------
# kernel bodies
# ---------------------------------------------------------------------------


def _online_softmax_tile(q, k, v, kv_pos, q_pos, m_ref, l_ref, acc_ref):
    """One [rows, T] score tile folded into the running (m, l, acc);
    ``q_pos`` is [rows, 1] — each query row carries its own causal bound."""
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)  # [rows, T]
    s = jnp.where(kv_pos <= q_pos, s, NEG_INF)
    m_prev = m_ref[...]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
    p = jnp.exp(s - m_new)
    corr = jnp.exp(m_prev - m_new)
    l_ref[...] = l_ref[...] * corr + jnp.sum(p, axis=-1, keepdims=True)
    acc_ref[...] = acc_ref[...] * corr + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
    m_ref[...] = m_new


def _paged_kernel(walk_ref, len_ref, q_ref, *rest, n_leaves: int, load_kv,
                  ps: int, ppb: int, n_blk_max: int, hb: int, group: int,
                  n_q: int, scale: float):
    """One (slot, head block, page block) step: ``ppb`` pages of ``hb`` KV
    heads, fetched by the pipeline one step ahead as one DMA wave
    (``walk_ref``, from :func:`walk_pages`, names the pages).

    ``rest`` holds the wave's KV tiles (``ppb`` refs per pool leaf, each
    [1, hb, ps, payload]), the output block and the running (m, l, acc).
    ``load_kv(kv_refs, h)`` materializes head ``h``'s [T, hd] f32 K/V tiles
    of the block (pool-dtype-specific — the only part that differs between
    the packed and dense pools).

    The query block is [hb, n_q·group, hd]: ``n_q`` consecutive decode/verify
    /prefill tokens × each KV head's GQA group.  Row ``r`` belongs to query
    token ``r // group`` sitting at absolute position ``len_ref[b] - 1 +
    r//group``; plain decode is the n_q == 1 special case."""
    kv_refs = [rest[i * ppb:(i + 1) * ppb] for i in range(n_leaves)]
    o_ref, m_ref, l_ref, acc_ref = rest[n_leaves * ppb:]
    b, j = pl.program_id(0), pl.program_id(2)
    T = ppb * ps
    length = len_ref[b]  # tokens visible to the FIRST query row
    live = length + n_q - 1  # tokens visible to the LAST query row

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(j < pl.cdiv(live, T))
    def _body():
        kv_pos = j * T + jax.lax.broadcasted_iota(jnp.int32, (1, T), 1)
        rows = jax.lax.broadcasted_iota(jnp.int32, (n_q * group, 1), 0)
        q_pos = length - 1 + rows // group

        @pl.loop(0, hb)
        def _head(h):
            q = q_ref[0, h].astype(jnp.float32) * scale  # [n_q*group, hd]
            k, v = load_kv(kv_refs, h)
            _online_softmax_tile(q, k, v, kv_pos, q_pos,
                                 m_ref.at[h], l_ref.at[h], acc_ref.at[h])

    @pl.when(j == n_blk_max - 1)
    def _flush():
        o_ref[0] = (acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)).astype(o_ref.dtype)


def _cat(refs, h, dtype):
    """Head ``h`` of a wave's pages, stacked along tokens: [ppb·ps, payload]."""
    return jnp.concatenate([r[0, h].astype(dtype) for r in refs], axis=0)


def _load_kv_mxfp4(block: int):
    """K/V in the split head-dim order of ``unpack_dequant_split``; the
    wrapper puts q in the same order and the output back."""
    def load(kv_refs, h):
        kc, ks, vc, vs = (_cat(refs, h, jnp.int32) for refs in kv_refs)
        return unpack_dequant_split(kc, ks, block), unpack_dequant_split(vc, vs, block)
    return load


def _load_kv_dense(kv_refs, h):
    k, v = kv_refs
    return _cat(k, h, jnp.float32), _cat(v, h, jnp.float32)


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
    """Decode/verify/prefill attention directly over the paged pool.

    ``q`` may carry a token axis S > 1 (speculative verify: the last accepted
    token plus the drafted suffix; batched prefill: a chunk) — all S tokens
    of a slot are scored in one pass with per-row causal bounds.  Returns
    [B, Hq, hd] for 3-d ``q``, [B, S, Hq, hd] for 4-d."""
    multi = q.ndim == 4
    if not multi:
        q = q[:, None]
    B, S, Hq, hd = q.shape
    leaf_names = (("k_codes", "k_scales", "v_codes", "v_scales")
                  if "k_codes" in pool else ("k", "v"))
    operands = tuple(pool[n] for n in leaf_names)
    Hkv, ps = operands[0].shape[1], operands[0].shape[2]
    group = Hq // Hkv
    n_pp = tables.shape[1]
    rows = S * group
    ppb, hb = block_sizes(
        ps, Hkv, hd, rows, n_pp,
        [(x.shape[3], x.dtype.itemsize) for x in operands], q.dtype.itemsize)
    quantized = len(operands) == 4
    load_kv = _load_kv_mxfp4(quant_block(hd)) if quantized else _load_kv_dense
    # [B, S, Hkv, group, hd] → [B, Hkv, S·group, hd]: row r = token r//group,
    # query head (r%group) of its KV head
    qg = (q.reshape(B, S, Hkv, group, hd)
          .transpose(0, 2, 1, 3, 4)
          .reshape(B, Hkv, rows, hd))
    if quantized:  # q·k is the same sum with both in split order
        qg = split_order(qg)

    walk = walk_pages(tables, lengths, S, ps, ppb)
    n_blk_max = walk.shape[1] // ppb

    def q_idx(b, g, j, wk, ln):
        del j, wk, ln
        return (b, g, 0, 0)

    def kv_idx(c):
        def idx(b, g, j, wk, ln):
            del ln
            return (wk[b, j * ppb + c], g, 0, 0)
        return idx

    kern = functools.partial(
        _paged_kernel, n_leaves=len(operands), load_kv=load_kv, ps=ps, ppb=ppb,
        n_blk_max=n_blk_max, hb=hb, group=group, n_q=S, scale=1.0 / np.sqrt(hd))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B, Hkv // hb, n_blk_max),
        in_specs=[pl.BlockSpec((1, hb, rows, hd), q_idx),
                  *[pl.BlockSpec((1, hb, ps, x.shape[3]), kv_idx(c))
                    for x in operands for c in range(ppb)]],
        out_specs=pl.BlockSpec((1, hb, rows, hd), q_idx),
        scratch_shapes=[
            pltpu.VMEM((hb, rows, 1), jnp.float32),  # running max
            pltpu.VMEM((hb, rows, 1), jnp.float32),  # running denom
            pltpu.VMEM((hb, rows, hd), jnp.float32),  # running numerator
        ],
    )
    out = pl.pallas_call(
        kern,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, Hkv, rows, hd), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=VMEM_LIMIT),
        interpret=interpret_mode(interpret),
    )(walk, lengths, qg, *[x for x in operands for _ in range(ppb)])
    if quantized:
        out = interleave_order(out)
    out = (out.reshape(B, Hkv, S, group, hd)
           .transpose(0, 2, 1, 3, 4)
           .reshape(B, S, Hq, hd))
    return out if multi else out[:, 0]
