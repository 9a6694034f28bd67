"""Pallas kernels for the serving engine's FP4 KV-cache pages.

Quantize-on-write / dequantize-on-read for persistent decode state: one
VMEM-resident pass per KV page fuses

  1. per-32-group AbsMax scale computation,
  2. E8M0 (nearest power-of-two) scale rounding → biased-exponent uint8,
  3. E2M1 round-to-nearest downcast (``core.formats.rtn_e2m1``: arithmetic
     ties-to-even — the only form that lowers in a v5e kernel body, where
     Mosaic refuses 4-bit float conversions),
  4. nibble packing: two 4-bit codes per byte (S EE M bit layout),

writing the *real* 4.25-bit payload (codes + scale exponents) back to HBM.
The unpack kernel inverts arithmetically: magnitude = 2^((i-2)>>1)·(1+m/2)
for normal codes, i/2 for the subnormal region — no table gathers, so both
bodies map onto the VPU.  Mosaic has no uint8 <-> float32 cast, so every
byte is widened to int32 before any arithmetic and narrowed from int32 at
the store.  Semantics are verified against the jnp reference pair
``core.quantizers.kv_quantize`` / ``kv_dequantize`` in
tests/test_serve_engine.py (bit-identical payloads).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core import formats as F
from repro.kernels.backend import interpret_mode

GROUP = 32
_E2M1_MAX = 6.0
# magnitude index of ±6.0 — a quantized element sitting on this code was at
# (or clipped to) the top of the E2M1 grid; the fraction of such codes in the
# pool is the FP4 saturation / clip-rate gauge (telemetry.quant_health)
E2M1_SAT_IDX = 7


def _exp2i(e: jnp.ndarray) -> jnp.ndarray:
    bits = (e.astype(jnp.int32) + 127) << 23
    return jax.lax.bitcast_convert_type(bits, jnp.float32)


def split_nibbles(packed: jnp.ndarray) -> jnp.ndarray:
    """Packed bytes [..., K/2] → nibble codes [..., K] of the same integer
    dtype (high nibble first — the pack order of ``_kv_quant_pack_kernel``).
    Shared by :func:`unpack_dequant` and the pool-health reductions
    (``serve.telemetry.quant_health``), which inspect codes without
    dequantizing."""
    *lead, kh = packed.shape
    return jnp.stack([(packed >> 4) & 0xF, packed & 0xF],
                     axis=-1).reshape(*lead, kh * 2)


def _e2m1_value(nib: jnp.ndarray) -> jnp.ndarray:
    """int32 nibble codes (S EE M) → f32 E2M1 values, by arithmetic."""
    idx = nib & 7
    mag_norm = _exp2i((idx - 2) >> 1) * (1.0 + 0.5 * (idx & 1).astype(jnp.float32))
    mag = jnp.where(idx >= 2, mag_norm, idx.astype(jnp.float32) * 0.5)
    return jnp.where((nib & 8) > 0, -mag, mag)


def unpack_dequant(packed: jnp.ndarray, scale_codes: jnp.ndarray,
                   block: int = GROUP) -> jnp.ndarray:
    """Packed nibble codes [..., K/2] u8 + E8M0 scale codes [..., K/block] u8
    → f32 values [..., K].  Pure arithmetic (no table gathers) so it lowers
    on the VPU — the body of the unpack kernel below."""
    val = _e2m1_value(split_nibbles(packed.astype(jnp.int32)))
    # per-element scale by lane repeat: Mosaic refuses the [..., K/block,
    # block] lane-splitting reshape
    scale = _exp2i(scale_codes.astype(jnp.int32) - 127)
    return val * jnp.repeat(scale, block, axis=-1)


def unpack_dequant_split(packed: jnp.ndarray, scale_codes: jnp.ndarray,
                         block: int = GROUP) -> jnp.ndarray:
    """:func:`unpack_dequant` with the K values in *split order*: the high
    nibbles' elements (even indices) in lanes ``[0, K/2)``, the low nibbles'
    (odd indices) in ``[K/2, K)``.  Each value is the same product of the
    same code and scale; only its lane differs.  The halves sit side by side
    instead of interleaved, so no lane shuffle is needed: the interleave
    costs Mosaic seconds of compile per [256, K/2] tile and most of the
    unpack's VPU work.  The fused paged-attention kernel
    (``kernels/paged_attention.py``) calls it per VMEM-resident KV block and
    puts its queries and outputs in the same order."""
    packed = packed.astype(jnp.int32)
    half = packed.shape[-1]
    val = _e2m1_value(jnp.concatenate([(packed >> 4) & 0xF, packed & 0xF], axis=-1))
    scale = _exp2i(scale_codes.astype(jnp.int32) - 127)
    # lane l holds element 2(l mod K/2) (+1 in the upper half), whose scale
    # block is (l mod K/2) // (block/2): a select per block, no lane repeat
    lane = jax.lax.broadcasted_iota(jnp.int32, val.shape, val.ndim - 1)
    blk = jnp.where(lane >= half, lane - half, lane) // (block // 2)
    s = scale[..., 0:1]
    for i in range(1, scale.shape[-1]):
        s = jnp.where(blk == i, scale[..., i:i + 1], s)
    return val * s


def split_order(x: jnp.ndarray) -> jnp.ndarray:
    """[..., K] → the split order of :func:`unpack_dequant_split`."""
    return jnp.concatenate([x[..., 0::2], x[..., 1::2]], axis=-1)


def interleave_order(x: jnp.ndarray) -> jnp.ndarray:
    """Inverse of :func:`split_order`."""
    *lead, k = x.shape
    return jnp.stack([x[..., :k // 2], x[..., k // 2:]], axis=-1).reshape(*lead, k)


def _kv_quant_pack_kernel(x_ref, codes_ref, scales_ref):
    """One [bm, 32] tile, one scale group per row → packed nibbles [bm, 16]
    + E8M0 codes [bm, 1].  Groups sit on rows because Mosaic refuses the
    lane-splitting reshape a [bm, K] tile would need per group."""
    x = x_ref[...].astype(jnp.float32)

    # (1) AbsMax per 32-group, (2) nearest power-of-two exponent
    amax = jnp.max(jnp.abs(x), axis=-1, keepdims=True)
    e = jnp.round(jnp.log2(jnp.maximum(amax / _E2M1_MAX, 2.0**-126)))
    e = jnp.clip(e, -126.0, 127.0)

    # (3) E2M1 RTN (saturating, ties-to-even)
    q = F.rtn_e2m1(jnp.clip(x / _exp2i(e), -_E2M1_MAX, _E2M1_MAX))

    # (4) arithmetic nibble encode, then byte j = 16·nib[2j] + nib[2j+1] as
    # a 0/1/16 matrix product — exact for these small integers, and it needs
    # no lane deinterleave (which Mosaic cannot lower).  int32 until the
    # store: Mosaic has no float32 -> uint8 cast.
    nib = F.e2m1_to_nibble_i32(q).astype(jnp.float32)
    row = jax.lax.broadcasted_iota(jnp.int32, (GROUP, GROUP // 2), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (GROUP, GROUP // 2), 1)
    pair = jnp.where((row >> 1) == col, jnp.where((row & 1) == 0, 16.0, 1.0), 0.0)
    packed = jnp.dot(nib, pair, preferred_element_type=jnp.float32)
    codes_ref[...] = packed.astype(jnp.int32).astype(jnp.uint8)
    scales_ref[...] = (e.astype(jnp.int32) + 127).astype(jnp.uint8)


def _kv_dequant_unpack_kernel(codes_ref, scales_ref, o_ref):
    """Packed [bm, bk/2] + scale codes [bm, bk/32] → f32 values [bm, bk]."""
    o_ref[...] = unpack_dequant(codes_ref[...], scales_ref[...])


@functools.partial(jax.jit, static_argnames=("block_rows", "interpret"))
def kv_quant_pack(
    x: jnp.ndarray,
    block_rows: int = 1024,
    interpret: bool | None = None,
):
    """x: [M, K] → (packed codes uint8 [M, K/2], E8M0 scale codes uint8 [M, K/32]).

    The kernel sees x as [M·K/32, 32] (one scale group per row, a free
    row-major reshape) and tiles ``block_rows`` groups per program."""
    m, k = x.shape
    if k % GROUP != 0:
        raise ValueError(f"K={k} not divisible by group {GROUP}")
    g = x.reshape(m * (k // GROUP), GROUP)
    rows = g.shape[0]
    br = min(block_rows, rows)
    grid = pl.cdiv(rows, br)
    pad = grid * br - rows
    if pad:
        g = jnp.pad(g, ((0, pad), (0, 0)))

    codes, scales = pl.pallas_call(
        _kv_quant_pack_kernel,
        grid=(grid,),
        in_specs=[pl.BlockSpec((br, GROUP), lambda i: (i, 0))],
        out_specs=[
            pl.BlockSpec((br, GROUP // 2), lambda i: (i, 0)),
            pl.BlockSpec((br, 1), lambda i: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((grid * br, GROUP // 2), jnp.uint8),
            jax.ShapeDtypeStruct((grid * br, 1), jnp.uint8),
        ],
        interpret=interpret_mode(interpret),
    )(g)
    return codes[:rows].reshape(m, k // 2), scales[:rows].reshape(m, k // GROUP)


@functools.partial(jax.jit, static_argnames=("block_m", "block_k", "interpret"))
def kv_dequant_unpack(
    codes: jnp.ndarray,
    scales: jnp.ndarray,
    block_m: int = 256,
    block_k: int = 512,
    interpret: bool | None = None,
) -> jnp.ndarray:
    """(packed codes [M, K/2], scale codes [M, K/32]) → f32 values [M, K]."""
    m, kh = codes.shape
    k = kh * 2
    assert scales.shape == (m, k // GROUP), (codes.shape, scales.shape)
    bk = min(block_k, k)
    while k % bk != 0:
        bk -= GROUP
    bm = min(block_m, m)
    grid_m = pl.cdiv(m, bm)
    pad_m = grid_m * bm - m
    if pad_m:
        codes = jnp.pad(codes, ((0, pad_m), (0, 0)))
        scales = jnp.pad(scales, ((0, pad_m), (0, 0)))

    out = pl.pallas_call(
        _kv_dequant_unpack_kernel,
        grid=(grid_m, k // bk),
        in_specs=[
            pl.BlockSpec((bm, bk // 2), lambda i, j: (i, j)),
            pl.BlockSpec((bm, bk // GROUP), lambda i, j: (i, j)),
        ],
        out_specs=pl.BlockSpec((bm, bk), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((grid_m * bm, k), jnp.float32),
        interpret=interpret_mode(interpret),
    )(codes, scales)
    return out[:m] if pad_m else out
