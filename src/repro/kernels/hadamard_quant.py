"""Fused forward Stage-1 Pallas kernel: grouped Hadamard + QuEST → MXFP4.

TPU adaptation of Quartet's Stage-1 CUDA kernel (§4.4): one VMEM-resident
pass fuses

  1. the block-32 Hadamard transform, executed as a [bm·bk/32, 32] × [32, 32]
     MXU matmul against the constant normalized Hadamard matrix,
  2. per-32-group RMSE-optimal (QuEST) scale computation,
  3. E8M0 (power-of-two) scale rounding,
  4. E2M1 round-to-nearest downcast (the Blackwell PTX cvt → the arithmetic
     ties-to-even ``core.formats.rtn_e2m1``; Mosaic refuses 4-bit float
     conversions before TPU v7),
  5. clip-mask generation for the backward trust estimator,

writing half-codes (int8 = 2×grid value), scales, and masks back to HBM.
Where Blackwell stages through GMEM→SMEM→RF, we stage HBM→VMEM→VREG; the
CUTLASS epilogue becomes the tail of the kernel body.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core import formats as F
from repro.core.hadamard import hadamard_matrix
from repro.kernels.backend import interpret_mode

GROUP = 32
_E2M1_MAX = 6.0


def _exp2i(e: jnp.ndarray) -> jnp.ndarray:
    """Exact 2^e via bit manipulation (XLA exp2 is inexact / flushes at -126)."""
    bits = (e.astype(jnp.int32) + 127) << 23
    return jax.lax.bitcast_convert_type(bits, jnp.float32)


def _round_scale_e8m0_nearest(s: jnp.ndarray) -> jnp.ndarray:
    e = jnp.round(jnp.log2(jnp.maximum(s, 2.0**-126)))
    return _exp2i(jnp.clip(e, -126.0, 127.0))


def _hadamard_quest_kernel(x_ref, h_ref, codes_ref, scales_ref, mask_ref, *, clip_c: float):
    """One [bm, bk] tile: Hadamard → QuEST scale → E2M1 RTN → mask."""
    x = x_ref[...].astype(jnp.float32)
    bm, bk = x.shape
    ng = bk // GROUP

    # (1) grouped Hadamard as an MXU matmul against the constant 32×32 H
    xg = x.reshape(bm * ng, GROUP)
    xh = jnp.dot(xg, h_ref[...], preferred_element_type=jnp.float32)

    # (2) QuEST scale: c* · rms per 32-group, mapped so clip point = grid max
    rms = jnp.sqrt(jnp.mean(xh * xh, axis=-1, keepdims=True))
    raw = jnp.maximum(clip_c * rms / _E2M1_MAX, 2.0**-126)

    # (3) E8M0 rounding (nearest power of two)
    scale = _round_scale_e8m0_nearest(raw)

    # (4) E2M1 RTN downcast (hardware-exact, saturating) + mask (5)
    v = xh / scale
    mask = jnp.abs(v) <= _E2M1_MAX
    q = F.rtn_e2m1(jnp.clip(v, -_E2M1_MAX, _E2M1_MAX))

    codes_ref[...] = jnp.round(q * 2.0).astype(jnp.int8).reshape(bm, bk)
    scales_ref[...] = scale.reshape(bm, ng)
    mask_ref[...] = mask.reshape(bm, bk).astype(jnp.int8)


@functools.partial(jax.jit, static_argnames=("block_m", "block_k", "interpret"))
def hadamard_quest_quantize(
    x: jnp.ndarray,
    block_m: int = 256,
    block_k: int = 512,
    interpret: bool | None = None,
):
    """x: [M, K] → (codes int8 [M,K], scales f32 [M,K/32], mask bool [M,K])."""
    m, k = x.shape
    if k % GROUP != 0:
        raise ValueError(f"K={k} not divisible by group {GROUP}")
    bk = min(block_k, k)
    while k % bk != 0:  # largest divisor of K ≤ block_k that is a multiple of 32
        bk -= GROUP
    bm = min(block_m, m)
    grid_m = pl.cdiv(m, bm)
    pad_m = grid_m * bm - m
    if pad_m:
        x = jnp.pad(x, ((0, pad_m), (0, 0)))

    clip_c = F.gaussian_optimal_clip("mxfp4")
    hmat = jnp.asarray(hadamard_matrix(GROUP), jnp.float32)
    kern = functools.partial(_hadamard_quest_kernel, clip_c=clip_c)
    codes, scales, mask = pl.pallas_call(
        kern,
        grid=(grid_m, k // bk),
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j: (i, j)),
            pl.BlockSpec((GROUP, GROUP), lambda i, j: (0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((bm, bk), lambda i, j: (i, j)),
            pl.BlockSpec((bm, bk // GROUP), lambda i, j: (i, j)),
            pl.BlockSpec((bm, bk), lambda i, j: (i, j)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((grid_m * bm, k), jnp.int8),
            jax.ShapeDtypeStruct((grid_m * bm, k // GROUP), jnp.float32),
            jax.ShapeDtypeStruct((grid_m * bm, k), jnp.int8),
        ],
        interpret=interpret_mode(interpret),
    )(x, hmat)
    if pad_m:
        codes, scales, mask = codes[:m], scales[:m], mask[:m]
    return codes, scales, mask.astype(jnp.bool_)
