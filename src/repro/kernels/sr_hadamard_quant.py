"""Fused backward Stage-1 Pallas kernel: randomized Hadamard + SR → MXFP4.

Implements the backward operand preparation of Algorithm 1:

    SR( ¾ · Ĥ_32(x, ξ) )   with E8M0 ceil scales (no clipping → unbiased)

The sign flip ξ, grouped Hadamard (MXU matmul), AbsMax scale, power-of-two
ceil rounding, and stochastic E2M1 rounding are fused in one VMEM pass.

Stochastic rounding is arithmetic (no grid search): for E2M1 the spacing at
|v| is   step(v) = 2^(floor(log2 |v|) − 1)  for |v| ≥ 1, and 0.5 below 1;
round down to the grid then move up with probability (v − lo)/step.  Uniform
randomness arrives as an explicit operand so the kernel is reproducible and
CPU-interpretable; on real TPU hardware the same kernel can draw bits from
``pltpu.prng_random_bits`` instead (switchable, see ``use_hw_rng``).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core.hadamard import hadamard_matrix
from repro.kernels.backend import interpret_mode

GROUP = 32
_E2M1_MAX = 6.0


def _e2m1_stochastic_round(v: jnp.ndarray, u: jnp.ndarray) -> jnp.ndarray:
    """Unbiased SR onto the E2M1 grid for |v| ≤ 6 (arithmetic formulation)."""
    a = jnp.abs(v)
    sign = jnp.sign(v)
    e = jnp.floor(jnp.log2(jnp.maximum(a, 1.0)))  # 0 for a<1 → step 0.5
    step = jnp.exp2(e - 1.0)  # 0.5, 0.5, 1, 2 for a in [0,1),[1,2),[2,4),[4,6]
    lo = jnp.floor(a / step) * step
    p_up = (a - lo) / step
    q = jnp.where(u < p_up, lo + step, lo)
    return sign * jnp.minimum(q, _E2M1_MAX)


def _sr_hadamard_kernel(x_ref, signs_ref, u_ref, h_ref, codes_ref, scales_ref, *, prescale: float):
    x = x_ref[...].astype(jnp.float32) * signs_ref[...].astype(jnp.float32)[None, :]
    bm, bk = x.shape
    ng = bk // GROUP

    xh = jnp.dot(x.reshape(bm * ng, GROUP), h_ref[...], preferred_element_type=jnp.float32)
    xh = xh * prescale

    absmax = jnp.max(jnp.abs(xh), axis=-1, keepdims=True)
    raw = jnp.maximum(absmax / _E2M1_MAX, 2.0**-126)
    # E8M0 ceil: guarantees |v| ≤ 6 ⇒ SR never clips ⇒ unbiased.
    # exact 2^e via bit manipulation (XLA exp2 is inexact / flushes at -126)
    e = jnp.clip(jnp.ceil(jnp.log2(raw) - 1e-6), -126.0, 127.0)
    scale = jax.lax.bitcast_convert_type((e.astype(jnp.int32) + 127) << 23, jnp.float32)

    v = xh / scale
    q = _e2m1_stochastic_round(v, u_ref[...].astype(jnp.float32).reshape(bm * ng, GROUP))

    codes_ref[...] = jnp.round(q * 2.0).astype(jnp.int8).reshape(bm, bk)
    scales_ref[...] = scale.reshape(bm, ng)


@functools.partial(
    jax.jit, static_argnames=("prescale", "block_m", "block_k", "interpret")
)
def sr_hadamard_quantize(
    x: jnp.ndarray,
    signs: jnp.ndarray,
    u: jnp.ndarray,
    prescale: float = 0.75,
    block_m: int = 256,
    block_k: int = 512,
    interpret: bool | None = None,
):
    """x: [M, K], signs: [K] ±1, u: [M, K] uniforms →
    (codes int8 [M, K], scales f32 [M, K/32])."""
    m, k = x.shape
    if k % GROUP != 0:
        raise ValueError(f"K={k} not divisible by group {GROUP}")
    bk = min(block_k, k)
    while k % bk != 0:
        bk -= GROUP
    bm = min(block_m, m)
    grid_m = pl.cdiv(m, bm)
    pad_m = grid_m * bm - m
    if pad_m:
        x = jnp.pad(x, ((0, pad_m), (0, 0)))
        u = jnp.pad(u, ((0, pad_m), (0, 0)), constant_values=0.5)

    kern = functools.partial(_sr_hadamard_kernel, prescale=prescale)
    codes, scales = pl.pallas_call(
        kern,
        grid=(grid_m, k // bk),
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j: (i, j)),
            pl.BlockSpec((bk,), lambda i, j: (j,)),
            pl.BlockSpec((bm, bk), lambda i, j: (i, j)),
            pl.BlockSpec((GROUP, GROUP), lambda i, j: (0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((bm, bk), lambda i, j: (i, j)),
            pl.BlockSpec((bm, bk // GROUP), lambda i, j: (i, j)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((grid_m * bm, k), jnp.int8),
            jax.ShapeDtypeStruct((grid_m * bm, k // GROUP), jnp.float32),
        ],
        interpret=interpret_mode(interpret),
    )(x, signs, u, jnp.asarray(hadamard_matrix(GROUP), jnp.float32))
    if pad_m:
        codes, scales = codes[:m], scales[:m]
    return codes, scales
