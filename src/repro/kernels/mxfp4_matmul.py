"""Stage-2 Pallas kernel: MXFP4 block-scaled GEMM.

TPU analogue of Quartet's dedicated CUTLASS ``tcgen05.mma`` kernel:

    D = (A ⊗ SFA) · (B ⊗ SFB),   scales along the K dim, one per 32 elements.

Blackwell applies the E8M0 rescale inside the tensor core; the TPU MXU has no
block-scaled input path, so the kernel dequantizes each [bm, bk] / [bk, bn]
code tile to f32 *in VMEM* (int8 half-code × 0.5 × scale — two vector ops,
no gather) and feeds the MXU with an fp32-accumulating ``jnp.dot``.  Because
E2M1×E2M1 products need ≤ 4 mantissa bits and E8M0 scales are exact powers of
two, this is bit-exact w.r.t. native FP4 hardware with fp32 accumulation
(DESIGN.md §2).  HBM traffic, however, is the *real* 4-bit payload: codes and
scales only.

Layout: A codes [M, K] + scales [M, K/32]; B codes [K, N] + scales [K/32, N].
Grid (m, n, k) with a VMEM f32 accumulator flushed at the last k step — the
standard Pallas TPU matmul schedule, K innermost so the accumulator stays
resident while code tiles stream through.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.backend import interpret_mode

GROUP = 32


def _mxfp4_matmul_kernel(a_ref, as_ref, b_ref, bs_ref, o_ref, acc_ref, *, n_k: int):
    @pl.when(pl.program_id(2) == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    a = a_ref[...].astype(jnp.float32)  # [bm, bk] half-codes
    b = b_ref[...].astype(jnp.float32)  # [bk, bn]
    bm, bk = a.shape
    bn = b.shape[1]
    ng = bk // GROUP

    # dequant: value = code · 0.5 · scale  (scale broadcast per 32-group)
    a = a.reshape(bm, ng, GROUP) * (0.5 * as_ref[...])[..., None]
    b = b.reshape(ng, GROUP, bn) * (0.5 * bs_ref[...])[:, None, :]

    acc_ref[...] += jnp.dot(
        a.reshape(bm, bk), b.reshape(bk, bn), preferred_element_type=jnp.float32
    )

    @pl.when(pl.program_id(2) == n_k - 1)
    def _flush():
        o_ref[...] = acc_ref[...]


@functools.partial(
    jax.jit, static_argnames=("block_m", "block_n", "block_k", "interpret")
)
def mxfp4_matmul(
    a_codes: jnp.ndarray,
    a_scales: jnp.ndarray,
    b_codes: jnp.ndarray,
    b_scales: jnp.ndarray,
    block_m: int = 256,
    block_n: int = 256,
    block_k: int = 512,
    interpret: bool | None = None,
) -> jnp.ndarray:
    """(A codes [M,K], scales [M,K/32]) × (B codes [K,N], scales [K/32,N]) → f32 [M,N]."""
    m, k = a_codes.shape
    k2, n = b_codes.shape
    assert k == k2, (a_codes.shape, b_codes.shape)
    assert a_scales.shape == (m, k // GROUP)
    assert b_scales.shape == (k // GROUP, n)

    bk = min(block_k, k)
    while k % bk != 0:
        bk -= GROUP
    bm, bn = min(block_m, m), min(block_n, n)
    gm, gn, gk = pl.cdiv(m, bm), pl.cdiv(n, bn), k // bk
    pm, pn = gm * bm - m, gn * bn - n
    if pm:
        a_codes = jnp.pad(a_codes, ((0, pm), (0, 0)))
        a_scales = jnp.pad(a_scales, ((0, pm), (0, 0)), constant_values=1.0)
    if pn:
        b_codes = jnp.pad(b_codes, ((0, 0), (0, pn)))
        b_scales = jnp.pad(b_scales, ((0, 0), (0, pn)), constant_values=1.0)

    kern = functools.partial(_mxfp4_matmul_kernel, n_k=gk)
    out = pl.pallas_call(
        kern,
        grid=(gm, gn, gk),
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, l: (i, l)),
            pl.BlockSpec((bm, bk // GROUP), lambda i, j, l: (i, l)),
            pl.BlockSpec((bk, bn), lambda i, j, l: (l, j)),
            pl.BlockSpec((bk // GROUP, bn), lambda i, j, l: (l, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, l: (i, j)),
        out_shape=jax.ShapeDtypeStruct((gm * bm, gn * bn), jnp.float32),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        interpret=interpret_mode(interpret),
    )(a_codes, a_scales, b_codes, b_scales)
    return out[:m, :n]
