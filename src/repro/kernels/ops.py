"""Jit'd public wrappers around the Pallas kernels.

These present a shape-flexible API (leading batch dims, transposed weights)
over the 2-D tiled kernels.  Whether a kernel runs compiled or in the Pallas
interpreter is decided when it is called, by
``repro.kernels.backend.interpret_mode`` (the interpreter exactly when the
default backend is not a TPU), so these wrappers pass no ``interpret=``.
"""

from __future__ import annotations

import jax.numpy as jnp

from repro.kernels.hadamard_quant import hadamard_quest_quantize as _hq_fn
from repro.kernels.kv_pack import kv_dequant_unpack as _kvd_fn
from repro.kernels.kv_pack import kv_quant_pack as _kvq_fn
from repro.kernels.mxfp4_matmul import mxfp4_matmul as _mm_fn
from repro.kernels.sr_hadamard_quant import sr_hadamard_quantize as _sr_fn

GROUP = 32


def _as2d(x: jnp.ndarray):
    lead = x.shape[:-1]
    return x.reshape(-1, x.shape[-1]), lead


def hadamard_quest_quantize(x: jnp.ndarray, group: int = GROUP):
    """[..., K] → (codes [...,K] int8, scales [...,K/32] f32, mask [...,K] bool)."""
    assert group == GROUP, "kernels are specialized to the MXFP4 group of 32"
    x2, lead = _as2d(x)
    codes, scales, mask = _hq_fn(x2)
    return (
        codes.reshape(*lead, -1),
        scales.reshape(*lead, -1),
        mask.reshape(*lead, -1),
    )


def sr_hadamard_quantize(
    x: jnp.ndarray, signs: jnp.ndarray, seed: jnp.ndarray,
    prescale: float = 0.75, salt: int = 0,
):
    """[..., K] → (codes, scales); randomness from the fused counter-hash
    PRNG (core/fastrng.py) — no materialized random buffers.  On real TPU
    hardware the same hash runs in-kernel from ``pltpu`` iota."""
    from repro.core import fastrng

    x2, lead = _as2d(x)
    u = fastrng.uniform(seed, x2.shape, salt)
    codes, scales = _sr_fn(x2, signs, u, prescale=prescale)
    return codes.reshape(*lead, -1), scales.reshape(*lead, -1)


def kv_quant_pack(x: jnp.ndarray):
    """[..., K] → (packed codes uint8 [..., K/2], E8M0 scale codes [..., K/32]).

    The serving PagedCache's quantize-on-write primitive (4.25 bits/element);
    bit-identical to ``core.quantizers.kv_quantize``."""
    x2, lead = _as2d(x)
    codes, scales = _kvq_fn(x2)
    return codes.reshape(*lead, -1), scales.reshape(*lead, -1)


def kv_dequant_unpack(codes: jnp.ndarray, scales: jnp.ndarray) -> jnp.ndarray:
    """(packed codes [..., K/2], scale codes [..., K/32]) → f32 values [..., K]."""
    c2, lead = _as2d(codes)
    s2, _ = _as2d(scales)
    out = _kvd_fn(c2, s2)
    return out.reshape(*lead, -1)


def mxfp4_matmul(a_codes, a_scales, b_codes, b_scales) -> jnp.ndarray:
    """[..., K] codes × [K, N] codes → f32 [..., N] (scales along K)."""
    a2, lead = _as2d(a_codes)
    s2, _ = _as2d(a_scales)
    out = _mm_fn(a2, s2, b_codes, b_scales)
    return out.reshape(*lead, -1)
