"""Pallas flash-attention (forward) for the 32k-prefill / long-decode shapes.

The jnp ``blocked_attention`` (models/attention.py) is the differentiable
reference used in training; this kernel is its serving-path hot-spot twin:
one VMEM-resident pass per (batch·head, q-block), streaming KV blocks with
online softmax — no [S, T] score matrix ever leaves VMEM.

Blocking: grid (BH, S/bq, T/bk) with the KV dimension innermost; the running
(m, l, acc) state lives in VMEM scratch across the innermost loop, flushed to
HBM at the last KV block.  Causal masking compares absolute q/kv indices, so
fully-masked future blocks are skipped via ``pl.when`` (the classic flash
triangular schedule).

Validated bit-consistently (≤1e-5) against a naive-softmax oracle in
``ref.py`` over shape sweeps in interpret mode.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.backend import interpret_mode

NEG_INF = -1e30


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref,
                  *, scale: float, causal: bool, bq: int, bk: int, nk: int,
                  t_valid: int):
    j = pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    i = pl.program_id(1)
    q_start = i * bq
    k_start = j * bk

    def _body():
        q = q_ref[0].astype(jnp.float32) * scale  # [bq, hd]
        k = k_ref[0].astype(jnp.float32)  # [bk, hd]
        v = v_ref[0].astype(jnp.float32)
        s = jnp.dot(q, k.T, preferred_element_type=jnp.float32)  # [bq, bk]

        q_pos = q_start + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
        k_pos = k_start + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
        mask = k_pos < t_valid  # KV padding (non-multiple T)
        if causal:
            mask &= q_pos >= k_pos
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * corr + jnp.sum(p, axis=-1, keepdims=True)
        acc_ref[...] = acc_ref[...] * corr + jnp.dot(
            p, v, preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    if causal:
        # skip KV blocks fully in the future of this q block (flash schedule)
        pl.when(k_start <= q_start + bq - 1)(_body)
    else:
        _body()

    @pl.when(j == nk - 1)
    def _flush():
        o_ref[0] = (acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("causal", "block_q", "block_k",
                                             "q_heads", "kv_heads", "interpret"))
def flash_attention(
    q: jnp.ndarray,  # [B·Hq, S, hd]
    k: jnp.ndarray,  # [B·Hkv, T, hd]
    v: jnp.ndarray,  # [B·Hkv, T, hd]
    causal: bool = True,
    block_q: int = 256,
    block_k: int = 256,
    q_heads: int = 1,
    kv_heads: int = 1,
    interpret: bool | None = None,
) -> jnp.ndarray:
    """GQA-native: the KV row for query row ``bh`` is resolved in the block
    index map (``(bh // Hq)·Hkv + (bh % Hq) // group``), so KV heads are read
    in place — never materialized ``group×`` larger via ``jnp.repeat``."""
    bh, s, hd = q.shape
    t = k.shape[1]
    scale = 1.0 / np.sqrt(hd)
    group = q_heads // kv_heads
    assert bh % q_heads == 0 and k.shape[0] == (bh // q_heads) * kv_heads

    bq = min(block_q, s)
    bk = min(block_k, t)
    pad_q = (-s) % bq
    pad_k = (-t) % bk
    if pad_q:
        q = jnp.pad(q, ((0, 0), (0, pad_q), (0, 0)))
    if pad_k:
        k = jnp.pad(k, ((0, 0), (0, pad_k), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad_k), (0, 0)))
    nq, nk = (s + pad_q) // bq, (t + pad_k) // bk

    def kv_row(b):
        return (b // q_heads) * kv_heads + (b % q_heads) // group

    kern = functools.partial(
        _flash_kernel, scale=scale, causal=causal, bq=bq, bk=bk, nk=nk,
        t_valid=t)
    out = pl.pallas_call(
        kern,
        grid=(bh, nq, nk),
        in_specs=[
            pl.BlockSpec((1, bq, hd), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, bk, hd), lambda b, i, j: (kv_row(b), j, 0)),
            pl.BlockSpec((1, bk, hd), lambda b, i, j: (kv_row(b), j, 0)),
        ],
        out_specs=pl.BlockSpec((1, bq, hd), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, s + pad_q, hd), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, 1), jnp.float32),  # running max
            pltpu.VMEM((bq, 1), jnp.float32),  # running denom
            pltpu.VMEM((bq, hd), jnp.float32),  # running numerator
        ],
        interpret=interpret_mode(interpret),
    )(q, k, v)
    return out[:, :s, :]


def mha_flash(q, k, v, causal: bool = True, interpret: bool | None = None,
              block_q: int = 256, block_k: int = 256):
    """[B, S, Hq, hd] × [B, T, Hkv, hd] (GQA) → [B, S, Hq, hd]."""
    B, S, Hq, hd = q.shape
    T, Hkv = k.shape[1], k.shape[2]
    qf = q.transpose(0, 2, 1, 3).reshape(B * Hq, S, hd)
    kf = k.transpose(0, 2, 1, 3).reshape(B * Hkv, T, hd)
    vf = v.transpose(0, 2, 1, 3).reshape(B * Hkv, T, hd)
    o = flash_attention(qf, kf, vf, causal=causal, block_q=block_q,
                        block_k=block_k, q_heads=Hq, kv_heads=Hkv,
                        interpret=interpret)
    return o.reshape(B, Hq, S, hd).transpose(0, 2, 1, 3)
