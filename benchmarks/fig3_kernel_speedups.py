"""Figure 3 analogue: linear-layer speedup model + kernel sanity timings.

The paper measures CUDA wall-clock on an RTX 5090.  Without FP4 silicon we
report the same quantity from a calibrated cost model over the *exact op
sequence our kernels execute*, per Llama-7B layer shape (as Fig. 3):

  t(layer) = max(flops/peak(format), bytes/HBM_bw) + quant-stage overhead

with Blackwell-class ratios (FP4 = 2× FP8 = 4× BF16 peak) and the real bytes
our Stage-1/Stage-2 kernels move (4-bit payload + 8-bit scales + masks).
Expected from the paper: fwd ≈ 2.4×/4× vs FP8/BF16 at large shapes, training
≈ 1.8×/2.6×.  CPU interpret-mode wall times are also printed per kernel —
correctness-path timings, not performance claims.
"""

from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

# Llama-7B linear shapes (d=4096, ffn=11008), batch 64 × seq 512 (paper Fig 3)
SHAPES = {
    "qkv_proj": (32768, 4096, 4096),
    "ffn_up": (32768, 4096, 11008),
    "ffn_down": (32768, 11008, 4096),
}

PEAK_BF16 = 1.0  # normalized
PEAK_FP8 = 2.0
PEAK_FP4 = 4.0
HBM = 1.0  # bytes/s normalized so that flops/byte balance ≈ B200 (~140)
RIDGE = 140.0  # flops per byte at the compute/memory roofline ridge


def _t_gemm(m, k, n, bits_in, peak):
    flops = 2 * m * k * n
    bytes_ = (m * k + k * n) * bits_in / 8 + m * n * 2  # out bf16
    return max(flops / (peak * RIDGE), bytes_ / HBM)


def _t_quant(m, k, bits_out):
    # Stage-1: read bf16, write 4-bit codes + e8m0 scales (1/32) + mask bits
    return (m * k * 2 + m * k * (bits_out / 8 + 1 / 32 + 1 / 8)) / HBM


def model_times(m, k, n):
    out = {}
    # BF16: one GEMM, no quant
    out["bf16"] = _t_gemm(m, k, n, 16, PEAK_BF16)
    # FP8: per-tensor cast fwd (cheap) + GEMM
    out["fp8"] = _t_gemm(m, k, n, 8, PEAK_FP8) + _t_quant(m, k, 8) + _t_quant(k, n, 8)
    # Quartet MXFP4: fused Hadamard+QuEST quant both operands + FP4 GEMM
    out["quartet_fp4"] = (_t_gemm(m, k, n, 4, PEAK_FP4)
                          + _t_quant(m, k, 4) + _t_quant(k, n, 4))
    return out


def run() -> list[tuple]:
    rows = []
    fwd_speedups_fp8, fwd_speedups_bf16 = [], []
    for name, (m, k, n) in SHAPES.items():
        t = model_times(m, k, n)
        s8 = t["fp8"] / t["quartet_fp4"]
        s16 = t["bf16"] / t["quartet_fp4"]
        fwd_speedups_fp8.append(s8)
        fwd_speedups_bf16.append(s16)
        rows.append((f"fig3/fwd/{name}", 0.0,
                     f"vs_fp8={s8:.2f}x vs_bf16={s16:.2f}x (paper: up to 2.4x/4x)"))
    # backward: 2 GEMMs + 4 SR-quantizations + inverse Hadamards (bf16 IO)
    for name, (m, k, n) in SHAPES.items():
        def t_bwd(fmt_bits, peak, extra_quants):
            t = (_t_gemm(m, n, k, fmt_bits, peak) + _t_gemm(k, m, n, fmt_bits, peak)
                 + extra_quants)
            return t
        tb16 = t_bwd(16, PEAK_BF16, 0)
        tb8 = t_bwd(8, PEAK_FP8, _t_quant(m, n, 8) * 2)
        tb4 = t_bwd(4, PEAK_FP4, (_t_quant(m, n, 4) + _t_quant(k, n, 4)
                                  + _t_quant(k, m, 4) + _t_quant(n, m, 4)))
        rows.append((f"fig3/bwd/{name}", 0.0,
                     f"vs_fp8={tb8 / tb4:.2f}x vs_bf16={tb16 / tb4:.2f}x "
                     f"(paper: up to 1.6x/2.3x)"))

    # CPU interpret-mode kernel wall times (correctness path, not perf)
    from repro.kernels.hadamard_quant import hadamard_quest_quantize
    from repro.kernels.mxfp4_matmul import mxfp4_matmul
    x = jax.random.normal(jax.random.PRNGKey(0), (256, 512))
    c, s, msk = hadamard_quest_quantize(x)  # compile
    jax.block_until_ready(c)
    t0 = time.perf_counter()
    for _ in range(5):
        c, s, msk = hadamard_quest_quantize(x)
    jax.block_until_ready(c)
    rows.append(("fig3/kernel_hadamard_quant_interp", (time.perf_counter() - t0) / 5 * 1e6,
                 "cpu-interpret"))
    w = jax.random.normal(jax.random.PRNGKey(1), (512, 256))
    cw, sw, _ = hadamard_quest_quantize(w.T)
    y = mxfp4_matmul(c, s, cw.T, sw.T)
    jax.block_until_ready(y)
    t0 = time.perf_counter()
    for _ in range(5):
        y = mxfp4_matmul(c, s, cw.T, sw.T)
    jax.block_until_ready(y)
    rows.append(("fig3/kernel_mxfp4_matmul_interp", (time.perf_counter() - t0) / 5 * 1e6,
                 "cpu-interpret"))

    # Paged-attention decode (serving): the step is HBM-bound, so the model
    # speedup is the KV-bytes ratio of the legacy gather-dequantize path
    # (read packed + write dense + read dense) over the fused kernel (read
    # packed pages in place) — llama-7B-class GQA shape (hd=128, 8 KV heads).
    hd, hkv = 128, 8
    packed = 2 * hkv * (hd // 2 + hd // 32)  # 4.25-bit K+V payload per token
    dense = 2 * hkv * hd * 2  # bf16 K+V per token
    rows.append(("fig3/decode_paged_vs_gather_bytes", 0.0,
                 f"{(packed + 2 * dense) / packed:.2f}x fewer KV bytes/step "
                 f"(packed {packed}B vs gather {packed + 2 * dense}B per tok)"))

    # CPU interpret-mode wall time for the fused paged-attention kernel
    from repro.kernels.paged_attention import paged_attention, quant_block

    B, hq, ps, n_pp = 4, 2 * hkv, 16, 4
    n_pages = 1 + B * n_pp
    nb = hd // quant_block(hd)
    pool = {
        "k_codes": jnp.zeros((n_pages, hkv, ps, hd // 2), jnp.uint8),
        "k_scales": jnp.full((n_pages, hkv, ps, nb), 127, jnp.uint8),
        "v_codes": jnp.zeros((n_pages, hkv, ps, hd // 2), jnp.uint8),
        "v_scales": jnp.full((n_pages, hkv, ps, nb), 127, jnp.uint8),
    }
    tables = jnp.arange(1, 1 + B * n_pp, dtype=jnp.int32).reshape(B, n_pp)
    lengths = jnp.full((B,), ps * n_pp, jnp.int32)
    qd = jax.random.normal(jax.random.PRNGKey(2), (B, hq, hd))
    o = paged_attention(qd, pool, tables, lengths)
    jax.block_until_ready(o)
    t0 = time.perf_counter()
    for _ in range(5):
        o = paged_attention(qd, pool, tables, lengths)
    jax.block_until_ready(o)
    rows.append(("fig3/kernel_paged_attention_interp",
                 (time.perf_counter() - t0) / 5 * 1e6, "cpu-interpret"))
    return rows
