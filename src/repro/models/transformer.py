"""Dense decoder-only LM (Llama-style): GQA + SwiGLU/GeLU MLP, RMSNorm,
scan-over-layers with optional remat, Quartet linears throughout.

This module also provides the generic LM scaffolding (embed → layer stack →
norm → logits) reused by the MoE / SSM / hybrid / VLM families, which plug in
their own layer body via the ``block_init`` / ``block_apply`` hooks.

Attention routes through ``models.attention``'s backend dispatch
(``ModelConfig.attn_backend``).  The per-layer ``caches`` threaded by the
layer scan are either dense ``(k, v)`` tuples or — for the serving engine's
batched decode — ``PagedKV`` pytrees (packed-pool leaves + page tables, both
carrying the leading ``[L]`` axis the scan consumes), in which case attention
runs the fused paged-attention kernel directly over the packed pages.
"""

from __future__ import annotations

import functools
from typing import Any, Callable

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.distributed.context import (
    constrain_layer_params,
    constrain_logits,
    constrain_tokens,
)
from repro.models import layers as L
from repro.models.attention import attention, init_attention

LAYER_SEED_STRIDE = 2654435761  # Knuth multiplicative hash increment


def init_mlp(key, cfg: ModelConfig, dtype):
    d, f = cfg.d_model, cfg.d_ff
    ks = jax.random.split(key, 3)
    if cfg.mlp == "swiglu":
        return {
            "gate": L.init_dense(ks[0], d, f, dtype, cfg.use_bias),
            "up": L.init_dense(ks[1], d, f, dtype, cfg.use_bias),
            "down": L.init_dense(ks[2], f, d, dtype, cfg.use_bias),
        }
    return {
        "up": L.init_dense(ks[0], d, f, dtype, cfg.use_bias),
        "down": L.init_dense(ks[1], f, d, dtype, cfg.use_bias),
    }


def mlp(params, x, seed, cfg: ModelConfig, method: str = "quartet"):
    qc = cfg.quartet
    if cfg.mlp == "swiglu":
        g = L.dense(params["gate"], x, L.seed_fold(seed, 11), qc, method)
        u = L.dense(params["up"], x, L.seed_fold(seed, 12), qc, method)
        h = jax.nn.silu(g.astype(jnp.float32)).astype(x.dtype) * u
    else:
        u = L.dense(params["up"], x, L.seed_fold(seed, 12), qc, method)
        h = jax.nn.gelu(u.astype(jnp.float32)).astype(x.dtype)
    return L.dense(params["down"], h, L.seed_fold(seed, 13), qc, method)


# ---------------------------------------------------------------------------
# Dense transformer block
# ---------------------------------------------------------------------------


def init_dense_block(key, cfg: ModelConfig, dtype):
    k1, k2 = jax.random.split(key)
    init_norm, _ = L.make_norm(cfg.norm)
    return {
        "attn_norm": init_norm(cfg.d_model, dtype),
        "attn": init_attention(k1, cfg, dtype),
        "mlp_norm": init_norm(cfg.d_model, dtype),
        "mlp": init_mlp(k2, cfg, dtype),
    }


def dense_block(params, x, positions, seed, cfg: ModelConfig, cache, cache_index,
                method, token_valid=None):
    # token_valid is accepted for signature parity with moe_block (batched
    # serving steps thread it uniformly); dense blocks have no cross-token
    # competition, so padding lanes are already harmless here
    _, norm = L.make_norm(cfg.norm)
    h, new_cache = attention(
        params["attn"], norm(params["attn_norm"], x, cfg.norm_eps), positions,
        L.seed_fold(seed, 100), cfg, causal=cfg.is_causal_lm,
        kv_cache=cache, cache_index=cache_index, method=method,
    )
    x = x + h
    x = x + mlp(params["mlp"], norm(params["mlp_norm"], x, cfg.norm_eps),
                L.seed_fold(seed, 200), cfg, method)
    return x, new_cache, jnp.float32(0.0)


def dense_cache_spec(cfg: ModelConfig, batch: int, max_len: int):
    hd = cfg.head_dim_
    shape = (batch, max_len, cfg.num_kv_heads, hd)
    return (
        jax.ShapeDtypeStruct(shape, jnp.dtype(cfg.dtype)),
        jax.ShapeDtypeStruct(shape, jnp.dtype(cfg.dtype)),
    )


# ---------------------------------------------------------------------------
# Generic LM scaffolding (scan over a stack of identical blocks)
# ---------------------------------------------------------------------------


def stacked_init(block_init: Callable, key, n: int, *args):
    """vmap a per-layer init over n keys → leaves with a leading [n] dim."""
    keys = jax.random.split(key, n)
    return jax.vmap(lambda k: block_init(k, *args))(keys)


def init_lm(key, cfg: ModelConfig, block_init=None):
    dtype = jnp.dtype(cfg.dtype)
    k_emb, k_layers, k_head = jax.random.split(key, 3)
    block_init = block_init or init_dense_block
    init_norm, _ = L.make_norm(cfg.norm)
    params = {
        "embed": L.init_embedding(k_emb, cfg.vocab_size, cfg.d_model, dtype),
        "layers": stacked_init(block_init, k_layers, cfg.num_layers, cfg, dtype),
        "final_norm": init_norm(cfg.d_model, dtype),
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = L.init_dense(k_head, cfg.d_model, cfg.vocab_size, dtype)
    return params


def _layer_scan(params_layers, x, positions, seed, cfg, caches, cache_index,
                block_apply, method, extra=None, token_valid=None):
    """Scan the block over stacked layer params (+ optional stacked caches)."""
    # forwarded as a kwarg only when present: training callers (and blocks
    # without cross-token routing, e.g. mamba1_block) never see it
    block_kw = {} if token_valid is None else {"token_valid": token_valid}

    def body(carry, inp):
        x, aux = carry
        layer_params, layer_idx, cache = inp
        # anchor the per-layer param slice (and, via the transpose, its
        # gradient) to the parameter sharding rules
        layer_params = constrain_layer_params(layer_params)
        # barrier: stops XLA hoisting the carry's bf16→f32 convert out of the
        # backward while as a whole-stack [L, B, S, D] f32 loop invariant
        x = jax.lax.optimization_barrier(x)
        seed_l = (seed + layer_idx.astype(jnp.uint32) * jnp.uint32(LAYER_SEED_STRIDE)).astype(jnp.uint32)
        x, new_cache, aux_l = block_apply(layer_params, x, positions, seed_l, cfg,
                                          cache, cache_index, method, **block_kw)
        x = constrain_tokens(x)  # anchor the scan carry's DP/SP sharding
        return (x, aux + aux_l), new_cache

    if cfg.remat:
        policy = (jax.checkpoint_policies.dots_with_no_batch_dims_saveable
                  if cfg.remat_policy == "dots" else None)
        body = jax.checkpoint(body, prevent_cse=False, policy=policy)
    idxs = jnp.arange(cfg.num_layers, dtype=jnp.uint32)
    (x, aux), new_caches = jax.lax.scan(body, (x, jnp.float32(0.0)),
                                        (params_layers, idxs, caches))
    return x, new_caches, aux


def lm_head_apply(params: dict, x: jnp.ndarray, cfg: ModelConfig,
                  seed: jnp.ndarray, method: str = "quartet") -> jnp.ndarray:
    """Final norm + unembedding → f32 logits.  Exposed separately so the
    training loss can apply it per sequence chunk (the full [B, S, V] f32
    logits tensor never materializes — see train.losses.chunked_lm_loss)."""
    _, norm = L.make_norm(cfg.norm)
    x = norm(params["final_norm"], x, cfg.norm_eps)
    if cfg.tie_embeddings:
        logits = L.unembed(params["embed"], x, L.seed_fold(seed, 999), cfg.quartet,
                           cfg.quantize_lm_head, method)
    else:
        logits = L.dense(params["lm_head"], x, L.seed_fold(seed, 999), cfg.quartet,
                         method if cfg.quantize_lm_head else "bf16")
    logits = constrain_logits(logits.astype(jnp.float32))
    if cfg.logit_softcap:
        c = cfg.logit_softcap
        logits = c * jnp.tanh(logits / c)
    return logits


def lm_forward(
    params: dict,
    tokens: jnp.ndarray,  # [B, S]
    cfg: ModelConfig,
    seed: jnp.ndarray,
    *,
    positions: jnp.ndarray | None = None,
    caches=None,  # stacked per-layer caches [L, ...] or None
    cache_index: jnp.ndarray | None = None,
    block_apply: Callable = dense_block,
    method: str = "quartet",
    extra: Any = None,
    features_only: bool = False,
    token_valid: jnp.ndarray | None = None,  # [B, S] bool — real-token lanes
):
    """Returns (logits [B, S, V] f32 — or [B, S, D] features —, caches, aux)."""
    B, S = tokens.shape
    if positions is None:
        positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32)[None, :], (B, S))
    x = constrain_tokens(L.embed(params["embed"], tokens))
    if cfg.pos_embed == "absolute":
        pe = L.sinusoidal_positions(max(4096, S), cfg.d_model)
        x = x + jnp.take(pe, jnp.clip(positions, 0, pe.shape[0] - 1), axis=0).astype(x.dtype)

    x, new_caches, aux = _layer_scan(params["layers"], x, positions, seed, cfg,
                                     caches, cache_index, block_apply, method, extra,
                                     token_valid)

    if features_only:
        return x, new_caches, aux
    return lm_head_apply(params, x, cfg, seed, method), new_caches, aux
