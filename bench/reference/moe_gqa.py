"""Plain float32 reference of a GQA decoder whose FFNs are routed experts
(Qwen3-MoE).

Written from the published architecture, not from the program.  Attention,
norms, rotary embedding, the MXFP4 rounding of keys and values, the LM head
and the fp8 control are ``dense_gqa``'s.  The FFN of every layer is a
softmax router over all E experts, the top k of them (renormalised to sum
to 1 where the configuration sets ``norm_topk_prob``), and the gate-weighted
sum of those experts' SwiGLU outputs.  No capacity: every token reaches all
k of its experts, as the published model computes it.  Each expert is
applied to every row and weighted by its gate, zero where not chosen, one
expert at a time, so the peak holds one expert's activations.
"""

from __future__ import annotations

import functools
import json

import jax
import jax.numpy as jnp

from bench.reference import dense_gqa

F32 = dense_gqa.F32
bucket = dense_gqa.bucket


def dims(c: dict) -> dict:
    if c.get("decoder_sparse_step", 1) != 1 or c.get("mlp_only_layers"):
        raise ValueError("moe_gqa covers decoders whose every FFN is routed experts")
    return {**dense_gqa.dims(c), "f": c["moe_intermediate_size"], "E": c["num_experts"],
            "k": c["num_experts_per_tok"], "norm_topk": bool(c["norm_topk_prob"])}


def program_fields(c: dict) -> dict:
    """Program ModelConfig attribute -> the value the published configuration
    requires: every layer's FFN routed experts of width ``d_ff``, with no
    dense FFN beside them."""
    m = dims(c)
    return {**dense_gqa.fields_of(m), "family": "moe", "num_experts": m["E"],
            "experts_per_token": m["k"], "moe_dense_residual": False}


def param_shapes(m: dict) -> dict:
    """The program's ``moe`` layout: ``dense_gqa``'s tree with each layer's
    ``mlp`` replaced by a router and E stacked SwiGLU experts."""
    if m["mlp"] != "swiglu" or m["bias"]:
        raise ValueError("moe_gqa's experts are SwiGLU without biases")
    shapes = dense_gqa.param_shapes(m)
    d, f, L, E = m["d"], m["f"], m["L"], m["E"]
    layers = shapes["layers"]
    del layers["mlp"]
    layers["moe"] = {"router": {"w": (L, d, E)}, "gate": (L, E, d, f),
                     "up": (L, E, d, f), "down": (L, E, f, d)}
    return shapes


def make_params(c: dict, seed: int):
    """All weights from ``seed``, in bf16 as they are served, on the device,
    in one jitted call."""
    return dense_gqa.draw(param_shapes(dims(c)), seed)


def gates(m: dict, router, h, fp8: bool):
    """[T, E] weight of each expert for each row: its router probability if
    among the row's top k (renormalised over them under ``norm_topk``), else 0."""
    probs = jax.nn.softmax(dense_gqa.linear(router, h, fp8), axis=-1)
    top, idx = jax.lax.top_k(probs, m["k"])
    if m["norm_topk"]:
        top = top / jnp.sum(top, -1, keepdims=True)
    return jnp.sum(jax.nn.one_hot(idx, m["E"], dtype=F32) * top[..., None], axis=1)


def experts(m: dict, mp, h, fp8: bool):
    """Routed-expert FFN of rows ``h`` [T, d]; ``mp`` in float32."""
    g = gates(m, mp["router"], h, fp8)

    def one(y, args):
        wg, wu, wd, ge = args
        u = (jax.nn.silu(dense_gqa.linear({"w": wg}, h, fp8))
             * dense_gqa.linear({"w": wu}, h, fp8))
        return y + ge[:, None] * dense_gqa.linear({"w": wd}, u, fp8), None

    y, _ = jax.lax.scan(one, jnp.zeros_like(h), (mp["gate"], mp["up"], mp["down"], g.T))
    return y


def _block(m, lp, x, pos, fp8):
    lp = jax.tree.map(lambda a: a.astype(F32), lp)
    x = dense_gqa.attend(m, lp, x, pos, fp8)
    return x + experts(m, lp["moe"], dense_gqa.apply_norm(lp["mlp_norm"], x, m["eps"]), fp8)


@functools.lru_cache(maxsize=None)
def _scorer(c_json: str, fp8: bool):
    return jax.jit(dense_gqa.score_fn(json.loads(c_json), fp8, _block))


def scorer(c: dict, fp8: bool = False):
    """Jitted ``score(params, tokens [T], query [T, K])`` for configuration ``c``."""
    return _scorer(json.dumps(dims(c), sort_keys=True), fp8)
