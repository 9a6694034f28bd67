"""Plain float32 reference of a dense GQA decoder (Qwen3, StarCoder2).

Written from the published architecture, not from the program: token
embedding, a stack of pre-norm blocks (RMSNorm or LayerNorm; q/k RMSNorm
where the model has it; rotary embedding on halves; grouped-query causal
softmax attention; SwiGLU or tanh-GeLU MLP; optional biases), a final norm
and the LM head (tied or not).  Every matmul runs in float32 at the highest
precision.  The keys and values attention reads are rounded to MXFP4 (E2M1
elements, E8M0 scale nearest to amax/6 over 32 lanes), the storage format
the configuration states for its KV cache.  No cache, no batching, no
kernels: one sequence at a time, padded at its end to a bucket length.

``make_params`` draws the weights from the seed in the program's parameter
layout (the input format of the system under test); the reference and the
program both read those.  ``scorer(..., fp8=True)`` is the lower-precision
control: the same forward with every linear's operands rounded to
float8_e4m3 (per-row activation scale, per-column weight scale).
``program_fields`` is what the program's ModelConfig must hold for the
configuration, checked before any weight is drawn.

Other references (``moe_gqa.py``) import the pieces they share from here.
"""

from __future__ import annotations

import functools
import json
import math

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
F32 = jnp.float32
ROW_BLOCK = 256  # query rows per attention / LM-head block


def dims(c: dict) -> dict:
    a = c["architecture"]
    hq = c["num_attention_heads"]
    eps = c.get("rms_norm_eps", c.get("norm_epsilon"))
    return {"d": c["hidden_size"], "f": c["intermediate_size"],
            "L": c["num_hidden_layers"], "hq": hq, "hkv": c["num_key_value_heads"],
            "hd": c.get("head_dim") or c["hidden_size"] // hq,
            "V": c["vocab_size"], "theta": float(c["rope_theta"]), "eps": float(eps),
            "norm": a["norm"], "qk_norm": a["qk_norm"], "mlp": a["mlp"],
            "bias": a["bias"], "tied": c["tie_word_embeddings"]}


def fields_of(m: dict) -> dict:
    """The program ModelConfig attributes a decoder of dims ``m`` sets, but
    its family."""
    return {"d_model": m["d"], "d_ff": m["f"], "num_layers": m["L"],
            "num_heads": m["hq"], "num_kv_heads": m["hkv"], "head_dim_": m["hd"],
            "vocab_size": m["V"], "rope_theta": m["theta"], "norm_eps": m["eps"],
            "norm": m["norm"], "qk_norm": m["qk_norm"], "mlp": m["mlp"],
            "use_bias": m["bias"], "tie_embeddings": m["tied"]}


def program_fields(c: dict) -> dict:
    """Program ModelConfig attribute -> the value the published configuration
    requires."""
    return {**fields_of(dims(c)), "family": "dense"}


# ---------------------------------------------------------------------------
# weights, in the program's parameter layout
# ---------------------------------------------------------------------------


def param_shapes(m: dict) -> dict:
    """Nested dict of leaf shapes for dims ``m``; layer leaves carry a leading [L]."""
    d, f, L, hd = m["d"], m["f"], m["L"], m["hd"]

    def lin(i, o):
        p = {"w": (L, i, o)}
        if m["bias"]:
            p["b"] = (L, o)
        return p

    def norm(n, lead=(L,)):
        p = {"scale": (*lead, n)}
        if m["norm"] == "layernorm":
            p["bias"] = (*lead, n)
        return p

    attn = {"wq": lin(d, m["hq"] * hd), "wk": lin(d, m["hkv"] * hd),
            "wv": lin(d, m["hkv"] * hd), "wo": lin(m["hq"] * hd, d)}
    if m["qk_norm"]:
        attn["q_norm"] = {"scale": (L, hd)}
        attn["k_norm"] = {"scale": (L, hd)}
    mlp = ({"gate": lin(d, f), "up": lin(d, f), "down": lin(f, d)}
           if m["mlp"] == "swiglu" else {"up": lin(d, f), "down": lin(f, d)})
    shapes = {"embed": {"table": (m["V"], d)},
              "layers": {"attn_norm": norm(d), "attn": attn,
                         "mlp_norm": norm(d), "mlp": mlp},
              "final_norm": norm(d, ())}
    if not m["tied"]:
        shapes["lm_head"] = {"w": (d, m["V"])}
    return shapes


def _leaf_init(path: tuple, shape: tuple, key):
    """Scale by role: weights 1/sqrt(fan_in), embeddings 1/sqrt(d), norm
    scales about 1, biases and norm offsets small but nonzero."""
    z = jax.random.normal(key, shape, F32)
    name, parent = path[-1], path[-2]
    if name == "scale":
        return 1.0 + 0.05 * z
    if name in ("b", "bias"):
        return 0.05 * z
    if parent == "embed":
        return z / math.sqrt(shape[-1])
    return z / math.sqrt(shape[-2])  # "w": [..., fan_in, fan_out]


def seed_key(seed: int):
    words = np.random.SeedSequence([int(seed), 0xD6E5]).generate_state(1)
    return jax.random.PRNGKey(int(words[0]))


def make_params(c: dict, seed: int):
    """All weights from ``seed``, in bf16 as they are served, on the device,
    in one jitted call."""
    return draw(param_shapes(dims(c)), seed)


def draw(shapes: dict, seed: int):
    """Leaves of ``shapes`` drawn from ``seed`` by ``_leaf_init``, in bf16."""
    flat = jax.tree_util.tree_flatten_with_path(
        shapes, is_leaf=lambda x: isinstance(x, tuple))[0]

    @jax.jit
    def init(key):
        leaves = {}
        for i, (path, shape) in enumerate(flat):
            names = tuple(p.key for p in path)
            leaf = _leaf_init(names, shape, jax.random.fold_in(key, i))
            leaves[names] = leaf.astype(jnp.bfloat16)
        out: dict = {}
        for names, v in leaves.items():
            node = out
            for n in names[:-1]:
                node = node.setdefault(n, {})
            node[names[-1]] = v
        return out

    return init(seed_key(seed))


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------


def apply_norm(p, x, eps):
    if "bias" in p:
        mu = jnp.mean(x, -1, keepdims=True)
        var = jnp.mean((x - mu) ** 2, -1, keepdims=True)
        return (x - mu) * jax.lax.rsqrt(var + eps) * p["scale"] + p["bias"]
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * p["scale"]


def fp8_round(x, axis):
    """Round to float8_e4m3 with one absmax scale per slice along ``axis``."""
    s = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / 448.0
    s = jnp.where(s > 0, s, 1.0)
    return (x / s).astype(jnp.float8_e4m3fn).astype(F32) * s


def linear(p, x, fp8: bool):
    w = p["w"]
    if fp8:
        x, w = fp8_round(x, -1), fp8_round(w, 0)
    y = jnp.dot(x, w, precision=HIGHEST)
    return y + p["b"] if "b" in p else y


def e2m1_round(y):
    """Nearest E2M1 value ({0, .5, 1, 1.5, 2, 3, 4, 6}), ties to even, saturating."""
    a = jnp.minimum(jnp.abs(y), 6.0)
    step = jnp.where(a < 2.0, 0.5, jnp.where(a < 4.0, 1.0, 2.0))
    return jnp.sign(y) * jnp.minimum(jnp.round(a / step) * step, 6.0)


def mxfp4_round(x, block: int = 32):
    """MXFP4 over blocks of the last axis: E8M0 scale = 2**round(log2(amax/6))."""
    xb = x.reshape(*x.shape[:-1], x.shape[-1] // block, block)
    raw = jnp.maximum(jnp.max(jnp.abs(xb), -1, keepdims=True) / 6.0, 2.0 ** -126)
    scale = jnp.exp2(jnp.clip(jnp.round(jnp.log2(raw)), -126, 127))
    return (e2m1_round(jnp.clip(xb / scale, -6.0, 6.0)) * scale).reshape(x.shape)


def _rope(x, pos, theta):
    """Rotary embedding on halves: x [T, H, hd], pos [T]."""
    hd = x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, hd, 2, dtype=F32) / hd)
    ang = pos[:, None].astype(F32) * inv
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2 = x[..., : hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _attention(q, k, v):
    """Causal GQA softmax attention.  q [T, Hq, hd], k/v [T, Hkv, hd]."""
    T, hq, hd = q.shape
    hkv = k.shape[1]
    qg = (q / math.sqrt(hd)).reshape(T // ROW_BLOCK, ROW_BLOCK, hkv, hq // hkv, hd)

    def block(args):
        i, qb = args
        s = jnp.einsum("qkgd,tkd->kgqt", qb, k, precision=HIGHEST)
        rows = i * ROW_BLOCK + jnp.arange(ROW_BLOCK)
        s = jnp.where(jnp.arange(T)[None, :] <= rows[:, None], s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        return jnp.einsum("kgqt,tkd->qkgd", p, v, precision=HIGHEST)

    out = jax.lax.map(block, (jnp.arange(T // ROW_BLOCK), qg))
    return out.reshape(T, hq * hd)


def attend(m, lp, x, pos, fp8):
    """The residual stream after a block's attention; ``lp`` in float32."""
    T = x.shape[0]
    h = apply_norm(lp["attn_norm"], x, m["eps"])
    a = lp["attn"]
    q = linear(a["wq"], h, fp8).reshape(T, m["hq"], m["hd"])
    k = linear(a["wk"], h, fp8).reshape(T, m["hkv"], m["hd"])
    v = linear(a["wv"], h, fp8).reshape(T, m["hkv"], m["hd"])
    if m["qk_norm"]:
        q, k = apply_norm(a["q_norm"], q, m["eps"]), apply_norm(a["k_norm"], k, m["eps"])
    q, k = _rope(q, pos, m["theta"]), _rope(k, pos, m["theta"])
    k, v = mxfp4_round(k), mxfp4_round(v)
    return x + linear(a["wo"], _attention(q, k, v), fp8)


def _block(m, lp, x, pos, fp8):
    lp = jax.tree.map(lambda a: a.astype(F32), lp)
    x = attend(m, lp, x, pos, fp8)
    h = apply_norm(lp["mlp_norm"], x, m["eps"])
    mp = lp["mlp"]
    if m["mlp"] == "swiglu":
        u = jax.nn.silu(linear(mp["gate"], h, fp8)) * linear(mp["up"], h, fp8)
    else:
        u = jax.nn.gelu(linear(mp["up"], h, fp8), approximate=True)
    return x + linear(mp["down"], u, fp8)


def score_fn(m: dict, fp8: bool, block):
    """``score`` over a stack of ``block(m, lp, x, pos, fp8)``, un-jitted."""

    def score(params, tokens, query):
        """tokens [T], query [T, K] -> (best [T], logit of each query [T, K],
        argmax [T]) at every position."""
        T = tokens.shape[0]
        pos = jnp.arange(T)
        x = params["embed"]["table"][tokens].astype(F32)
        x, _ = jax.lax.scan(lambda x, lp: (block(m, lp, x, pos, fp8), None),
                            x, params["layers"])
        fn = jax.tree.map(lambda a: a.astype(F32), params["final_norm"])
        h = apply_norm(fn, x, m["eps"])
        w = (params["embed"]["table"].T if m["tied"] else params["lm_head"]["w"]).astype(F32)
        if fp8:
            w = fp8_round(w, 0)

        def rows(args):
            hb, qb = args
            if fp8:
                hb = fp8_round(hb, -1)
            logits = jnp.dot(hb, w, precision=HIGHEST)
            return (jnp.max(logits, -1), jnp.take_along_axis(logits, qb, 1),
                    jnp.argmax(logits, -1).astype(jnp.int32))

        n = T // ROW_BLOCK
        best, vals, top = jax.lax.map(
            rows, (h.reshape(n, ROW_BLOCK, -1), query.reshape(n, ROW_BLOCK, -1)))
        return best.reshape(T), vals.reshape(T, -1), top.reshape(T)

    return score


@functools.lru_cache(maxsize=None)
def _scorer(c_json: str, fp8: bool):
    return jax.jit(score_fn(json.loads(c_json), fp8, _block))


def scorer(c: dict, fp8: bool = False):
    """Jitted ``score(params, tokens [T], query [T, K])`` for configuration ``c``."""
    return _scorer(json.dumps(dims(c), sort_keys=True), fp8)


def bucket(n: int) -> int:
    """Padded length for a sequence of ``n`` tokens: a power of two of at
    least ROW_BLOCK rows, so few shapes compile."""
    return max(ROW_BLOCK, 1 << (n - 1).bit_length())
