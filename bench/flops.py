"""Operations and bytes each step call and kernel needs, from shapes alone.

``m`` is a configuration's ``dims`` (see ``bench/reference/dense_gqa.py``).
These count what the algorithm needs -- model FLOPs of the valid rows, the
bytes of live pages -- never what a padded or re-quantizing implementation
happens to move, so a roofline share computed from them cannot pass 100%
unless the time is wrong.
"""

from __future__ import annotations

import math

# The dims of a dense decoder with full-context attention, all that these
# counts know.  Dims that declare more (experts, windows) are not counted here.
DENSE_DIMS = frozenset({"d", "f", "L", "hq", "hkv", "hd", "V", "theta", "eps", "norm",
                        "qk_norm", "mlp", "bias", "tied"})


def linear_params(m: dict) -> int:
    """Weights of the transformer linears (all layers; no embedding or head)."""
    d, hd, f = m["d"], m["hd"], m["f"]
    attn = d * hd * (m["hq"] + 2 * m["hkv"]) + m["hq"] * hd * d
    mlp = (3 if m["mlp"] == "swiglu" else 2) * d * f
    return m["L"] * (attn + mlp)


def head_params(m: dict) -> int:
    return m["V"] * m["d"]


def total_params(m: dict) -> int:
    """All weights, counting a tied embedding once."""
    d = m["d"]
    norms = (2 * m["L"] + 1) * d * (2 if m["norm"] == "layernorm" else 1)
    qk = 2 * m["L"] * m["hd"] if m["qk_norm"] else 0
    bias = 0
    if m["bias"]:
        bias = m["L"] * (m["hd"] * (m["hq"] + 2 * m["hkv"]) + d
                         + (m["f"] + d if m["mlp"] == "gelu" else 2 * m["f"] + d))
    emb = head_params(m) * (1 if m["tied"] else 2)
    return linear_params(m) + emb + norms + qk + bias


def attention_flops(m: dict, contexts) -> int:
    """QK^T and PV over all layers, one query row per context length."""
    return 4 * m["hq"] * m["hd"] * m["L"] * int(sum(contexts))


def kv_bytes_per_token(m: dict, block: int = 32) -> float:
    """Packed MXFP4 bytes of one token's K and V over all layers
    (4-bit codes plus one E8M0 byte per ``block`` lanes)."""
    return m["L"] * m["hkv"] * 2 * m["hd"] * (0.5 + 1.0 / block)


def prefill_call_flops(m: dict, rows, finished: int) -> int:
    """Model FLOPs of one batched prefill call.

    ``rows``: ``(start, n_valid)`` per prefilling slot; the valid tokens sit
    at positions ``start .. start + n_valid - 1`` and each attends to itself
    and everything before it.  ``finished``: slots whose prompt ends in this
    call, the only rows whose logits are needed.
    """
    tokens = sum(n for _, n in rows)
    ctx = sum(n * s + n * (n + 1) // 2 for s, n in rows)
    return (2 * linear_params(m) * tokens + attention_flops(m, [ctx])
            + 2 * head_params(m) * finished)


def decode_call_flops(m: dict, contexts) -> int:
    """Model FLOPs of one batched decode call: one token per active slot,
    ``contexts`` = tokens each attends to (its position + 1)."""
    return (2 * (linear_params(m) + head_params(m)) * len(contexts)
            + attention_flops(m, contexts))


def paged_attention_cost(m: dict, contexts, page_size: int,
                         act_bytes: int = 2) -> tuple[int, float]:
    """(FLOPs, bytes) of the paged-attention kernel over all layers of one
    decode call: the packed pages holding live tokens, plus q and out."""
    pages = sum(math.ceil(c / page_size) for c in contexts)
    kv = pages * page_size * kv_bytes_per_token(m)
    qo = 2 * m["L"] * len(contexts) * m["hq"] * m["hd"] * act_bytes
    return attention_flops(m, contexts), kv + qo


def roofline_seconds(flops: float, nbytes: float, peak: dict) -> tuple[float, str]:
    """Least time on the chip and which bound sets it."""
    t_c = flops / peak["bf16_flops_per_s"]
    t_m = nbytes / peak["hbm_bytes_per_s"]
    return (t_c, "compute") if t_c >= t_m else (t_m, "memory")
