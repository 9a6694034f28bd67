"""Sharding rules: param-path → PartitionSpec, MaxText-style.

Mesh axes: ``(pod, data, model)`` multi-pod or ``(data, model)`` single-pod.

  * batch/tokens      → (pod, data)            [DP]
  * weights, K dim    → data (+pod)            [FSDP / ZeRO-3]
  * weights, N dim    → model                  [TP: heads / d_ff / vocab]
  * MoE expert dim    → model                  [EP: 128 experts / 16 shards]
  * long-context seq  → data                   [SP / context parallelism]
  * mamba inner dim   → model                  [SSM TP]

Every mapping is divisibility-guarded: a dim is only sharded if the axis size
divides it (e.g. starcoder2's 24 heads are sharded via the fused 3072-wide
projection, not the head count).  Rules are *name-based* over the param-tree
paths so they cover all six families uniformly.
"""

from __future__ import annotations

import re
from typing import Any

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


def dp_axes(mesh: Mesh):
    return ("pod", "data") if "pod" in mesh.axis_names else ("data",)


def fsdp_axes(mesh: Mesh, include_pod: bool):
    if include_pod and "pod" in mesh.axis_names:
        return ("pod", "data")
    return ("data",)


def _axis_size(mesh: Mesh, axes) -> int:
    if axes is None:
        return 1
    if isinstance(axes, str):
        axes = (axes,)
    n = 1
    for a in axes:
        n *= mesh.shape[a]
    return n


def _fit(mesh: Mesh, dim: int, axes):
    """Return ``axes`` if it divides ``dim``, else progressively shrink.

    Preserves the caller's form — a string stays a string, a tuple stays a
    tuple: jax 0.4.x PartitionSpec equality is structural (``('data',)`` !=
    ``'data'``), and the rule tests pin the tuple form for FSDP axes."""
    if axes is None:
        return None
    was_str = isinstance(axes, str)
    if was_str:
        axes = (axes,)
    while axes and dim % _axis_size(mesh, axes) != 0:
        axes = axes[:-1]
    if not axes:
        return None
    return axes[0] if was_str else tuple(axes)


# --- rule table: (path regex, spec builder over trailing dims) ---------------
# Specs are given for the *unstacked* parameter; a leading scan/stack dim
# (layers, super-blocks, experts-in-name) is auto-padded with None.


def _spec_for(path: str, shape: tuple[int, ...], mesh: Mesh, fsdp) -> P:
    tp = "model"

    def fit(dim, axes):
        return _fit(mesh, dim, axes)

    # ---- embeddings / lm head ----
    if re.search(r"embed/table$", path):
        v, d = shape[-2:]
        return P(fit(v, tp), fit(d, fsdp))
    if re.search(r"lm_head/w$", path):
        d, v = shape[-2:]
        return P(*_pad(shape, (fit(d, fsdp), fit(v, tp))))

    # ---- MoE experts: [.., E, K, N] ----
    if re.search(r"moe/(gate|up)$", path):
        e, d, f = shape[-3:]
        return P(*_pad(shape, (fit(e, tp), fit(d, fsdp), None)))
    if re.search(r"moe/down$", path):
        e, f, d = shape[-3:]
        return P(*_pad(shape, (fit(e, tp), None, fit(d, fsdp))))
    if re.search(r"moe/router/w$", path):
        d, e = shape[-2:]
        return P(*_pad(shape, (fit(d, fsdp), None)))

    # ---- column-parallel linears: K → fsdp, N → tp ----
    if re.search(r"(wq|wk|wv|gate|up|in_proj|dt_proj)/w$", path):
        k, n = shape[-2:]
        return P(*_pad(shape, (fit(k, fsdp), fit(n, tp))))
    # ---- row-parallel linears: K → tp, N → fsdp ----
    if re.search(r"(wo|down|out_proj|x_proj)/w$", path):
        k, n = shape[-2:]
        return P(*_pad(shape, (fit(k, tp), fit(n, fsdp))))

    # ---- biases of column-parallel layers ----
    if re.search(r"(wq|wk|wv|gate|up|in_proj|dt_proj)/b$", path):
        return P(*_pad(shape, (fit(shape[-1], tp),)))

    # ---- SSM internals: inner dim → tp ----
    if re.search(r"conv_w$", path):
        return P(*_pad(shape, (None, fit(shape[-1], tp))))
    if re.search(r"conv_b$", path):
        return P(*_pad(shape, (fit(shape[-1], tp),)))
    if re.search(r"A_log$", path) and len(shape) >= 2:
        return P(*_pad(shape, (fit(shape[-2], tp), None)))

    # ---- everything else (norms, scalars, small vectors): replicated ----
    return P(*([None] * len(shape)))


def _pad(shape, trailing) -> tuple:
    """Left-pad a trailing-dims spec with None for stacked leading dims."""
    lead = len(shape) - len(trailing)
    return tuple([None] * lead) + tuple(trailing)


def param_partition(params: Any, mesh: Mesh, include_pod_fsdp: bool = True):
    """PartitionSpec pytree for a param tree (works on ShapeDtypeStructs)."""
    fsdp = fsdp_axes(mesh, include_pod_fsdp)
    flat = jax.tree_util.tree_flatten_with_path(params)[0]
    treedef = jax.tree_util.tree_structure(params)
    specs = []
    for path, leaf in flat:
        pstr = "/".join(_key_name(k) for k in path)
        specs.append(_spec_for(pstr, tuple(leaf.shape), mesh, fsdp))
    return jax.tree_util.tree_unflatten(treedef, specs)


def _key_name(k) -> str:
    if hasattr(k, "key"):
        return str(k.key)
    if hasattr(k, "idx"):
        return str(k.idx)
    return str(k)


def opt_partition(opt_state: Any, param_specs: Any, mesh: Mesh):
    """Adam moments inherit their parameter's spec; int8 block-state stays
    replicated-by-structure (flat blocks don't align with param dims)."""

    def like(spec, leaf):
        if hasattr(leaf, "shape") and len(leaf.shape) == len(spec):
            return spec
        return P(*([None] * len(getattr(leaf, "shape", ()))))

    out = {}
    for key in opt_state:
        if key == "step":
            out[key] = P()
        elif _is_q8_tree(opt_state[key]):
            # int8 moments are blocked along the param's last axis:
            # q [..., n, 256] and s [..., n] inherit the param's leading-dim
            # sharding; the last-dim axis moves to the block-count dim.
            def q8spec(spec, m):
                parts = list(spec) if len(spec) else []
                if isinstance(m, dict):
                    lead = parts[:-1] if parts else []
                    # the param's last-dim axis moves to the block-count dim
                    # — only if the (much smaller) count stays divisible
                    n_blocks = m["q"].shape[-2]
                    last = _fit(mesh, n_blocks, parts[-1]) if parts else None
                    qdims = m["q"].ndim
                    qspec = (lead + [last, None])[:qdims]
                    qspec = [None] * (qdims - len(qspec)) + qspec if len(qspec) < qdims else qspec
                    sdims = m["s"].ndim
                    sspec = (lead + [last])[:sdims]
                    sspec = [None] * (sdims - len(sspec)) + sspec if len(sspec) < sdims else sspec
                    return {"q": P(*qspec), "s": P(*sspec)}
                return P(*([None] * m.ndim))
            out[key] = jax.tree.map(
                q8spec, param_specs, opt_state[key],
                is_leaf=lambda x: isinstance(x, P))
        else:
            out[key] = jax.tree.map(
                lambda spec, m: like(spec, m), param_specs, opt_state[key],
                is_leaf=lambda x: isinstance(x, P))
    return out


def _is_q8_tree(tree) -> bool:
    leaves = jax.tree.leaves(tree)
    return any(getattr(l, "dtype", None) == jnp.int8 for l in leaves)


def partition_state(state, param_specs, mesh: Mesh):
    """Specs for a full TrainState."""
    from repro.train.state import TrainState

    opt = opt_partition(state.opt_state, param_specs, mesh)
    err = None
    if state.err is not None:
        err = param_specs
    return TrainState(param_specs, opt, P(), err)


# ---------------------------------------------------------------------------
# Inputs / activations / caches
# ---------------------------------------------------------------------------


def batch_partition(mesh: Mesh, batch: int, seq: int | None = None) -> P:
    """Token batches: batch over DP axes; context-parallel fallback when the
    batch is too small (long_500k: B=1 → shard the sequence over data)."""
    dp = _fit(mesh, batch, dp_axes(mesh))
    if dp is not None and _axis_size(mesh, dp) > 1:
        return P(dp, None)
    if seq is not None and seq % mesh.shape["data"] == 0:
        return P(None, "data")  # SP / context parallelism
    return P(None, None)


def serve_pool_partition(pool: Any, mesh: Mesh) -> Any:
    """PartitionSpecs for the serving engine's packed page pool.

    Every pool leaf is ``[L, n_pages, H, page, payload]`` (packed codes,
    scales, or a dense-dtype payload) — the KV-head dim (axis 2) is the
    natural shard axis for GQA serving: the paged-attention grid is already
    ``(B, Hkv, pages)``, so each shard runs the identical kernel over its
    local ``Hkv/tp`` heads and local pool slice.  Divisibility-guarded like
    every rule here: a non-divisible head count falls back to replicated,
    which the models' shape-based tp detection treats as "not sharded"
    (consistent by construction)."""

    def spec(leaf):
        ax = _fit(mesh, leaf.shape[2], "model")
        return P(None, None, ax, None, None)

    return jax.tree.map(spec, pool)


def cache_partition(cache_specs: Any, mesh: Mesh, batch: int) -> Any:
    """KV/SSM cache sharding: batch dim → DP axes if divisible; kv-head or
    inner dims → model if divisible; long sequences → data."""

    def spec(leaf):
        shape = leaf.shape
        # stacked caches: [L, B, T, H, hd] / [L, B, T', Di] / [L, B, Di, N]...
        out = [None] * len(shape)
        try:  # batch dim: first dim equal to `batch` after the stack dim
            bdim = next(i for i, s in enumerate(shape) if s == batch and i > 0)
        except StopIteration:
            bdim = None
        dp = _fit(mesh, batch, dp_axes(mesh))
        batch_sharded = bdim is not None and dp is not None and _axis_size(mesh, dp) > 1
        if batch_sharded:
            out[bdim] = dp
        start = (bdim + 1) if bdim is not None else (1 if len(shape) > 1 else 0)
        free = [i for i in range(start, len(shape)) if out[i] is None]

        def fits(i, axis):
            return shape[i] % mesh.shape[axis] == 0 and shape[i] >= mesh.shape[axis]

        # model axis: prefer the head-like dim (second-to-last, ≤512), else
        # the largest remaining divisible dim
        mi = None
        if len(shape) >= 2 and (len(shape) - 2) in free and shape[-2] <= 512 \
                and fits(len(shape) - 2, "model"):
            mi = len(shape) - 2
        else:
            for i in sorted(free, key=lambda i: -shape[i]):
                if fits(i, "model"):
                    mi = i
                    break
        if mi is not None:
            out[mi] = "model"
            free.remove(mi)
        # data axis (when the batch couldn't use it): the seq-like dim
        if not batch_sharded:
            for i in sorted(free, key=lambda i: -shape[i]):
                if shape[i] > 1024 and fits(i, "data"):
                    out[i] = "data"
                    break
        return P(*out)

    return jax.tree.map(spec, cache_specs)
