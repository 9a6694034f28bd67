"""Quantization-health gauges sampled from the packed MXFP4 KV pool.

Low-precision serving needs *numerical* observability: clip rates and scale
distributions are the leading indicators of FP4 degradation (the same
statistics FP4 training work tracks for gradients — see PAPERS.md).  Every
KV write quantizes through ``kernels/kv_pack`` semantics, so the packed pool
*is* the record of what quantization did; this module reduces it device-side
into three cheap health signals per K/V stream:

* **clip fraction** — share of E2M1 codes at the saturating magnitude
  (``kv_pack.E2M1_SAT_IDX``, |x| = 6.0): rising clip means the per-32-group
  AbsMax scales are being overwhelmed by outliers,
* **zero fraction** — share of codes at magnitude 0: rising dead codes mean
  the scale is too coarse for the tail (underflow),
* **E8M0 scale histogram** — 256-bin histogram of the biased scale
  exponents actually stored: drift or widening of this distribution is the
  earliest sign the KV value range is moving.

The reduction is ONE extra jitted function over the whole pool with a
``[n_pages]`` page mask (mapped pages only — scratch page 0 and unmapped
pages never count), compiled once per engine regardless of how many pages
are mapped; the engine fetches it at ``TelemetryConfig.quant_stride`` ticks.
The hot-path step functions are untouched — the compile-count guard in
``tests/test_telemetry.py`` pins that.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels.kv_pack import E2M1_SAT_IDX, split_nibbles

N_SCALE_BINS = 256  # E8M0 biased exponent codes


def page_mask_from_tables(tables: np.ndarray, n_pages: int) -> np.ndarray:
    """Host-side [n_pages] bool mask of pages currently mapped by any slot.
    Page id 0 is the scratch sentinel — never mapped, never counted."""
    mask = np.zeros((n_pages,), bool)
    ids = np.asarray(tables).reshape(-1)
    mask[ids[ids > 0]] = True
    return mask


def _stream_health(codes: jnp.ndarray, scales: jnp.ndarray,
                   page_mask: jnp.ndarray) -> dict:
    """One packed stream ([L, P, H, ps, hd/2] codes + [L, P, H, ps, nb]
    scale codes) → masked clip/zero fractions and the scale histogram."""
    w = page_mask.astype(jnp.int32)[None, :, None, None, None]
    nib = split_nibbles(codes)  # [..., hd] u8
    mag = (nib & 7).astype(jnp.int32)
    # weights broadcast over the doubled last axis exactly like the codes
    w_el = jnp.broadcast_to(w, mag.shape)
    n_elems = jnp.sum(w_el)
    clip = jnp.sum((mag == E2M1_SAT_IDX).astype(jnp.int32) * w_el)
    zero = jnp.sum((mag == 0).astype(jnp.int32) * w_el)
    denom = jnp.maximum(n_elems, 1).astype(jnp.float32)
    w_sc = jnp.broadcast_to(w, scales.shape).reshape(-1)
    hist = jnp.zeros((N_SCALE_BINS,), jnp.int32).at[
        scales.reshape(-1).astype(jnp.int32)].add(w_sc)
    # bin 0 collects unmapped-page zeros scaled by w=0 scatter adds — they
    # contribute 0 counts, so no correction is needed
    return {"clip_frac": clip.astype(jnp.float32) / denom,
            "zero_frac": zero.astype(jnp.float32) / denom,
            "scale_hist": hist,
            "n_elems": n_elems}


@jax.jit
def pool_health(pool: dict, page_mask: jnp.ndarray) -> dict:
    """Packed MXFP4 pool + mapped-page mask → per-stream health dict.

    One compile per pool geometry (shapes are fixed for an engine's
    lifetime; the varying quantity — which pages are mapped — is a runtime
    operand), so sampling never perturbs the step compile counts.
    """
    if "k_codes" not in pool:
        raise ValueError("pool_health needs a packed (mxfp4) pool")
    return {
        "k": _stream_health(pool["k_codes"], pool["k_scales"], page_mask),
        "v": _stream_health(pool["v_codes"], pool["v_scales"], page_mask),
        "mapped_pages": jnp.sum(page_mask.astype(jnp.int32)),
    }


def sample_pool_health(cache) -> dict | None:
    """Host convenience: reduce a :class:`~repro.serve.paged_cache.PagedCache`
    and fetch the result — ``None`` when the pool is dense (nothing to
    measure) or no page is mapped (no live KV)."""
    if cache.kv_dtype != "mxfp4":
        return None
    mask = cache.page_mask()
    if not mask.any():
        return None
    out = pool_health(cache.pool, jnp.asarray(mask))
    return jax.tree.map(np.asarray, out)


@jax.jit
def ring_health(pool: dict, page_mask: jnp.ndarray) -> dict:
    """One packed state-ring plane ([P, E/2] codes + [P, E/32] scale codes)
    + live-page mask → clip/zero fractions.  The block-padding tail of each
    page quantizes exact zeros, so it rides in the zero fraction as a small
    constant floor (same pages every sample — trends are unaffected)."""
    if "codes" not in pool:
        raise ValueError("ring_health needs a packed (mxfp4) ring plane")
    w = page_mask.astype(jnp.int32)[:, None]
    nib = split_nibbles(pool["codes"])  # [P, E] u8
    mag = (nib & 7).astype(jnp.int32)
    w_el = jnp.broadcast_to(w, mag.shape)
    n_elems = jnp.sum(w_el)
    clip = jnp.sum((mag == E2M1_SAT_IDX).astype(jnp.int32) * w_el)
    zero = jnp.sum((mag == 0).astype(jnp.int32) * w_el)
    denom = jnp.maximum(n_elems, 1).astype(jnp.float32)
    return {"clip_frac": clip.astype(jnp.float32) / denom,
            "zero_frac": zero.astype(jnp.float32) / denom,
            "n_elems": n_elems}


def sample_state_health(pool) -> dict | None:
    """Reduce a :class:`~repro.serve.state_pool.StatePool`, per tenant kind:
    ``"kv"``/``"cross"`` reuse the paged-plane reduction (each is a real
    :class:`PagedCache`); ``"state"`` aggregates every ring plane's
    clip/zero fractions over the pages holding each live slot's CURRENT
    state, element-weighted across planes.  ``None`` when the pool is dense
    or nothing is live."""
    if pool.kv_dtype != "mxfp4":
        return None
    out = {}
    if pool.kv is not None and (h := sample_pool_health(pool.kv)) is not None:
        out["kv"] = h
    if pool.cross is not None and (h := sample_pool_health(pool.cross)) is not None:
        out["cross"] = h
    if pool.rings:
        mask = pool.ring_page_mask()
        if mask.any():
            clip = zero = n = 0
            for r in pool.rings:
                h = jax.tree.map(np.asarray, ring_health(r.pool, jnp.asarray(mask)))
                n_r = int(h["n_elems"])
                clip += float(h["clip_frac"]) * n_r
                zero += float(h["zero_frac"]) * n_r
                n += n_r
            if n:
                out["state"] = {"clip_frac": clip / n, "zero_frac": zero / n,
                                "n_elems": n}
    return out or None
