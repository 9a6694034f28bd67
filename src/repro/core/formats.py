"""Numeric formats for low-precision training.

Implements the microscaling (MX) formats from the OCP MX spec v1.0 [32] and
NVIDIA Blackwell [31], plus the integer grids used by the paper's baselines.

The central object is :class:`Format`: a (possibly non-uniform) quantization
grid together with its block-scaling rule.  MXFP4 = E2M1 element grid +
E8M0 (power-of-two) scale shared over 1-D blocks of 32 elements.

All grids are represented explicitly as sorted jnp arrays so that RTN /
stochastic rounding can be written once, generically, and verified against
``jnp.float4_e2m1fn`` casts (which JAX implements natively with
round-to-nearest-ties-even semantics — see tests/test_formats.py).  The
library itself never uses that cast: :func:`rtn_e2m1` is arithmetic, the
form that also lowers inside a TPU kernel.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Literal

import jax
import jax.numpy as jnp
import numpy as np

# ---------------------------------------------------------------------------
# Element grids
# ---------------------------------------------------------------------------

# E2M1: 1 sign, 2 exponent, 1 mantissa. Positive values:
#   subnormal: 0, 0.5 ;  normals: 1, 1.5, 2, 3, 4, 6
_E2M1_POS = np.array([0.0, 0.5, 1.0, 1.5, 2.0, 3.0, 4.0, 6.0], dtype=np.float64)

# E3M2 (FP6 variant, for completeness / ablations)
_E3M2_POS = np.array(
    [0.0, 0.0625, 0.125, 0.1875, 0.25, 0.3125, 0.375, 0.4375]
    + [v * 2.0**e for e in range(-2, 5) for v in (1.0, 1.25, 1.5, 1.75)],
    dtype=np.float64,
)

# E4M3 (FP8, used as the "lossless" baseline precision in the paper)
def _e4m3_grid() -> np.ndarray:
    vals = [0.0]
    # subnormals: mantissa/8 * 2^-6
    for m in range(1, 8):
        vals.append(m / 8.0 * 2.0**-6)
    # normals: exponent -6..8, 1.m/8 ; top exponent loses 1 code (NaN) -> max 448
    for e in range(-6, 9):
        for m in range(8):
            v = (1.0 + m / 8.0) * 2.0**e
            if v <= 448.0:
                vals.append(v)
    return np.array(sorted(set(vals)), dtype=np.float64)


_E4M3_POS = _e4m3_grid()

# E5M2 (FP8 wide-range variant, gradients in classic mixed precision)
def _e5m2_grid() -> np.ndarray:
    vals = [0.0]
    for m in range(1, 4):
        vals.append(m / 4.0 * 2.0**-14)
    for e in range(-14, 16):
        for m in range(4):
            v = (1.0 + m / 4.0) * 2.0**e
            if v <= 57344.0:
                vals.append(v)
    return np.array(sorted(set(vals)), dtype=np.float64)


_E5M2_POS = _e5m2_grid()


def _int_grid(bits: int) -> np.ndarray:
    """Symmetric integer grid, e.g. INT4 -> -7..7 (symmetric, no -8)."""
    m = 2 ** (bits - 1) - 1
    return np.arange(0, m + 1, dtype=np.float64)


def _signed(pos: np.ndarray) -> np.ndarray:
    return np.unique(np.concatenate([-pos, pos]))


@dataclasses.dataclass(frozen=True)
class Format:
    """A block-scaled quantization format.

    Attributes:
      name: identifier, e.g. "mxfp4".
      grid: full signed grid (sorted 1-D float32 array) of representable
        element values at scale 1.
      block: block size sharing one scale (1-D blocks along the last /
        contraction dimension). ``0`` means per-tensor scale.
      scale_dtype: "e8m0" (power-of-two, MX formats), "e4m3" (NVFP4), or
        "fp32" (idealised).
      bits: element bit-width (for BOPS speedup modelling).
    """

    name: str
    grid: tuple[float, ...]
    block: int
    scale_dtype: Literal["e8m0", "e4m3", "fp32"]
    bits: int

    @property
    def grid_array(self) -> np.ndarray:
        # host-side (numpy) so static masks/splits stay concrete under jit
        return np.asarray(self.grid, dtype=np.float32)

    @property
    def max_value(self) -> float:
        return float(self.grid[-1])

    @property
    def num_levels(self) -> int:
        return len(self.grid)


MXFP4 = Format("mxfp4", tuple(_signed(_E2M1_POS)), 32, "e8m0", 4)
NVFP4 = Format("nvfp4", tuple(_signed(_E2M1_POS)), 16, "e4m3", 4)
MXFP6 = Format("mxfp6", tuple(_signed(_E3M2_POS)), 32, "e8m0", 6)
MXFP8 = Format("mxfp8", tuple(_signed(_E4M3_POS)), 32, "e8m0", 8)
FP8_E4M3 = Format("fp8_e4m3", tuple(_signed(_E4M3_POS)), 0, "fp32", 8)
FP8_E5M2 = Format("fp8_e5m2", tuple(_signed(_E5M2_POS)), 0, "fp32", 8)
INT4 = Format("int4", tuple(_signed(_int_grid(4))), 32, "fp32", 4)
INT8 = Format("int8", tuple(_signed(_int_grid(8))), 32, "fp32", 8)
BF16 = Format("bf16", (), 0, "fp32", 16)  # passthrough sentinel

FORMATS: dict[str, Format] = {
    f.name: f
    for f in (MXFP4, NVFP4, MXFP6, MXFP8, FP8_E4M3, FP8_E5M2, INT4, INT8, BF16)
}


def get_format(name: str) -> Format:
    try:
        return FORMATS[name]
    except KeyError:
        raise ValueError(f"unknown format {name!r}; have {sorted(FORMATS)}") from None


# ---------------------------------------------------------------------------
# E8M0 scale handling
# ---------------------------------------------------------------------------

# OCP E8M0 spans 2^-127..2^127; we clamp the simulation to the f32 *normal*
# floor (2^-126): XLA's exp2 flushes below it (and is inexact near it), which
# would turn all-zero blocks into 0/0 = NaN.  Blocks at that magnitude
# quantize to zero either way, so this is value-exact.
E8M0_MIN_EXP = -126
E8M0_MAX_EXP = 127


def exp2i(e: jnp.ndarray) -> jnp.ndarray:
    """Exact 2^e for integer-valued e ∈ [-126, 127] via f32 bit manipulation.

    XLA's exp2 is neither exact (≈3e-6 rel. error near the subnormal
    boundary) nor total (flushes 2^-126 to 0 on CPU); power-of-two scales
    must be *bit-exact* for the QDQ GEMM equivalence, so we build the float
    directly: bits = (e + 127) << 23.
    """
    bits = (e.astype(jnp.int32) + 127) << 23
    return jax.lax.bitcast_convert_type(bits, jnp.float32)


def round_scale_e8m0(scale: jnp.ndarray, mode: str = "ceil") -> jnp.ndarray:
    """Quantize positive scales to E8M0 (pure powers of two).

    mode="ceil"   rounds the exponent up — guarantees ``absmax/scale`` stays
                  inside the grid so stochastic rounding never clips; this is
                  the rule used by Tseng et al. [41] and by Quartet's backward.
    mode="nearest" rounds to the nearest power of two (lower MSE; forward).
    """
    scale = jnp.asarray(scale, jnp.float32)
    safe = jnp.maximum(scale, 2.0**E8M0_MIN_EXP)
    log2 = jnp.log2(safe)
    if mode == "ceil":
        e = jnp.ceil(log2 - 1e-6)  # eps: exact powers of two stay put
    elif mode == "floor":
        e = jnp.floor(log2 + 1e-6)
    elif mode == "nearest":
        e = jnp.round(log2)
    else:
        raise ValueError(f"bad e8m0 rounding mode {mode!r}")
    e = jnp.clip(e, E8M0_MIN_EXP, E8M0_MAX_EXP)
    return exp2i(e)


def scale_to_e8m0_code(scale: jnp.ndarray) -> jnp.ndarray:
    """Biased-exponent uint8 code for a power-of-two scale (storage format)."""
    e = jnp.round(jnp.log2(jnp.maximum(scale, 2.0**E8M0_MIN_EXP)))
    return (e + 127.0).astype(jnp.uint8)


def e8m0_code_to_scale(code: jnp.ndarray) -> jnp.ndarray:
    return exp2i(code.astype(jnp.int32) - 127)


def quantize_scale(scale: jnp.ndarray, fmt: Format, mode: str) -> jnp.ndarray:
    """Apply the format's scale-dtype constraint to raw positive scales."""
    if fmt.scale_dtype == "e8m0":
        return round_scale_e8m0(scale, mode)
    if fmt.scale_dtype == "e4m3":
        return scale.astype(jnp.float8_e4m3fn).astype(jnp.float32)
    return scale.astype(jnp.float32)


# ---------------------------------------------------------------------------
# Generic grid rounding (the reference semantics; kernels mirror this)
# ---------------------------------------------------------------------------


def rtn_to_grid(x: jnp.ndarray, grid: jnp.ndarray) -> jnp.ndarray:
    """Round-to-nearest onto an arbitrary sorted grid (ties -> lower index).

    For the E2M1 grid this matches ``x.astype(float4_e2m1fn)`` everywhere
    except exact ties, where IEEE uses ties-to-even; the discrepancy set has
    measure zero and is covered explicitly in tests.
    """
    x = jnp.asarray(x, jnp.float32)
    grid = jnp.asarray(grid)
    mids = (grid[1:] + grid[:-1]) / 2.0
    idx = jnp.searchsorted(mids, x, side="right")
    return grid[idx]


def rtn_e2m1(x: jnp.ndarray) -> jnp.ndarray:
    """Hardware-exact E2M1 RTN (ties-to-even, saturating), in arithmetic:
    saturate to ±6, then round the mantissa to 1 bit per binade with
    ``jnp.round`` (round-half-to-even, matching IEEE).  Subnormals (|x| < 1)
    live on the uniform {0, 0.5, 1} grid, so a single half-unit round covers
    them.  Same values as the native ``float4_e2m1fn`` cast
    (tests/test_formats.py), but pure arithmetic with no gathers, so it
    lowers inside Pallas kernel bodies on every TPU generation — Mosaic
    refuses 4-bit float conversions before TPU v7.
    """
    x = jnp.asarray(x, jnp.float32)
    a = jnp.clip(jnp.abs(x), 0.0, 6.0)
    # normals (1 <= a <= 6): a = m * 2^e with m in [1, 2), e in {0, 1, 2};
    # one mantissa bit => grid step 2^e / 2
    e = jnp.clip(jnp.floor(jnp.log2(jnp.maximum(a, 1.0))), 0.0, 2.0)
    pw = exp2i(e)
    q_norm = jnp.round(a / pw * 2.0) * 0.5 * pw
    q_sub = jnp.round(a * 2.0) * 0.5  # {0, 0.5, 1} uniform region
    q = jnp.where(a >= 1.0, q_norm, q_sub)
    return jnp.sign(x) * q


def stochastic_round_to_grid(
    x: jnp.ndarray, grid: jnp.ndarray, u: jnp.ndarray
) -> jnp.ndarray:
    """Unbiased stochastic rounding onto a symmetric sorted grid.

    Sign-magnitude convention (matches the hardware-style arithmetic SR in
    the Pallas kernels): round |x| up (in magnitude) with probability
    (|x| − lo)/(hi − lo), then reapply the sign.  ``u`` ~ U[0,1) of the same
    shape as ``x``.  Values beyond the grid max saturate (biased there —
    callers pick scales that avoid clipping; Quartet guarantees this via the
    ceil-mode E8M0 scale).
    """
    x = jnp.asarray(x, jnp.float32)
    grid_np = np.asarray(grid)
    pos = jnp.asarray(grid_np[grid_np >= 0])  # positive half (static mask)
    gmax = float(grid_np[-1])
    a = jnp.clip(jnp.abs(x), 0.0, gmax)
    lo_idx = jnp.clip(jnp.searchsorted(pos, a, side="right") - 1, 0, pos.shape[0] - 1)
    hi_idx = jnp.clip(lo_idx + 1, 0, pos.shape[0] - 1)
    lo, hi = pos[lo_idx], pos[hi_idx]
    gap = jnp.where(hi > lo, hi - lo, 1.0)
    p_up = jnp.clip((a - lo) / gap, 0.0, 1.0)
    mag = jnp.where(u < p_up, hi, lo)
    return jnp.sign(x) * mag


# ---------------------------------------------------------------------------
# E2M1 nibble codes (storage format: two elements per byte)
# ---------------------------------------------------------------------------

# Positive E2M1 half-grid in code order: index i encodes sign·_E2M1_POS[i&7],
# bit 3 is the sign — the standard FP4 bit layout (S EE M).
_E2M1_POS_F32 = np.asarray(_E2M1_POS, dtype=np.float32)


def e2m1_to_nibble_i32(q: jnp.ndarray) -> jnp.ndarray:
    """On-grid E2M1 values (scale 1) → 4-bit codes 0..15 as int32.

    Pure arithmetic (no searchsorted): for |q| ≥ 1 the magnitude index is
    2 + 2·e + m with e = floor(log2|q|) and m the half-step mantissa bit;
    below 1 the grid is uniform at 0.5.  Negative zero maps to code 0.
    int32 throughout, so kernel bodies can pack the codes before the one
    int32 → uint8 narrowing at the store (Mosaic has no float → uint8 cast).
    """
    q = jnp.asarray(q, jnp.float32)
    a = jnp.abs(q)
    e = jnp.clip(jnp.floor(jnp.log2(jnp.maximum(a, 1.0))), 0.0, 2.0)
    m = a / exp2i(e) * 2.0 - 2.0  # 0 or 1 for on-grid normals
    idx_norm = 2.0 + 2.0 * e + m
    idx = jnp.where(a >= 1.0, idx_norm, a * 2.0)
    sign = (q < 0).astype(jnp.int32) << 3
    return idx.astype(jnp.int32) | sign


def e2m1_to_nibble(q: jnp.ndarray) -> jnp.ndarray:
    """On-grid E2M1 values (scale 1) → 4-bit codes 0..15 (uint8)."""
    return e2m1_to_nibble_i32(q).astype(jnp.uint8)


def nibble_to_e2m1(codes: jnp.ndarray) -> jnp.ndarray:
    """4-bit codes 0..15 (uint8) → f32 E2M1 grid values."""
    mag = jnp.asarray(_E2M1_POS_F32)[(codes & 7).astype(jnp.int32)]
    return jnp.where((codes & 8) > 0, -mag, mag)


def pack_nibbles(codes: jnp.ndarray) -> jnp.ndarray:
    """uint8 codes 0..15 [..., K] → packed uint8 [..., K/2] (even elem = high
    nibble).  K must be even."""
    k = codes.shape[-1]
    if k % 2 != 0:
        raise ValueError(f"last dim {k} not even")
    pairs = codes.reshape(*codes.shape[:-1], k // 2, 2)
    return (pairs[..., 0] << 4) | (pairs[..., 1] & 0xF)


def unpack_nibbles(packed: jnp.ndarray) -> jnp.ndarray:
    """packed uint8 [..., K/2] → uint8 codes 0..15 [..., K]."""
    hi = (packed >> 4) & 0xF
    lo = packed & 0xF
    return jnp.stack([hi, lo], axis=-1).reshape(*packed.shape[:-1], -1)


# ---------------------------------------------------------------------------
# Block reshaping helpers
# ---------------------------------------------------------------------------


def to_blocks(x: jnp.ndarray, block: int) -> jnp.ndarray:
    """Reshape [..., K] -> [..., K // block, block]. K must divide by block."""
    if block <= 0:
        return x[..., None, :] if x.ndim >= 1 else x
    k = x.shape[-1]
    if k % block != 0:
        raise ValueError(f"last dim {k} not divisible by block {block}")
    return x.reshape(*x.shape[:-1], k // block, block)


def from_blocks(xb: jnp.ndarray) -> jnp.ndarray:
    return xb.reshape(*xb.shape[:-2], xb.shape[-2] * xb.shape[-1])


@functools.lru_cache(maxsize=None)
def gaussian_optimal_clip(fmt_name: str) -> float:
    """Clip multiplier c* minimizing E[(x - Q(clip(x)))^2], x ~ N(0,1).

    QuEST [33] fits the quantization scale to the RMS of the (Hadamard-
    Gaussianized) input: scale = c* · std.  We precompute c* per grid by
    numeric integration over a fine Gaussian quadrature — done once, on host.
    """
    fmt = get_format(fmt_name)
    grid = np.asarray(fmt.grid, dtype=np.float64)
    gmax = grid[-1]
    xs = np.linspace(-12.0, 12.0, 48001)
    pdf = np.exp(-0.5 * xs**2) / np.sqrt(2 * np.pi)

    def mse(c: float) -> float:
        scaled = xs / (c / gmax)  # scale s.t. clip point = c*std
        mids = (grid[1:] + grid[:-1]) / 2.0
        q = grid[np.searchsorted(mids, np.clip(scaled, -gmax, gmax))]
        err = (xs - q * (c / gmax)) ** 2
        return float(np.trapezoid(err * pdf, xs))

    cs = np.linspace(1.0, 8.0, 141)
    errs = [mse(c) for c in cs]
    c0 = cs[int(np.argmin(errs))]
    cs2 = np.linspace(c0 - 0.1, c0 + 0.1, 81)
    errs2 = [mse(c) for c in cs2]
    return float(cs2[int(np.argmin(errs2))])
