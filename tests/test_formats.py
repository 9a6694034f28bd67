"""Format-level invariants: grids, E8M0 scales, rounding semantics."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

pytest.importorskip("hypothesis")  # property tests need hypothesis; skip cleanly without it
from hypothesis import given, settings, strategies as st

from repro.core import formats as F

E2M1_VALUES = [0.0, 0.5, 1.0, 1.5, 2.0, 3.0, 4.0, 6.0]


def test_mxfp4_grid_matches_spec():
    pos = [v for v in F.MXFP4.grid if v >= 0]
    assert pos == E2M1_VALUES
    assert F.MXFP4.block == 32 and F.MXFP4.scale_dtype == "e8m0"
    assert F.MXFP4.bits == 4


def test_rtn_matches_native_fp4_cast():
    """Our generic grid RTN must agree with jnp.float4_e2m1fn off ties."""
    x = np.linspace(-7, 7, 4001).astype(np.float32)
    ours = np.asarray(F.rtn_to_grid(jnp.asarray(x), F.MXFP4.grid_array))
    native = np.asarray(jnp.asarray(x).astype(jnp.float4_e2m1fn).astype(jnp.float32))
    mids = {0.25, 0.75, 1.25, 1.75, 2.5, 3.5, 5.0}
    off_tie = ~np.isin(np.abs(x), list(mids))
    np.testing.assert_array_equal(ours[off_tie], native[off_tie])


def test_rtn_e2m1_matches_native_fp4_cast_including_ties():
    """The library's one E2M1 path is arithmetic; it must equal the native
    float4_e2m1fn cast everywhere, ties (to even) and saturation included."""
    x = np.concatenate([np.linspace(-8, 8, 6401),
                        [0.25, 0.75, 1.25, 1.75, 2.5, 3.5, 5.0, 7.0, 1e9],
                        [-0.25, -0.75, -1.25, -1.75, -2.5, -3.5, -5.0, -7.0, -1e9]])
    x = x.astype(np.float32)
    ours = np.asarray(F.rtn_e2m1(jnp.asarray(x)))
    native = np.asarray(jnp.asarray(np.clip(x, -6, 6)).astype(jnp.float4_e2m1fn)
                        .astype(jnp.float32))
    np.testing.assert_array_equal(ours, native)


def test_exp2i_exact():
    e = jnp.arange(-126, 128)
    got = np.asarray(F.exp2i(e), np.float64)
    want = np.exp2(np.arange(-126, 128, dtype=np.float64))
    np.testing.assert_array_equal(got, want.astype(np.float32))


@given(st.floats(1e-30, 1e30))
@settings(max_examples=200, deadline=None)
def test_e8m0_ceil_bounds(s):
    q = float(F.round_scale_e8m0(jnp.float32(s), "ceil"))
    assert q >= np.float32(s) * (1 - 1e-6) or q == 2.0**127
    assert q / 2 < np.float32(s) * (1 + 1e-5) or q == 2.0**-126
    assert np.log2(q) == int(np.log2(q))  # exact power of two


def test_e8m0_code_roundtrip():
    scales = F.exp2i(jnp.arange(-126, 128))
    codes = F.scale_to_e8m0_code(scales)
    back = F.e8m0_code_to_scale(codes)
    np.testing.assert_array_equal(np.asarray(scales), np.asarray(back))


def test_stochastic_round_stays_on_grid():
    key = jax.random.PRNGKey(0)
    x = jax.random.normal(key, (1024,)) * 3
    u = jax.random.uniform(jax.random.PRNGKey(1), (1024,))
    q = F.stochastic_round_to_grid(x, F.MXFP4.grid_array, u)
    grid = np.asarray(F.MXFP4.grid_array)
    assert np.isin(np.asarray(q), grid).all()


def test_stochastic_round_unbiased_interior():
    """E[SR(x)] == x exactly for in-range values (analytic, not MC)."""
    x = jnp.float32(2.4)  # between grid points 2 and 3
    us = jnp.linspace(0, 1, 10001)[:-1]
    q = F.stochastic_round_to_grid(jnp.full_like(us, x), F.MXFP4.grid_array, us)
    assert abs(float(q.mean()) - 2.4) < 1e-3


def test_gaussian_optimal_clip_sane():
    c = F.gaussian_optimal_clip("mxfp4")
    assert 2.0 < c < 4.0  # literature value ≈ 2.92 for E2M1


def test_blocks_roundtrip():
    x = jnp.arange(96.0).reshape(2, 48)
    xb = F.to_blocks(x, 16)
    assert xb.shape == (2, 3, 16)
    np.testing.assert_array_equal(np.asarray(F.from_blocks(xb)), np.asarray(x))
    with pytest.raises(ValueError):
        F.to_blocks(x, 32)
