"""Fused MXFP4 paged-attention kernel: interpret-mode parity + engine wiring.

Parity contract (tests marked ``kernels``): over sweeps of page size, GQA
group size, ragged per-slot lengths, and pool dtype, the Pallas kernel must
match ``models.attention.blocked_attention`` run over the gathered
(dequantized) KV — token-exact in dense-pool mode (same values, same
online-softmax math), bit-close in mxfp4 mode (both paths read the identical
packed payload), and bounded-error vs the original unquantized values.

Engine contract: with ``kv_dtype="dense"`` the paged-kernel decode backend is
token-for-token identical to both the gather-dense oracle and sequential
``greedy_generate``; with ``kv_dtype="mxfp4"`` it stays within a log-prob
tolerance of the dense run.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_reduced_config
from repro.core import quantizers as Q
from repro.kernels import paged_attention as PA
from repro.models import build_model
from repro.models.attention import blocked_attention

KEY = jax.random.PRNGKey(0)


# ---------------------------------------------------------------------------
# pool construction helpers
# ---------------------------------------------------------------------------


def _empty_pool(mode: str, n_pages: int, ps: int, Hkv: int, hd: int) -> dict:
    if mode == "dense":
        shape = (n_pages, Hkv, ps, hd)
        return {"k": jnp.zeros(shape, jnp.float32),
                "v": jnp.zeros(shape, jnp.float32)}
    nb = hd // PA.quant_block(hd)
    return {"k_codes": jnp.zeros((n_pages, Hkv, ps, hd // 2), jnp.uint8),
            "k_scales": jnp.zeros((n_pages, Hkv, ps, nb), jnp.uint8),
            "v_codes": jnp.zeros((n_pages, Hkv, ps, hd // 2), jnp.uint8),
            "v_scales": jnp.zeros((n_pages, Hkv, ps, nb), jnp.uint8)}


def _scatter_prefix(pool, tables, k, v, counts, ps):
    """Write positions ``[0, counts[b])`` of every slot through the engine's
    write path (``scatter_token``, quantize-on-write per token), in one call."""
    b, t = np.nonzero(np.arange(k.shape[1])[None, :] < np.asarray(counts)[:, None])
    return PA.scatter_token(pool, tables[b, t // ps], jnp.asarray(t % ps),
                            k[b, t], v[b, t])


def _paged_setup(mode, lengths, ps, Hkv, hd, pages_per_slot, seed=0):
    """Random KV scattered into a pool (quantize-on-write in mxfp4 mode) +
    page tables with low ids first — exactly the engine's write path.
    Returns (pool, tables, k_dense, v_dense) where the dense arrays hold the
    values the pool effectively stores."""
    rng = np.random.default_rng(seed)
    B = len(lengths)
    T = pages_per_slot * ps
    k = jnp.asarray(rng.standard_normal((B, T, Hkv, hd)).astype(np.float32) * 1.5)
    v = jnp.asarray(rng.standard_normal((B, T, Hkv, hd)).astype(np.float32) * 1.5)
    n_pages = 1 + B * pages_per_slot
    pool = _empty_pool(mode, n_pages, ps, Hkv, hd)
    tables = np.zeros((B, pages_per_slot), np.int32)
    nxt = 1
    for b in range(B):
        for p in range(-(-lengths[b] // ps)):  # only allocated pages mapped
            tables[b, p] = nxt
            nxt += 1
    tables = jnp.asarray(tables)
    pool = _scatter_prefix(pool, tables, k, v, lengths, ps)
    if mode == "mxfp4":
        fmt = PA.quant_fmt(hd)
        k = Q.kv_dequantize(Q.kv_quantize(k, fmt), fmt)
        v = Q.kv_dequantize(Q.kv_quantize(v, fmt), fmt)
    return pool, tables, k, v


def _run_both(mode, lengths, ps, Hkv, group, hd=32, seed=0, pages_per_slot=None):
    pages_per_slot = pages_per_slot or max(-(-max(lengths) // ps), 2)
    pool, tables, k, v = _paged_setup(mode, lengths, ps, Hkv, hd,
                                      pages_per_slot, seed)
    B, Hq = len(lengths), Hkv * group
    rng = np.random.default_rng(seed + 99)
    q = jnp.asarray(rng.standard_normal((B, Hq, hd)).astype(np.float32))
    ln = jnp.asarray(np.asarray(lengths, np.int32))
    out = PA.paged_attention(q, pool, tables, ln)
    ref = blocked_attention(q[:, None], k, v, (ln - 1)[:, None],
                            causal=True, kv_chunk=ps)[:, 0]
    return out, ref, (q, k, v, ln)


# ---------------------------------------------------------------------------
# kernel parity sweeps (interpret mode)
# ---------------------------------------------------------------------------


@pytest.mark.kernels
@pytest.mark.parametrize("hd", [16, 32, 64, 128])
def test_split_order_dequant_is_the_same_values(hd):
    """The kernel's split-order unpack gives every value bit for bit as
    ``unpack_dequant`` does, only in lanes [even dims | odd dims]; the
    wrapper's two reorders are inverses."""
    from repro.kernels import kv_pack

    block = PA.quant_block(hd)
    rng = np.random.default_rng(hd)
    codes = jnp.asarray(rng.permutation(np.resize(np.arange(256), (64, hd // 2))),
                        jnp.uint8)  # every byte value
    scales = jnp.asarray(rng.integers(0, 256, (64, hd // block)), jnp.uint8)
    ref = kv_pack.unpack_dequant(codes, scales, block)
    got = kv_pack.unpack_dequant_split(codes, scales, block)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(kv_pack.split_order(ref)))
    np.testing.assert_array_equal(np.asarray(kv_pack.interleave_order(got)),
                                  np.asarray(ref))


# Long walks: a wave carries WAVE_TOKENS = 256 tokens (16 pages of 16, 32 of
# 8), and the 40 page columns are not a whole number of blocks.  The slots
# cross several blocks and end inside one, sit at length 1 (a masked lane),
# fill all 40 columns (max_len) or hold a single page.
LONG_WALK = {16: [600, 1, 640, 16], 8: [300, 1, 320, 8]}
LONG_PAGES = 40


def test_long_walk_shapes_cross_blocks():
    """The long-walk cases below take several blocks, end inside one, and
    use a block size that does not divide the pages per slot."""
    for ps, lengths in LONG_WALK.items():
        ppb, _ = PA.block_sizes(ps, 2, 32, 2, LONG_PAGES, [(16, 1), (1, 1)] * 2, 4)
        T = ppb * ps
        assert T == PA.WAVE_TOKENS and LONG_PAGES % ppb
        assert max(lengths) == LONG_PAGES * ps > T and lengths[0] % T


@pytest.mark.kernels
@pytest.mark.parametrize("ps,group,lengths,pages_per_slot", [
    *[pytest.param(ps, g, [7, 1, 2 * ps, ps + 3], None, id=f"{g}-{ps}")
      for g in (1, 2, 4) for ps in (4, 8, 16)],  # ragged, single-token, page-exact
    pytest.param(16, 2, LONG_WALK[16], LONG_PAGES, id="2-16-long-walk"),
    pytest.param(8, 1, LONG_WALK[8], LONG_PAGES, id="1-8-long-walk"),
])
def test_paged_kernel_parity_dense(ps, group, lengths, pages_per_slot):
    out, ref, _ = _run_both("dense", lengths, ps, Hkv=2, group=group,
                            pages_per_slot=pages_per_slot)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=0, atol=1e-5)


@pytest.mark.kernels
@pytest.mark.parametrize("hd,ps,lengths,pages_per_slot", [
    *[pytest.param(hd, ps, [9, 3 * ps, 1], None, id=f"{hd}-{ps}")
      for hd in (16, 32, 64) for ps in (4, 8)],
    pytest.param(32, 16, LONG_WALK[16], LONG_PAGES, id="32-16-long-walk"),
])
def test_paged_kernel_parity_mxfp4(hd, ps, lengths, pages_per_slot):
    """mxfp4 pool: the kernel's in-tile dequant must reproduce the jnp
    dequantize-then-attend reference on the identical packed payload; the
    result must also stay close to attention over the original fp values."""
    out, ref, _ = _run_both("mxfp4", lengths, ps, Hkv=2, group=2, hd=hd,
                            pages_per_slot=pages_per_slot)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=0, atol=1e-5)


@pytest.mark.kernels
@pytest.mark.parametrize("S,mode,ps,lengths", [
    *[pytest.param(S, mode, 4, [6, 1, 9], id=f"{S}-{mode}")
      for S in (2, 4) for mode in ("dense", "mxfp4")],
    # several blocks of 16 pages; the last slot's last row sees max_len
    pytest.param(4, "mxfp4", 16, [500, 1, 637], id="4-mxfp4-long-walk"),
])
def test_paged_kernel_multi_query_parity(S, mode, ps, lengths):
    """Speculative-verify shape: S consecutive queries per slot with per-row
    causal bounds (row s at absolute position lengths[b]-1+s) must match the
    blocked reference with per-row positions over the same ragged batch
    (``lengths``: first-query visible lengths, incl. a fresh slot)."""
    Hkv, group, hd = 2, 2, 32
    pages_per_slot = max(-(-(max(lengths) + S - 1) // ps), 2)
    written = [l + S - 1 for l in lengths]  # burst KV is written before reading
    pool, tables, k, v = _paged_setup(mode, written, ps, Hkv, hd,
                                      pages_per_slot, seed=7)
    B, Hq = len(lengths), Hkv * group
    rng = np.random.default_rng(77)
    q = jnp.asarray(rng.standard_normal((B, S, Hq, hd)).astype(np.float32))
    ln = jnp.asarray(lengths, jnp.int32)
    out = PA.paged_attention(q, pool, tables, ln)
    pos = (ln[:, None] - 1) + jnp.arange(S)[None, :]
    ref = blocked_attention(q, k, v, pos, causal=True, kv_chunk=ps,
                            shared_mask=False)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=0, atol=1e-5)
    # the S == 1 fast path is the same kernel
    out1 = PA.paged_attention(q[:, 0], pool, tables, ln)
    np.testing.assert_allclose(np.asarray(out1), np.asarray(ref[:, 0]),
                               rtol=0, atol=1e-5)


@pytest.mark.kernels
def test_prefill_chunk_layout_write_masking():
    """Valid tokens position onto their own pages; padding of active rows
    lands exactly on the appended all-zero sentinel column; inactive lanes
    sit at position 0 of a zeroed row — every masked write resolves to the
    scratch page."""
    ps, P, C = 4, 3, 5
    tables = jnp.asarray([[1, 2, 3], [4, 5, 0], [7, 8, 9]], jnp.int32)
    mask = jnp.asarray([True, True, False])
    tables = jnp.where(mask[:, None], tables, 0)  # engine zeroes masked rows
    start = jnp.asarray([4, 0, 2], jnp.int32)
    n_valid = jnp.asarray([5, 2, 3], jnp.int32)
    tbl_ext, pos = PA.prefill_chunk_layout(tables, start, n_valid, C, ps, mask)
    assert tbl_ext.shape == (3, P + 1)
    assert bool(jnp.all(tbl_ext[:, -1] == 0))  # sentinel column
    np.testing.assert_array_equal(np.asarray(pos[0]), [4, 5, 6, 7, 8])
    np.testing.assert_array_equal(np.asarray(pos[1]), [0, 1, 12, 12, 12])
    np.testing.assert_array_equal(np.asarray(pos[2]), [0, 0, 0, 0, 0])
    # every position's page lookup: padding/inactive → page 0 (scratch)
    page_ids = np.asarray(tbl_ext)[np.arange(3)[:, None], np.asarray(pos) // ps]
    np.testing.assert_array_equal(page_ids[0], [2, 2, 2, 2, 3])
    np.testing.assert_array_equal(page_ids[1], [4, 4, 0, 0, 0])
    np.testing.assert_array_equal(page_ids[2], [0, 0, 0, 0, 0])


@pytest.mark.kernels
@pytest.mark.parametrize("mode,ps,starts", [
    pytest.param("dense", 4, [4, 0, 9], id="dense"),
    pytest.param("mxfp4", 4, [4, 0, 9], id="mxfp4"),
    # several blocks of 16 pages, the first slot ending inside its second
    pytest.param("mxfp4", 16, [420, 0, 250], id="mxfp4-long-walk"),
])
def test_paged_kernel_batched_prefill_parity(mode, ps, starts):
    """Batched-prefill shape: C queries per slot at per-slot start offsets
    with ragged valid counts.  Valid rows must match the blocked reference
    with per-row positions; padding rows scatter only to the scratch page
    (every real pool page is bit-identical to a run that wrote valid tokens
    only)."""
    Hkv, group, hd, C = 2, 2, 32, 6
    n_valid = [6, 3, 1]  # full chunk / ragged tail / single-token remainder
    B = len(starts)
    written = [s + n for s, n in zip(starts, n_valid)]
    pages_per_slot = max(-(-max(written) // ps) + 1, 2)
    rng = np.random.default_rng(21)
    T = pages_per_slot * ps
    k = jnp.asarray(rng.standard_normal((B, T, Hkv, hd)).astype(np.float32))
    v = jnp.asarray(rng.standard_normal((B, T, Hkv, hd)).astype(np.float32))

    # context prefix (positions < start) written engine-style
    n_pages = 1 + B * pages_per_slot
    pool = _empty_pool(mode, n_pages, ps, Hkv, hd)
    tables = np.zeros((B, pages_per_slot), np.int32)
    nxt = 1
    for b in range(B):
        for p in range(-(-written[b] // ps)):
            tables[b, p] = nxt
            nxt += 1
    tables = jnp.asarray(tables)
    pool = _scatter_prefix(pool, tables, k, v, starts, ps)

    # the chunk itself goes through the batched layout: padding tokens carry
    # garbage K/V that must only ever reach the scratch page
    mask = jnp.asarray([True] * B)
    start_j = jnp.asarray(starts, jnp.int32)
    nv_j = jnp.asarray(n_valid, jnp.int32)
    tbl_ext, positions = PA.prefill_chunk_layout(tables, start_j, nv_j, C, ps, mask)
    ck = np.asarray(rng.standard_normal((B, C, Hkv, hd)), np.float32)
    cv = np.asarray(rng.standard_normal((B, C, Hkv, hd)), np.float32)
    for b in range(B):  # place the chunk's real K/V into the dense reference
        for s in range(n_valid[b]):
            k = k.at[b, starts[b] + s].set(ck[b, s])
            v = v.at[b, starts[b] + s].set(cv[b, s])
    page_ids = tbl_ext[jnp.arange(B)[:, None], positions // ps]
    pool = PA.scatter_token(pool, page_ids, positions % ps,
                            jnp.asarray(ck), jnp.asarray(cv))

    # write-masking conservation: non-scratch pages match a valid-only write
    pool_ref = _scatter_prefix(_empty_pool(mode, n_pages, ps, Hkv, hd),
                               tables, k, v, written, ps)
    for key in pool:
        np.testing.assert_array_equal(np.asarray(pool[key][1:]),
                                      np.asarray(pool_ref[key][1:]))

    if mode == "mxfp4":
        fmt = PA.quant_fmt(hd)
        k = Q.kv_dequantize(Q.kv_quantize(k, fmt), fmt)
        v = Q.kv_dequantize(Q.kv_quantize(v, fmt), fmt)
    q = jnp.asarray(rng.standard_normal((B, C, Hkv * group, hd)), jnp.float32)
    lengths = start_j + 1  # tokens visible to each slot's FIRST chunk row
    out = PA.paged_attention(q, pool, tbl_ext, lengths)
    pos_ref = start_j[:, None] + jnp.arange(C)[None, :]
    ref = blocked_attention(q, k, v, pos_ref, causal=True, kv_chunk=ps,
                            shared_mask=False)
    for b in range(B):  # padding rows are garbage by design — compare valid
        np.testing.assert_allclose(np.asarray(out[b, :n_valid[b]]),
                                   np.asarray(ref[b, :n_valid[b]]),
                                   rtol=0, atol=1e-5)


@pytest.mark.kernels
def test_paged_kernel_mxfp4_bounded_vs_fp():
    """End-to-end quantization error: paged attention over the packed pool
    vs blocked attention over the *original* (unquantized) KV."""
    ps, Hkv, group, hd = 8, 2, 2, 32
    lengths = [13, 25]
    pages_per_slot = 4
    pool, tables, kq, vq = _paged_setup("mxfp4", lengths, ps, Hkv, hd,
                                        pages_per_slot, seed=3)
    # rebuild the original fp values with the same rng stream
    rng = np.random.default_rng(3)
    B, T = len(lengths), pages_per_slot * ps
    k = jnp.asarray(rng.standard_normal((B, T, Hkv, hd)).astype(np.float32) * 1.5)
    v = jnp.asarray(rng.standard_normal((B, T, Hkv, hd)).astype(np.float32) * 1.5)
    q = jnp.asarray(np.random.default_rng(4).standard_normal((B, Hkv * group, hd)),
                    jnp.float32)
    ln = jnp.asarray(lengths, jnp.int32)
    out = PA.paged_attention(q, pool, tables, ln)
    ref_fp = blocked_attention(q[:, None], k, v, (ln - 1)[:, None],
                               causal=True, kv_chunk=ps)[:, 0]
    err = float(jnp.max(jnp.abs(out - ref_fp)))
    # bounded, not exact: E2M1 grid error on K shifts softmax weights and V
    # rows carry ~2^-2 relative error — observed ≈1.1 max over this workload
    assert err < 1.5, err


@pytest.mark.kernels
def test_paged_kernel_ignores_unmapped_pages():
    """Table rows past the valid length point at the scratch page (id 0);
    whatever it contains must not leak into the output."""
    ps, Hkv, group, hd = 4, 2, 2, 32
    lengths = [5, 2]
    pool, tables, k, v = _paged_setup("dense", lengths, ps, Hkv, hd, 4, seed=1)
    # poison the scratch page
    pool["k"] = pool["k"].at[0].set(1e3)
    pool["v"] = pool["v"].at[0].set(1e3)
    q = jnp.asarray(np.random.default_rng(2).standard_normal((2, Hkv * group, hd)),
                    jnp.float32)
    ln = jnp.asarray(lengths, jnp.int32)
    out = PA.paged_attention(q, pool, tables, ln)
    ref = blocked_attention(q[:, None], k, v, (ln - 1)[:, None],
                            causal=True, kv_chunk=ps)[:, 0]
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=0, atol=1e-5)


@pytest.mark.parametrize("n_q", [1, 3])
def test_walk_pages_reads_live_pages_only(n_q):
    """Each block of the walk names only the slot's live pages, every live
    page once in order, and blocks past the last live one repeat it (so the
    pipeline fetches nothing there)."""
    ps, ppb, n_pp = 4, 4, 10
    lengths = np.asarray([1, 13, 16, 38])
    tables = np.arange(1, 1 + 4 * n_pp, dtype=np.int32).reshape(4, n_pp)
    walk = np.asarray(PA.walk_pages(jnp.asarray(tables), jnp.asarray(lengths, jnp.int32),
                                    n_q, ps, ppb)).reshape(4, -1, ppb)
    assert walk.shape[1] == -(-n_pp // ppb)
    for b, ln in enumerate(lengths):
        n_live = -(-(ln + n_q - 1) // ps)
        n_blk = -(-n_live // ppb)
        live = tables[b, :n_live]
        assert set(walk[b].ravel()) <= set(live)
        assert walk[b, :n_blk].ravel()[:n_live].tolist() == live.tolist()
        assert (walk[b, n_blk:] == walk[b, n_blk - 1]).all()


@pytest.mark.kernels
@pytest.mark.parametrize("mode", ["dense", "mxfp4"])
def test_paged_kernel_never_reads_dead_pages(mode):
    """The walk stops at each slot's live pages.  Every dead column of the
    page tables points at a page whose values decode to NaN/Inf, and the
    output still equals the reference: a fetched dead page would reach acc
    through p·v (0 · Inf is NaN).  The walk crosses blocks of 16 pages, ends
    inside one, skips whole dead blocks, holds a slot at length 1, and runs
    S = 1 decode and S = 3 verify bounds.  The same page on a live column
    does poison the output."""
    ps, Hkv, group, hd = 16, 2, 2, 32
    lengths = [600, 1, 300, 16]
    B, Hq = len(lengths), Hkv * group
    for S in (1, 3):
        written = [l + S - 1 for l in lengths]
        pool, tables, k, v = _paged_setup(mode, written, ps, Hkv, hd,
                                          LONG_PAGES, seed=5)
        poison = next(iter(pool.values())).shape[0]  # one page past the pool
        bad = {"k": np.nan, "v": np.nan, "k_codes": 0x77, "v_codes": 0x77,
               "k_scales": 255, "v_scales": 255}  # E8M0 255 decodes to Inf
        pool = {n: jnp.concatenate([x, jnp.full((1, *x.shape[1:]), bad[n], x.dtype)])
                for n, x in pool.items()}
        live = np.asarray([-(-w // ps) for w in written])
        cols = np.arange(LONG_PAGES)[None, :]
        tables = jnp.where(jnp.asarray(cols < live[:, None]), tables, poison)
        rng = np.random.default_rng(55 + S)
        q = jnp.asarray(rng.standard_normal((B, S, Hq, hd)), jnp.float32)
        ln = jnp.asarray(lengths, jnp.int32)
        out = PA.paged_attention(q, pool, tables, ln)
        pos = (ln[:, None] - 1) + jnp.arange(S)[None, :]
        ref = blocked_attention(q, k, v, pos, causal=True, kv_chunk=ps,
                                shared_mask=False)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=0, atol=1e-5)
    hit = PA.paged_attention(q, pool, tables.at[0, 0].set(poison), ln)
    assert not np.isfinite(np.asarray(hit[0])).all()


@pytest.mark.kernels
@pytest.mark.parametrize("hq,hkv", [(4, 2), (8, 2), (6, 3), (4, 4)])
def test_flash_gqa_in_place(hq, hkv):
    """mha_flash maps query-head → KV-head in the block index map: no
    group×-materialized KV (satellite fix), same outputs as the reference."""
    from repro.kernels.flash_attention import mha_flash

    rng = np.random.default_rng(0)
    B, S, hd = 2, 24, 32
    q = jnp.asarray(rng.standard_normal((B, S, hq, hd)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((B, S, hkv, hd)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((B, S, hkv, hd)), jnp.float32)
    pos = jnp.broadcast_to(jnp.arange(S)[None], (B, S))
    for causal in (True, False):
        o1 = mha_flash(q, k, v, causal=causal, block_q=8, block_k=8)
        o2 = blocked_attention(q, k, v, pos, causal=causal, kv_chunk=8)
        np.testing.assert_allclose(np.asarray(o1), np.asarray(o2),
                                   rtol=0, atol=1e-5)


# ---------------------------------------------------------------------------
# engine integration: paged-kernel decode backend
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def qwen_setup():
    cfg = get_reduced_config("qwen3-1.7b")
    model = build_model(cfg)
    return cfg, model, model.init(KEY)


def _run_engine(model, params, prompts, max_new, kv, backend, n_slots=3):
    from repro.serve import Engine, EngineConfig

    eng = Engine(model, params, EngineConfig(
        n_slots=n_slots, max_len=32, page_size=8, kv_dtype=kv,
        prefill_chunk=8, keep_logits=True, decode_backend=backend))
    handles = [eng.submit(p, max_new) for p in prompts]
    eng.drain()
    return eng, handles


def test_engine_paged_decode_token_exact_dense(qwen_setup):
    """decode_backend="paged" == "gather" == sequential greedy, dense pool."""
    from repro.train.serve import greedy_generate

    cfg, model, params = qwen_setup
    rng = np.random.default_rng(11)
    prompts = [rng.integers(0, cfg.vocab_size, n).astype(np.int32)
               for n in (7, 12)]
    _, paged_h = _run_engine(model, params, prompts, 4, "dense", "paged")
    _, gather_h = _run_engine(model, params, prompts, 4, "dense", "gather")
    for p, hp, hg in zip(prompts, paged_h, gather_h):
        assert hp.tokens == hg.tokens
        ref = greedy_generate(model, params, jnp.asarray(p)[None], max_new=4,
                              max_len=int(p.size) + 4)
        assert hp.tokens == ref[0].tolist()


def test_engine_paged_decode_mxfp4_bounded(qwen_setup):
    """mxfp4 paged-kernel decode stays close to the dense-cache run (the
    self-token is quantized on write before it attends to itself, so this is
    a slightly stronger quantization than the gather oracle applies)."""
    cfg, model, params = qwen_setup
    rng = np.random.default_rng(12)
    prompt = rng.integers(0, cfg.vocab_size, 11).astype(np.int32)
    _, dense_h = _run_engine(model, params, [prompt], 4, "dense", "paged")
    _, fp4_h = _run_engine(model, params, [prompt], 4, "mxfp4", "paged")
    d0 = np.asarray(jax.nn.log_softmax(dense_h[0].logits_trace[0]))
    q0 = np.asarray(jax.nn.log_softmax(fp4_h[0].logits_trace[0]))
    assert np.max(np.abs(d0 - q0)) < 2.5
    assert np.mean(np.abs(d0 - q0)) < 0.5


def test_engine_moe_paged_decode_token_exact_dense():
    """MoE layers route through the same attention dispatch — paged decode
    must stay token-exact vs the gather oracle in dense mode."""
    cfg = get_reduced_config("qwen3-moe-235b-a22b")
    model = build_model(cfg)
    params = model.init(KEY)
    rng = np.random.default_rng(13)
    prompts = [rng.integers(0, cfg.vocab_size, 9).astype(np.int32)]
    _, paged_h = _run_engine(model, params, prompts, 3, "dense", "paged")
    _, gather_h = _run_engine(model, params, prompts, 3, "dense", "gather")
    assert paged_h[0].tokens == gather_h[0].tokens


# ---------------------------------------------------------------------------
# allocator: free() restores the low-ids-first contract (satellite fix)
# ---------------------------------------------------------------------------


def test_free_list_low_ids_first_after_out_of_order_retire():
    from repro.serve import PagedCache

    cfg = get_reduced_config("qwen3-1.7b")
    model = build_model(cfg)
    cache = PagedCache(model, n_slots=3, pages_per_slot=2, page_size=4,
                       kv_dtype="dense")
    cache.alloc(0, 8)   # pages 1, 2
    cache.alloc(1, 8)   # pages 3, 4
    cache.alloc(2, 4)   # page 5
    assert cache.tables[0].tolist() == [1, 2]
    cache.free(2)       # out-of-order retirement …
    cache.free(0)       # … returns 5 then {1, 2}
    # pop() must hand out low ids first regardless of retirement order
    cache.alloc(2, 8)
    assert cache.tables[2].tolist() == [1, 2]
    cache.alloc(0, 4)
    assert cache.tables[0].tolist() == [5, 0]
    # invariant: the free list stays descending so pop() is always the min
    assert cache._free == sorted(cache._free, reverse=True)
