"""Multi-device serving: TP/DP token-exactness, sharded-pool conservation,
replica placement, and mesh validation.

Run with ``XLA_FLAGS=--xla_force_host_platform_device_count=8`` — device-
dependent tests skip themselves when the process sees too few devices (the
tier-1 suite runs single-device by design; see tests/conftest.py).

The exactness contract (ISSUE 8 / serve/README.md): a TP=2 engine — and a
TP=2 x DP=2 ReplicatedEngine — on a forced-host-device mesh emits
bit-identical tokens to the single-device engine across
dense/MoE x paged/gather x spec on/off x prefix-cache on/off.  Sharding is
exactness-preserving by construction (head/expert slices + tiled all_gather
concats, never a cross-shard reduction), so these are equality asserts, not
tolerance checks.
"""

import itertools

import jax
import numpy as np
import pytest

from repro.configs import get_reduced_config
from repro.launch.mesh import make_local_mesh, make_serve_meshes
from repro.models import build_model
from repro.serve import (Engine, EngineConfig, Placement, ReplicaPlacer,
                         ReplicatedEngine, ShardingConfig, SpecConfig,
                         make_engine)

pytestmark = pytest.mark.sharded

N_DEV = len(jax.devices())
HINT = " (run with XLA_FLAGS=--xla_force_host_platform_device_count=8)"
needs2 = pytest.mark.skipif(N_DEV < 2, reason="needs >= 2 devices" + HINT)
needs4 = pytest.mark.skipif(N_DEV < 4, reason="needs >= 4 devices" + HINT)

ARCHS = ["qwen3-1.7b", "qwen3-moe-235b-a22b"]  # dense, moe
_MODELS: dict = {}


def _setup(arch):
    if arch not in _MODELS:
        cfg = get_reduced_config(arch)
        model = build_model(cfg)
        _MODELS[arch] = (cfg, model, model.init(jax.random.PRNGKey(0)))
    return _MODELS[arch]


def _prompts(cfg, n=4):
    rng = np.random.default_rng(0)
    ps = [rng.integers(0, cfg.vocab_size, 12 + i).astype(np.int32)
          for i in range(n)]
    for i in range(1, n):  # shared 8-token prefix exercises aliasing/COW
        ps[i][:8] = ps[0][:8]
    return ps


def _run(arch, backend, tp=1, dp=1, joint=False):
    """Drain a small workload; returns ([tokens per request], engine)."""
    cfg, model, params = _setup(arch)
    sh = ShardingConfig(tp=tp, dp=dp) if (tp > 1 or dp > 1) else None
    ec = EngineConfig(
        n_slots=2, max_len=64, page_size=8, kv_dtype="mxfp4",
        prefill_chunk=8, decode_backend=backend, sharding=sh,
        # spec + prefix toggle jointly ("on" combos); the self-proposer is
        # the exactness oracle and rides the engine's own sharded steps
        spec=SpecConfig(k=2, proposer="self") if (joint and backend == "paged")
        else None,
        prefix_cache=joint)
    eng = make_engine(model, params, ec)
    for p in _prompts(cfg):
        eng.submit(p, 8)
    done = eng.drain()
    return [r.tokens for r in sorted(done, key=lambda r: r.rid)], eng


# ---------------------------------------------------------------------------
# token-exactness: the 8-combo TP=2 sweep + TP=2 x DP=2
# ---------------------------------------------------------------------------


@needs2
@pytest.mark.parametrize("arch,backend,joint",
                         list(itertools.product(ARCHS, ["paged", "gather"],
                                                [False, True])))
def test_tp2_token_exact(arch, backend, joint):
    base, _ = _run(arch, backend, tp=1, joint=joint)
    tp2, eng = _run(arch, backend, tp=2, joint=joint)
    assert tp2 == base
    assert eng.placement.tp == 2


@needs4
@pytest.mark.parametrize("arch", ARCHS)
def test_tp2_dp2_token_exact(arch):
    base, _ = _run(arch, "paged", tp=1, joint=True)
    tpdp, eng = _run(arch, "paged", tp=2, dp=2, joint=True)
    assert tpdp == base
    assert isinstance(eng, ReplicatedEngine)
    # both replicas actually served work (placer spread the 4 requests)
    assert all(e.completed for e in eng.engines)


# ---------------------------------------------------------------------------
# sharded pool: placement + per-shard conservation
# ---------------------------------------------------------------------------


@needs2
def test_pool_sharded_on_head_axis():
    cfg, model, params = _setup("qwen3-1.7b")
    eng = Engine(model, params, EngineConfig(
        n_slots=2, max_len=64, page_size=8, kv_dtype="mxfp4",
        sharding=ShardingConfig(tp=2)))
    H = cfg.num_kv_heads
    for name, leaf in eng.cache.pool.items():
        assert leaf.shape[2] == H
        shards = leaf.addressable_shards
        assert len(shards) == 2, name
        # each shard holds exactly its H/2-head slice — together they
        # conserve the full pool (no replication, no overlap)
        for s in shards:
            assert s.data.shape[2] == H // 2, name
        lo = sorted(shards, key=lambda s: s.index[2].start or 0)
        full = np.concatenate([np.asarray(s.data) for s in lo], axis=2)
        np.testing.assert_array_equal(full, np.asarray(leaf))


@needs2
def test_sharded_pool_survives_workload_invariants():
    """Allocator invariants (page conservation, refcounts) are host-side and
    must hold regardless of device layout; the pool stays head-sharded after
    a full drain (steps' out_shardings keep the placement)."""
    _, eng = _run("qwen3-1.7b", "paged", tp=2, joint=True)
    eng.cache.check_invariants()
    leaf = next(iter(eng.cache.pool.values()))
    assert len(leaf.addressable_shards) == 2
    assert {s.data.shape[2] for s in leaf.addressable_shards} == {
        leaf.shape[2] // 2}


# ---------------------------------------------------------------------------
# replica placement (pure host logic)
# ---------------------------------------------------------------------------


def test_replica_placer_prefers_free_pages():
    p = ReplicaPlacer(3)
    assert p.place([1, 9, 4], [1, 1, 1]) == 1
    assert p.place([4, 4, 9], [1, 1, 1]) == 2


def test_replica_placer_breaks_ties_by_slots_then_round_robin():
    p = ReplicaPlacer(2)
    assert p.place([5, 5], [1, 3]) == 1  # pages tie → slots decide
    p2 = ReplicaPlacer(3)
    # exact ties round-robin instead of piling onto replica 0
    seen = [p2.place([2, 2, 2], [1, 1, 1]) for _ in range(3)]
    assert seen == [0, 1, 2]


def test_replica_placer_validates():
    with pytest.raises(ValueError):
        ReplicaPlacer(0)


# ---------------------------------------------------------------------------
# mesh / config validation
# ---------------------------------------------------------------------------


def test_make_local_mesh_rejects_non_divisor():
    with pytest.raises(ValueError, match="does not divide"):
        make_local_mesh(model=N_DEV + 1)
    if N_DEV >= 2:  # 3 never divides a power-of-two device count
        bad = 3 if N_DEV % 3 else 5
        if N_DEV % bad:
            with pytest.raises(ValueError, match="does not divide"):
                make_local_mesh(model=bad)


def test_make_local_mesh_rejects_nonpositive():
    with pytest.raises(ValueError, match=">= 1"):
        make_local_mesh(model=0)


def test_make_local_mesh_valid_divisors():
    for m in range(1, N_DEV + 1):
        if N_DEV % m == 0:
            mesh = make_local_mesh(model=m)
            assert mesh.shape["model"] == m
            assert mesh.shape["data"] * m == N_DEV


def test_make_serve_meshes_disjoint_groups():
    with pytest.raises(ValueError, match=">= 1"):
        make_serve_meshes(tp=0)
    with pytest.raises(ValueError, match="devices"):
        make_serve_meshes(tp=N_DEV + 1)
    if N_DEV >= 4:
        meshes = make_serve_meshes(tp=2, dp=2)
        assert len(meshes) == 2
        devs = [d for m in meshes for d in m.devices.flat]
        assert len(set(devs)) == 4  # disjoint


def test_sharding_config_validates():
    with pytest.raises(ValueError):
        ShardingConfig(tp=0)
    with pytest.raises(ValueError):
        Placement(tp=0)


@needs2
def test_engine_rejects_dp_and_nonpaged_tp():
    cfg, model, params = _setup("qwen3-1.7b")
    with pytest.raises(ValueError, match="ReplicatedEngine"):
        Engine(model, params,
               EngineConfig(sharding=ShardingConfig(tp=1, dp=2)))
    ssm_cfg = get_reduced_config("falcon-mamba-7b")
    ssm = build_model(ssm_cfg)
    with pytest.raises(ValueError, match="paged families"):
        Engine(ssm, ssm.init(jax.random.PRNGKey(0)),
               EngineConfig(sharding=ShardingConfig(tp=2)))


@needs4
def test_replicated_engine_unique_rids_and_merge_order():
    _, eng = _run("qwen3-1.7b", "paged", tp=2, dp=2)
    rids = [r.rid for r in eng.completed]
    assert rids == sorted(rids) and len(set(rids)) == len(rids)
    assert {getattr(r, "replica", None) for r in eng.completed} <= {0, 1}


# ---------------------------------------------------------------------------
# DP telemetry aggregation + merged profile trace
# ---------------------------------------------------------------------------


@needs2
def test_dp_aggregate_pools_histograms_and_merges_trace(tmp_path):
    from repro.serve import TelemetryConfig
    from repro.serve.telemetry.profiling import validate_trace_file

    cfg, model, params = _setup("qwen3-1.7b")
    eng = make_engine(model, params, EngineConfig(
        n_slots=2, max_len=64, page_size=8, kv_dtype="mxfp4", prefill_chunk=8,
        sharding=ShardingConfig(tp=1, dp=2),
        telemetry=TelemetryConfig(
            profile_trace_path=str(tmp_path / "dp_trace.json"))))
    assert isinstance(eng, ReplicatedEngine)
    for p in _prompts(cfg):
        eng.submit(p, 8, arrival_time=0.0)
    eng.drain()
    assert all(e.completed for e in eng.engines)  # placer spread the work

    agg = eng.aggregate_telemetry()
    regs = [e.telemetry.registry for e in eng.engines]
    assert agg["replicas"] == 2
    # counters sum across replicas
    assert agg["counters"]["engine_ticks"] == sum(
        r.counter("engine_ticks").value for r in regs)
    assert agg["counters"]["decode_calls"] == sum(
        r.counter("decode_calls").value for r in regs)
    # histograms are POOLED, not dropped (the old aggregate carried only
    # counters + a few gauges): aggregate counts/sums span both replicas
    for hname in ("tick_s", "decode_tick_s", "ttft_s"):
        per = [r.histogram(hname) for r in regs]
        assert agg["histograms"][hname]["count"] == sum(h.count for h in per)
        assert agg["histograms"][hname]["sum"] == pytest.approx(
            sum(h.total for h in per))
    assert agg["histograms"]["tick_s"]["count"] > 0
    # profiler gauges averaged across replicas, nonzero with profiling on
    assert agg["gauges"]["roofline_util_decode"] > 0

    # one merged Perfetto document: a process lane per replica
    path = eng.write_profile()
    doc = validate_trace_file(path)
    payload = [e for e in doc["traceEvents"] if e["ph"] != "M"]
    assert {e["pid"] for e in payload} == {0, 1}
    cats = {e.get("cat") for e in payload}
    assert {"tick", "phase", "request"} <= cats
