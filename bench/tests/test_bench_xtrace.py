"""Trace reduction: a hand-made trace with known answers, read both from the
profiler's own format and as a Timeline, and the per-layer readers on it."""

import pytest
from jax.profiler import ProfileData

from bench import common, flops, harness, layers, xtrace
from bench.tests import small

MS = 1_000_000  # ns


def _events(line_id, name, items, t0=0):
    evs = " ".join(f"events {{ metadata_id: {m} offset_ps: {s * 1000} duration_ps: {d * 1000} }}"
                   for m, s, d in items)
    return f'lines {{ id: {line_id} name: "{name}" timestamp_ns: {t0} {evs} }}'


def _meta(names):
    return " ".join(f'event_metadata {{ key: {i} value {{ id: {i} name: "{n}" }} }}'
                    for i, n in enumerate(names, 1))


# device: decode call [0, 10) ms with a fusion [1, 3) and the kernel [4, 8)
# inside the layer loop [0, 9); prefill call [20, 50) ms with one fusion
# [21, 49); a stray op [60, 61).  Op names are the HLO text the trace holds.
# host: window [0, 100) ms; engine.step [0, 55); driver.wait [55, 100).
XSPACE = f"""
planes {{ id: 1 name: "/device:TPU:0"
  {_events(1, "XLA Modules", [(1, 0, 10 * MS), (2, 20 * MS, 30 * MS)])}
  {_events(2, "XLA Ops", [(3, 1 * MS, 2 * MS), (4, 4 * MS, 4 * MS), (5, 21 * MS, 28 * MS),
                          (6, 60 * MS, 1 * MS), (7, 0, 9 * MS)])}
  {_events(3, "Steps", [(1, 0, 99 * MS)])}
  {_meta(["jit_decode_all(3)", "jit_prefill_all(4)", "%fusion.1 = f32[2] fusion(f32[2] %p)",
          "%paged_attention.11 = bf16[8,8,2,128] custom-call(s32[8,9] %t)",
          "%fusion.9 = f32[2] fusion(f32[2] %q)", "%copy.2 = u8[4] copy(u8[4] %c)",
          "%while.7 = (s32[]) while((s32[]) %init)"])} }}
planes {{ id: 2 name: "/host:CPU"
  {_events(1, "python", [(1, 0, 100 * MS), (2, 0, 55 * MS), (3, 55 * MS, 45 * MS),
                         (4, 56 * MS, 1 * MS)])}
  {_meta(["bench.window", "engine.step", "driver.wait", "something_else"])} }}
"""


@pytest.fixture(scope="module")
def tl():
    return xtrace.from_profile(ProfileData.from_text_proto(XSPACE))


def test_extraction_keeps_modules_ops_and_harness_spans(tl):
    assert tl.window == (0, 100 * MS)
    dev = tl.devices["/device:TPU:0"]
    assert dev["modules"] == [("jit_decode_all", 0, 10 * MS), ("jit_prefill_all", 20 * MS, 50 * MS)]
    assert [o[0] for o in dev["ops"]] == ["fusion.1", "paged_attention.11", "fusion.9",
                                          "copy.2"]  # the loop op holds no time of its own
    assert [h[0] for h in tl.host] == ["engine.step", "driver.wait"]
    assert xtrace.Timeline.from_json(tl.to_json()) == tl


def test_busy_idle_and_what_the_host_did(tl):
    d = "/device:TPU:0"
    assert xtrace.busy_ns(tl, d) == (2 + 4 + 28 + 1) * MS
    gaps = xtrace.idle_gaps(tl, d)
    assert gaps[0] == (0, 1 * MS) and gaps[-1] == (61 * MS, 100 * MS)
    assert sum(e - s for s, e in gaps) == 65 * MS
    assert xtrace.host_label(tl, 61 * MS, 100 * MS) == "driver.wait"
    assert xtrace.host_label(tl, 10 * MS, 20 * MS) == "engine.step"
    bd = xtrace.breakdown(tl)
    assert bd["device_ops"][0] == ["jit_prefill_all/fusion", pytest.approx(0.028)]
    assert ["jit_decode_all/paged_attention", pytest.approx(0.004)] in bd["device_ops"]
    assert bd["idle_gaps"][0] == ["driver.wait", pytest.approx(0.039)]
    assert len(bd["device_ops"]) <= 10 and len(bd["idle_gaps"]) <= 10


def test_calls_and_kernel_time(tl):
    d = "/device:TPU:0"
    calls = xtrace.module_calls(tl, d, "jit_decode_all")
    assert calls == [(0, 10 * MS)]
    assert xtrace.ops_in_calls(tl, d, calls, layers.is_paged_attention) == 4 * MS
    assert xtrace.ops_in_calls(tl, d, calls, lambda n: True) == 6 * MS
    assert xtrace.ops_in_calls(tl, d, xtrace.module_calls(tl, d, "jit_prefill_all"),
                               layers.is_paged_attention) == 0


def _ctx(tl):
    cell = small.small_cell()
    m = common.reference_module(cell.config).dims(cell.config)
    steps = [harness.StepRec(0.0, 0.01, [], 0, [100, 40]),
             harness.StepRec(0.02, 0.05, [(0, 16), (32, 5)], 1, [])]
    recs = [harness.Rec(req=None, due=0.001, submitted=0.001, admitted=0.02),
            harness.Rec(req=None, due=0.05, submitted=0.05)]
    peak = {"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e9}
    return layers.Context(cell=cell, timeline=tl, steps=steps, recs=recs, t0=0.0,
                          t1=0.1, peak=peak, m=m), m, peak


def test_readers_on_the_hand_trace(tl):
    ctx, m, peak = _ctx(tl)
    assert layers.mean_call_ms(ctx, layers.DECODE) == pytest.approx(10.0)
    assert layers.mean_call_ms(ctx, layers.PREFILL) == pytest.approx(30.0)
    assert layers.decode_mfu(ctx) == pytest.approx(
        100 * flops.decode_call_flops(m, [100, 40]) / (0.010 * 1e12))
    assert layers.prefill_mfu(ctx) == pytest.approx(
        100 * flops.prefill_call_flops(m, [(0, 16), (32, 5)], 1) / (0.030 * 1e12))
    f, b = flops.paged_attention_cost(m, [100, 40], 16)
    assert layers.paged_attention_roofline(ctx) == pytest.approx(
        100 * max(f / 1e12, b / 1e9) / 0.004)
    assert ctx.notes["paged_attention"]["bound"] == "memory"
    assert layers.idle_share(ctx) == pytest.approx(65.0)


def test_readers_find_nothing_and_say_so():
    empty = xtrace.Timeline(window=(0, 100), devices={}, host=[])
    ctx, _, _ = _ctx(empty)
    for read in (layers.decode_mfu, layers.prefill_mfu, layers.paged_attention_roofline,
                 layers.idle_share):
        assert read(ctx) is None
    assert layers.mean_call_ms(ctx, layers.DECODE) is None


@pytest.mark.parametrize("read", [layers.decode_mfu, layers.prefill_mfu,
                                  layers.paged_attention_roofline], ids=lambda f: f.__name__)
def test_counting_readers_leave_out_moe_dims(tl, read):
    """``bench/flops.py`` counts a dense decoder: with routed experts in the
    dims a counting reader reads nothing, where the trace has its calls."""
    ctx, _, _ = _ctx(tl)
    moe = common.load_json(small.SMALL_MOE)
    ctx.m = common.reference_module(moe).dims(moe)
    assert read(ctx) is None
    assert "['E', 'k', 'norm_topk']" in ctx.notes[f"{read.__name__}_not_counted"]


def test_every_metric_file_reads_through_read_all(tl):
    ctx, _, _ = _ctx(tl)
    spec = common.load_json(common.ROOT / "BENCHMARK.json")
    out = layers.read_all(ctx, spec["per_layer"])
    assert set(out) <= {m["name"] for m in spec["per_layer"]}
    assert all(v["value"] >= 0 for v in out.values())


def test_recorded_v5e_decode_call():
    """One ``decode_all`` call of qwen3-1.7b (16 slots of 4096 tokens, MXFP4
    pool) as traced on a v5e chip: the paged-attention kernel, called once
    per layer, takes most of the call."""
    tl = xtrace.Timeline.from_json(common.load_json(
        common.BENCH / "tests" / "data" / "v5e_qwen3_decode_call.json"))
    d = "/device:TPU:0"
    calls = xtrace.module_calls(tl, d, "jit_decode_all")
    assert len(calls) == 1
    call = calls[0][1] - calls[0][0]
    kernel = xtrace.ops_in_calls(tl, d, calls, layers.is_paged_attention)
    n_kernel = sum(1 for n, _, _ in tl.devices[d]["ops"] if layers.is_paged_attention(n))
    assert n_kernel == 28  # one per layer
    assert 0.7 < kernel / call < 1.0  # 0.77 as measured
    assert xtrace.busy_ns(tl, d) <= tl.window[1] - tl.window[0]
    bd = xtrace.breakdown(tl)
    assert bd["device_ops"][0][0] == "jit_decode_all/paged_attention"
    ctx, _, _ = _ctx(tl)
    qwen = common.load_json(common.BENCH / "configs" / "qwen3-1.7b.json")
    ctx.m = common.reference_module(qwen).dims(qwen)
    ctx.steps = [harness.StepRec(0.0, 0.5, [], 0, [600] * 16)]
    ctx.peak = common.peaks_for("TPU v5 lite")
    share = layers.paged_attention_roofline(ctx)
    assert 0 < share < 1  # the kernel walks every page column of every slot
    assert ctx.notes["paged_attention"]["bound"] == "memory"
