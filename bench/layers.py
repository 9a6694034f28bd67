"""Per-layer metrics: the context their readers see, and how they are found.

Each metric in ``BENCHMARK.json``'s ``per_layer`` has a reader
``bench/metrics/<name>.py`` with ``read(ctx) -> float | None``.  A reader
that finds nothing to read returns None and the metric is left out of the
line.  The shared arithmetic lives here, so a reader is a line or two.
"""

from __future__ import annotations

import dataclasses

import numpy as np

from bench import common, flops, xtrace

DECODE = "jit_decode_all"
PREFILL = "jit_prefill_all"


def is_paged_attention(op_name: str) -> bool:
    """The Pallas paged-attention kernel: in the trace it is the custom call
    ``paged_attention.<n>`` (``tpu_custom_call``), named after its entry
    point in ``kernels/paged_attention.py``."""
    return xtrace.op_kind(op_name) == "paged_attention"


@dataclasses.dataclass
class Context:
    cell: common.Cell
    timeline: xtrace.Timeline
    steps: list  # harness StepRec of the traced window
    recs: list  # harness Rec of the whole run
    t0: float  # traced window on the harness clock
    t1: float
    peak: dict
    m: dict  # the configuration's dims
    notes: dict = dataclasses.field(default_factory=dict)

    @property
    def devices(self):
        return sorted(self.timeline.devices)

    def calls(self, module: str) -> list[list[tuple[float, float]]]:
        return [xtrace.module_calls(self.timeline, d, module) for d in self.devices]

    def note(self, key, value):
        self.notes[key] = value


def mean_call_ms(ctx: Context, module: str) -> float | None:
    per_dev = [np.mean([e - s for s, e in c]) for c in ctx.calls(module) if c]
    return 1e-6 * float(np.mean(per_dev)) if per_dev else None


def _device_seconds(ctx: Context, module: str) -> tuple[float, int]:
    """Mean over devices of the summed device time of ``module``'s calls."""
    calls = ctx.calls(module)
    if not any(calls):
        return 0.0, 0
    return (1e-9 * float(np.mean([sum(e - s for s, e in c) for c in calls])),
            int(np.mean([len(c) for c in calls])))


def _per_call_ratio(ctx, module, work_per_step, host_calls):
    """Work over device time, scaled by calls seen in the trace against calls
    the harness made, should the two differ at the window's edges."""
    dev_s, n_dev = _device_seconds(ctx, module)
    if not n_dev or not host_calls:
        return None, None
    work = sum(work_per_step) * n_dev / len(host_calls)
    ctx.note(f"{module}_calls", {"trace": n_dev, "harness": len(host_calls)})
    return work, dev_s


def counted(ctx: Context, metric: str) -> bool:
    """Whether ``bench/flops.py`` can count the configuration's work; where
    its dims declare more than a dense full-attention decoder, a note says
    why the metric is left out."""
    extra = sorted(set(ctx.m) - flops.DENSE_DIMS)
    if extra:
        ctx.note(f"{metric}_not_counted",
                 f"dims {extra}: bench/flops.py counts only a dense full-attention decoder")
    return not extra


def decode_mfu(ctx: Context) -> float | None:
    if not counted(ctx, "decode_mfu"):
        return None
    steps = [s for s in ctx.steps if s.decode_contexts]
    work, dev_s = _per_call_ratio(
        ctx, DECODE, [flops.decode_call_flops(ctx.m, s.decode_contexts) for s in steps], steps)
    return None if not dev_s else 100.0 * work / (dev_s * ctx.peak["bf16_flops_per_s"])


def prefill_mfu(ctx: Context) -> float | None:
    if not counted(ctx, "prefill_mfu"):
        return None
    steps = [s for s in ctx.steps if s.prefill_rows]
    work, dev_s = _per_call_ratio(
        ctx, PREFILL, [flops.prefill_call_flops(ctx.m, s.prefill_rows, s.prefill_finished)
                       for s in steps], steps)
    return None if not dev_s else 100.0 * work / (dev_s * ctx.peak["bf16_flops_per_s"])


def paged_attention_roofline(ctx: Context) -> float | None:
    """Least time of the kernel's work (live pages, q, out) over its summed
    device time inside ``decode_all``."""
    if not counted(ctx, "paged_attention_roofline"):
        return None
    steps = [s for s in ctx.steps if s.decode_contexts]
    calls = ctx.calls(DECODE)
    kern = [xtrace.ops_in_calls(ctx.timeline, d, c, is_paged_attention)
            for d, c in zip(ctx.devices, calls)]
    n_dev = int(np.mean([len(c) for c in calls])) if calls else 0
    if not steps or not n_dev or not any(kern):
        return None
    page = ctx.cell.config["engine"]["page_size"]
    fl = by = 0.0
    for s in steps:
        f, b = flops.paged_attention_cost(ctx.m, s.decode_contexts, page)
        fl, by = fl + f, by + b
    scale = n_dev / len(steps)
    least, bound = flops.roofline_seconds(fl * scale, by * scale, ctx.peak)
    kern_s = 1e-9 * float(np.mean(kern))
    ctx.note("paged_attention", {"bound": bound, "kernel_s": kern_s, "least_s": least,
                                 "bytes": by * scale, "flops": fl * scale})
    return 100.0 * least / kern_s


def idle_share(ctx: Context) -> float | None:
    tl = ctx.timeline
    span = tl.window[1] - tl.window[0]
    if not ctx.devices or span <= 0:
        return None
    return 100.0 * float(np.mean([1.0 - xtrace.busy_ns(tl, d) / span for d in ctx.devices]))


def read_all(ctx: Context, metrics) -> dict:
    out = {}
    for m in metrics:
        mod = common.load_module(common.BENCH / "metrics" / f"{m['name']}.py",
                                 f"bench_metric_{m['name'].replace('.', '_')}")
        value = mod.read(ctx)
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out
