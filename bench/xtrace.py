"""From a profiler trace to a small timeline, and from that to numbers.

``from_xspace`` reads the ``.xplane.pb`` the JAX profiler writes and keeps
only what the metrics need: per device, the XLA module executions (one per
jitted step call, e.g. ``jit_decode_all``) and the XLA ops inside them
(fusions and Pallas custom calls); on the host, the harness's own
annotations (``bench.window``, ``engine.step``, ``driver.submit``,
``driver.wait``).  Everything after that works on the ``Timeline`` alone, so
tests can feed it a small recorded one.  Times are nanoseconds.
"""

from __future__ import annotations

import dataclasses
import glob
import os
from collections import defaultdict

HOST_SPANS = ("bench.window", "engine.step", "driver.submit", "driver.wait")
# ops that only contain other ops (a scan's loop): their time is their body's
CONTAINERS = ("while", "conditional", "call")


@dataclasses.dataclass
class Timeline:
    window: tuple[float, float]
    # device name -> {"modules": [(name, start, end)], "ops": [(name, start, end)]}
    devices: dict
    host: list  # [(name, start, end)]

    def to_json(self) -> dict:
        return dataclasses.asdict(self)

    @classmethod
    def from_json(cls, d: dict) -> "Timeline":
        return cls(window=tuple(d["window"]),
                   devices={k: {kk: [tuple(e) for e in vv] for kk, vv in v.items()}
                            for k, v in d["devices"].items()},
                   host=[tuple(e) for e in d["host"]])


def _module_name(raw: str) -> str:
    """``jit_decode_all(12)`` -> ``jit_decode_all``."""
    return raw.split("(")[0].strip()


def op_name(raw: str) -> str:
    """``%paged_attention.11 = bf16[...] custom-call(...)`` -> ``paged_attention.11``."""
    return raw.split(" = ")[0].strip().lstrip("%")


def op_kind(name: str) -> str:
    """``paged_attention.11`` -> ``paged_attention``."""
    return name.rstrip("0123456789").rstrip(".")


def find_xspace(logdir: str) -> str:
    paths = sorted(glob.glob(os.path.join(logdir, "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {logdir}")
    return paths[-1]


def from_profile(pd) -> Timeline:
    """Build a Timeline from a ``jax.profiler.ProfileData``."""
    devices, host = {}, []
    for plane in pd.planes:
        if plane.name.startswith("/device:") and "TPU" in plane.name:
            dev = {"modules": [], "ops": []}
            for line in plane.lines:
                key = ("modules" if line.name == "XLA Modules"
                       else "ops" if line.name == "XLA Ops" else None)
                if key is None:
                    continue
                for e in line.events:
                    name = _module_name(e.name) if key == "modules" else op_name(e.name)
                    if key == "ops" and op_kind(name) in CONTAINERS:
                        continue
                    dev[key].append((name, e.start_ns, e.start_ns + e.duration_ns))
            devices[plane.name] = dev
        elif plane.name.startswith("/host:CPU"):
            for line in plane.lines:
                for e in line.events:
                    if e.name in HOST_SPANS:
                        host.append((e.name, e.start_ns, e.start_ns + e.duration_ns))
    windows = [(s, e) for n, s, e in host if n == "bench.window"]
    if not windows:
        raise ValueError("trace holds no 'bench.window' annotation")
    return Timeline(window=windows[0], devices=devices,
                    host=sorted((h for h in host if h[0] != "bench.window"),
                                key=lambda h: h[1]))


def from_xspace(path: str) -> Timeline:
    from jax.profiler import ProfileData

    return from_profile(ProfileData.from_file(path))


# ---------------------------------------------------------------------------
# reductions
# ---------------------------------------------------------------------------


def clip(events, window):
    lo, hi = window
    return [(n, max(s, lo), min(e, hi)) for n, s, e in events if e > lo and s < hi]


def union(intervals) -> list[tuple[float, float]]:
    out = []
    for s, e in sorted((s, e) for s, e in intervals if e > s):
        if out and s <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], e))
        else:
            out.append((s, e))
    return out


def busy_ns(tl: Timeline, device: str) -> float:
    """Time in the window in which any op ran on ``device``."""
    ops = clip(tl.devices[device]["ops"], tl.window)
    return sum(e - s for s, e in union((s, e) for _, s, e in ops))


def idle_gaps(tl: Timeline, device: str) -> list[tuple[float, float]]:
    lo, hi = tl.window
    busy = union((s, e) for _, s, e in clip(tl.devices[device]["ops"], tl.window))
    gaps, t = [], lo
    for s, e in busy:
        if s > t:
            gaps.append((t, s))
        t = max(t, e)
    if hi > t:
        gaps.append((t, hi))
    return gaps


def host_label(tl: Timeline, start: float, end: float) -> str:
    """The harness span that covers most of ``[start, end]``."""
    cover = defaultdict(float)
    for name, s, e in tl.host:
        ov = min(e, end) - max(s, start)
        if ov > 0:
            cover[name] += ov
    return max(cover, key=cover.get) if cover else "outside harness spans"


def module_calls(tl: Timeline, device: str, module: str) -> list[tuple[float, float]]:
    """Executions of one jitted step that lie wholly inside the window."""
    lo, hi = tl.window
    return [(s, e) for n, s, e in tl.devices[device]["modules"]
            if n == module and s >= lo and e <= hi]


def ops_in_calls(tl: Timeline, device: str, calls, match) -> float:
    """Summed duration of ops whose name satisfies ``match`` inside ``calls``."""
    calls = sorted(calls)
    total, i = 0.0, 0
    for name, s, e in sorted(tl.devices[device]["ops"], key=lambda x: x[1]):
        while i < len(calls) and calls[i][1] <= s:
            i += 1
        if i < len(calls) and calls[i][0] <= s and e <= calls[i][1] and match(name):
            total += e - s
    return total


def op_module(tl: Timeline, device: str) -> list[tuple[str, str, float]]:
    """(module, op, duration) for every op in the window."""
    mods = sorted(clip(tl.devices[device]["modules"], tl.window), key=lambda x: x[1])
    out, i = [], 0
    for name, s, e in sorted(clip(tl.devices[device]["ops"], tl.window), key=lambda x: x[1]):
        while i < len(mods) and mods[i][2] <= s:
            i += 1
        mod = mods[i][0] if i < len(mods) and mods[i][1] <= s else "?"
        out.append((mod, name, e - s))
    return out


def breakdown(tl: Timeline, top: int = 10) -> dict:
    """Device ops that took most time, and the longest idle gaps by what the
    host was doing in them (seconds, all devices pooled)."""
    ops = defaultdict(float)
    gaps = []
    for dev in tl.devices:
        for mod, name, dur in op_module(tl, dev):
            ops[f"{mod}/{op_kind(name)}"] += dur
        gaps += [(host_label(tl, s, e), e - s) for s, e in idle_gaps(tl, dev)]
    top_ops = sorted(ops.items(), key=lambda kv: -kv[1])[:top]
    top_gaps = sorted(gaps, key=lambda g: -g[1])[:top]
    return {"device_ops": [[n, v * 1e-9] for n, v in top_ops],
            "idle_gaps": [[n, v * 1e-9] for n, v in top_gaps]}


