"""Paths, cell lookup by name, device facts and the table of peaks."""

from __future__ import annotations

import dataclasses
import importlib.util
import json
from pathlib import Path

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent


@dataclasses.dataclass(frozen=True)
class Cell:
    """One entry of ``BENCHMARK.json``'s ``workloads`` with its data files."""

    name: str
    chips: int
    config: dict  # bench/configs/<config>.json
    traffic: dict  # bench/traffic/<traffic>.json
    end_to_end: tuple  # metric entries this cell reports with --trace 0
    per_layer: tuple  # metric entries this cell reports with --trace 1


def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def _reports(metric: dict, cell: str, e2e_names: set) -> bool:
    if "workloads" in metric:
        return cell in metric["workloads"]
    return metric.get("moves", metric["name"]) in e2e_names


def load_cell(name: str, benchmark: Path = ROOT / "BENCHMARK.json") -> Cell:
    spec = load_json(benchmark)
    by_name = {w["name"]: w for w in spec["workloads"]}
    if name not in by_name:
        raise KeyError(f"no workload {name!r} in {benchmark}; have {sorted(by_name)}")
    w = by_name[name]
    cfg_entry = next(c for c in spec["configs"] if c["name"] == w["config"])
    e2e = tuple(m for m in spec["end_to_end"]
                if "workloads" not in m or name in m["workloads"])
    e2e_names = {m["name"] for m in e2e}
    per_layer = tuple(m for m in spec["per_layer"] if _reports(m, name, e2e_names))
    return Cell(name=name, chips=int(w["chips"]),
                config=load_json(ROOT / cfg_entry["file"]),
                traffic=load_json(BENCH / "traffic" / f"{w['traffic']}.json"),
                end_to_end=e2e, per_layer=per_layer)


def load_module(path: Path, name: str):
    """Import a file by path (metric readers carry dots in their names)."""
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def reference_module(config: dict):
    """The configuration's plain reference, ``bench/reference/<name>.py``."""
    name = config["reference"]
    return load_module(BENCH / "reference" / f"{name}.py", f"bench_reference_{name}")


def peaks_for(device_kind: str, table: Path = BENCH / "peaks.json") -> dict:
    """Published peaks of one chip of ``device_kind``; unknown kinds are an error."""
    peaks = load_json(table)
    if device_kind not in peaks:
        raise KeyError(f"no peaks for device kind {device_kind!r} in {table}; "
                       f"known: {sorted(peaks)}")
    return peaks[device_kind]


def device_info(jax) -> dict:
    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def check_device(jax, chips: int) -> str | None:
    """Why this process cannot run a cell on ``chips`` TPU chips, or None."""
    backend = jax.default_backend()
    if backend != "tpu":
        return f"needs a TPU, JAX found backend {backend!r}"
    n = len(jax.devices())
    if n < chips:
        return f"cell needs {chips} chips, JAX sees {n}"
    return None


def peak_bytes(jax, devices) -> int | None:
    """Peak bytes in use on the fullest of ``devices``."""
    vals = [(d.memory_stats() or {}).get("peak_bytes_in_use") for d in devices]
    vals = [v for v in vals if v is not None]
    return max(vals) if vals else None
