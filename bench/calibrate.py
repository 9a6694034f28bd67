"""Readings that set the limits of ``correct``: the program against the
plain reference, and the fp8 control against it, on many seeds.

    python3 bench/calibrate.py --workload <name> --seeds 1,2,3 --seconds 15

For each seed, in one process (set-up is paid once, the step programs come
from the compile cache): fresh weights and traffic from the seed, warm-up,
pre-roll and a window of ``--seconds`` at the cell's own load, then the
finished requests' served tokens are re-scored by the reference, and at
the same positions the token the fp8 control puts first.  Both readings go
through the harness's own decision (``harness.decide``) at the cell's
limits.  Prints one JSON line per seed and a summary, and exits 1 unless
the program is correct and the control is not on every seed.  The
benchmark's own runs never run the control.
"""

import argparse
import gc
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def calibrate(cell, seeds, seconds: float, out=print) -> list[dict]:
    from bench import harness

    rows = []
    for seed in seeds:
        sizing = harness.engine_config(cell.config, cell.traffic)
        _, params, engine = harness.build(cell.config, seed, sizing)
        harness.warm_up(engine)
        driver = harness.Driver(engine, cell.traffic, seed, cell.config["vocab_size"],
                                seconds)
        t = time.monotonic()
        driver.open_at(t + float(cell.traffic["preroll_s"]))
        driver.run(driver.t_open)
        t1 = driver.run(driver.t_open + seconds)
        sample = harness.sample_finished(driver.recs, t1, seed)
        driver.engine = engine = None
        gc.collect()
        g = harness.logit_gaps(cell.config, params, sample, control=True)
        prog, ctrl = harness.gap_stats(g["gap"]), harness.gap_stats(g["control_gap"])
        row = {"seed": seed, "requests": len(sample), "program": prog, "control": ctrl,
               "program_correct": harness.decide(prog, cell.config)[0],
               "control_correct": harness.decide(ctrl, cell.config)[0]}
        out(json.dumps(row))
        rows.append(row)
        del params
        gc.collect()
    return rows


def summary(rows) -> dict:
    keys = [k for k in rows[0]["program"] if k != "tokens"]
    out = {k: {"program_max": max(r["program"][k] for r in rows),
               "control_min": min(r["control"][k] for r in rows)} for k in keys}
    out["separated"] = all(r["program_correct"] and not r["control_correct"] for r in rows)
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=15.0)
    args = ap.parse_args(argv)
    from bench import common

    cell = common.load_cell(args.workload)
    import jax

    why = common.check_device(jax, cell.chips)
    if why:
        print(f"calibrate: {why}", file=sys.stderr)
        return 2
    from bench import harness

    harness.enable_compile_cache()
    rows = calibrate(cell, [int(s) for s in args.seeds.split(",")], args.seconds)
    result = summary(rows)
    print(json.dumps({"summary": result}))
    return 0 if result["separated"] else 1


if __name__ == "__main__":
    sys.exit(main())
