"""Knee sweep: a cell's traffic at fixed rates, one window each.

    python3 bench/knee.py --workload <name> --rates 1.0 --fractions 0.8,1.0 \\
        --seconds 150 --seed 5

Builds the cell once and serves each rate on a fresh engine with the same
weights.  For each rate it prints the window's numbers and the queue at the
window's open and close; the knee is the highest rate whose queue does not
grow across the window.  The first rate may be one far above the knee: its
window then keeps every slot busy, and the tokens it completes per second,
over the mix's mean answer, give the capacity in requests per second.
``--fractions`` then serves those fractions of that capacity.  The chosen
rate is written into the traffic file by hand, as a number: the benchmark
never searches for one.
"""

import argparse
import json
import sys
import time
from pathlib import Path

import numpy as np

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def mean_answer(traffic: dict, seconds: float) -> float:
    """Mean answer length of the requests a run at ``traffic`` offers."""
    from bench.traffic import generators

    n = generators.arrivals_for(traffic, seconds)
    return float(np.mean(generators.lognormal_quantiles(n, traffic["output"])))


def window(cell, model, params, engine, rate, seconds, seed, out):
    from repro.serve import Engine, EngineConfig

    from bench import harness

    sizing = harness.engine_config(cell.config, cell.traffic)
    engine = engine or Engine(model, params, EngineConfig(**sizing))
    harness.warm_up(engine)
    traffic = {**cell.traffic, "rate_per_s": rate}
    driver = harness.Driver(engine, traffic, seed, cell.config["vocab_size"], seconds)
    driver.open_at(time.monotonic() + float(traffic["preroll_s"]))
    driver.run(driver.t_open)
    q_open = len(engine.sched.queue)
    t1 = driver.run(driver.t_open + seconds)
    stats = harness.window_stats(driver.recs, driver.t_open, t1)
    row = {"rate_per_s": rate, "queued_open": q_open,
           "queued_close": len(engine.sched.queue),
           "capacity_req_s": stats["output_tok_s"] / mean_answer(traffic, seconds), **stats}
    out(json.dumps(row))
    return row


def sweep(cell, rates, seconds: float, seed: int, fractions=(), out=print) -> list[dict]:
    from bench import harness

    sizing = harness.engine_config(cell.config, cell.traffic)
    model, params, engine = harness.build(cell.config, seed, sizing)
    rows = []
    for rate in rates:
        rows.append(window(cell, model, params, engine, rate, seconds, seed, out))
        engine = None
    for f in fractions:
        rate = round(f * rows[0]["capacity_req_s"], 4)
        rows.append(window(cell, model, params, None, rate, seconds, seed, out))
    return rows


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--fractions", default="",
                    help="fractions of the capacity the first rate's window measured")
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--seed", type=int, default=5)
    args = ap.parse_args(argv)
    from bench import common

    cell = common.load_cell(args.workload)
    import jax

    why = common.check_device(jax, cell.chips)
    if why:
        print(f"knee: {why}", file=sys.stderr)
        return 2
    from bench import harness

    harness.enable_compile_cache()
    sweep(cell, [float(r) for r in args.rates.split(",")], args.seconds, args.seed,
          [float(f) for f in args.fractions.split(",") if f])
    return 0


if __name__ == "__main__":
    sys.exit(main())
