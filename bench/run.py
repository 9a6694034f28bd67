"""Run one cell of the chip benchmark.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Builds the cell's configuration from ``bench/configs``, draws its weights and
traffic from ``--seed``, warms up the two step shapes, pre-rolls the traffic
to steady state, measures for ``--seconds`` (or, with ``--trace 1``, traces a
few seconds of it), re-scores a sample of the served tokens with the plain
reference, and prints one JSON object as the last line of stdout.  It runs
only on a TPU with the chips the cell asks for, and exits non-zero with no
result otherwise.
"""

import time

T_PROCESS = time.monotonic()

import argparse  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    from bench import common

    cell = common.load_cell(args.workload)
    import jax

    why = common.check_device(jax, cell.chips)
    if why:
        print(f"bench: {why}", file=sys.stderr)
        return 2
    from bench import harness

    print(f"compile cache: {harness.enable_compile_cache()}", flush=True)
    result, check = harness.run_cell(cell, args.seed, args.seconds, bool(args.trace),
                                     T_PROCESS)
    harness.report(result, check)
    return 0


if __name__ == "__main__":
    sys.exit(main())
