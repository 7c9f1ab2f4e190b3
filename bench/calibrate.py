"""Readings that the limits of ``correct`` are set from, in one process.

    python3 bench/calibrate.py --workload <cell> --seeds 11 12 ... \\
        --control-seeds 3 --seconds 10 [--out FILE]

For each seed, one run of the cell at its own load with a short window,
and the numbers its check compares.  For the first ``--control-seeds``
seeds the control too: the reference computed with float8 linear layers
put in the program's place (its family's ``forward``, ``bench/families/``)
and judged by the same check, which has to read it not correct.  Prints
one JSON line per seed (and appends it to ``--out``).  A limit lies above
the largest program reading and below the smallest control reading.
The benchmark's own runs never run the control.
"""

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description="Program and control readings over seeds.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--control-seeds", type=int, default=3)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--out", help="append the JSON lines to this file")
    args = ap.parse_args(argv)

    from bench import harness, spec

    cell = spec.cell(args.workload)
    cfg, mix = spec.config(cell["config"]), spec.traffic(cell["traffic"])
    limits = spec.limits(cell["config"])
    import jax

    if jax.devices()[0].platform != "tpu":
        print("calibrate: needs a TPU", file=sys.stderr)
        return 1
    for i, seed in enumerate(args.seeds):
        res = harness.run(cfg, mix, limits, seed, args.seconds, spec.rate(args.workload),
                          time.perf_counter(), control=i < args.control_seeds)
        control = {c["name"]: c["value"] for c in res.control_compared}
        line = json.dumps({"workload": args.workload, "seed": seed, "correct": res.correct,
                           "numbers": res.numbers, "control_correct": res.control_correct,
                           "control": control, "e2e": res.e2e, "stamps": res.stamps})
        print(line, flush=True)
        if args.out:
            with open(args.out, "a") as f:
                f.write(line + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
