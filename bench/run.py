"""Run one cell of the benchmark on the chip this process finds.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Prints progress and stamps on earlier lines, and as its last line one JSON
object: ``correct``, ``attempted``, ``failed``, ``metrics`` (the cell's
end-to-end metrics, or with ``--trace 1`` its per-layer metrics), ``device``,
with ``--trace 1`` a ``breakdown``, and last ``compared``: each number the
check compared, beside its limit.  Exits nonzero, with no result line,
where JAX finds no TPU or fewer chips than the cell asks for.

``--rehearse`` runs on the CPU at smoke widths (every width and token
count cut) and prints a line with no metric and no device: it exercises
the path, not the chip.  ``--rate`` offers a cell's traffic at another
rate, as a knee sweep does.
"""

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]


def parse(argv=None):
    ap = argparse.ArgumentParser(description="Run one benchmark cell.")
    ap.add_argument("--workload", required=True, help="a cell of BENCHMARK.json")
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True, help="length of the measured window")
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true", help="CPU run at smoke widths")
    ap.add_argument("--rate", type=float, help="requests/s, in place of the cell's")
    ap.add_argument("--trace-dir", help="keep the profiler trace here")
    return ap.parse_args(argv)


def result_line(res, cell_name, traced: bool, rehearse: bool) -> dict:
    from bench import spec

    compared = {c["name"]: {"value": c["value"], "limit": c["limit"]} for c in res.compared}
    if rehearse:
        return {"rehearsal": True, "correct": res.correct, "attempted": res.attempted,
                "failed": res.failed, "stamps": res.stamps, "compared": compared}
    kind = "per_layer" if traced else "end_to_end"
    metrics = {}
    for m in spec.metrics_of(cell_name, kind):
        if traced:
            value = spec.reader(m["name"])(res.window)
        else:
            value = res.e2e.get(m["name"])
        if value is None:
            # left out of the line, as the contract asks, but never in silence
            print(f"bench: metric {m['name']}: nothing to read in this run", file=sys.stderr,
                  flush=True)
        else:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    line = {"correct": res.correct, "attempted": res.attempted, "failed": res.failed,
            "metrics": metrics, "device": dict(res.device)}
    red = res.window.reduction
    if traced and red is not None:
        line["device"]["busy_s"] = red.busy_s
        line["device"]["window_s"] = red.window_s
        line["breakdown"] = {"device_ops": [list(x) for x in red.top_ops],
                             "idle_gaps": [list(x) for x in red.idle_gaps]}
    line["compared"] = compared
    return line


def main(argv=None) -> int:
    args = parse(argv)
    from bench import spec

    cell = spec.cell(args.workload)
    chips = cell["chips"]
    rate = args.rate or spec.rate(args.workload)
    cfg, mix, limits = (spec.config(cell["config"]), spec.traffic(cell["traffic"]),
                        spec.limits(cell["config"]))

    import jax

    devices = jax.devices()
    if not args.rehearse:
        if devices[0].platform != "tpu":
            print(f"bench: needs a TPU, JAX found {devices[0].platform}", file=sys.stderr)
            return 1
        if len(devices) < chips:
            print(f"bench: the cell asks for {chips} chips, JAX found {len(devices)}",
                  file=sys.stderr)
            return 1

    from bench import harness

    with tempfile.TemporaryDirectory(prefix="kvbench_trace_") as tmp:
        trace_dir = (args.trace_dir or tmp) if args.trace else None
        res = harness.run(cfg, mix, limits, args.seed, args.seconds, rate, T_PROCESS,
                          traced=bool(args.trace), rehearse=args.rehearse, trace_dir=trace_dir)
    print("stamps: " + json.dumps(res.stamps), flush=True)
    print("end-to-end: " + json.dumps(res.e2e), flush=True)
    line = result_line(res, args.workload, bool(args.trace), args.rehearse)
    for c in res.compared:  # the last lines on standard error
        print(f"compared {c['name']}: {c['value']!r} limit {c['limit']} "
              f"{'ok' if c['ok'] else 'FAIL'}", file=sys.stderr, flush=True)
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
