"""Readings that the limits of ``correct`` are set from.

    python bench/readings.py --workload <name> --seeds 1,2,... \
        [--control-seeds 1,2,3] [--seconds 4]

For each seed, in one process: the cell's set-up, a short window at the
cell's own load (``--seconds``, at least one call per fleet), and the
numbers compared against the reference.  For each control seed, the same
numbers with the reference computed in bfloat16 put in the program's
place: the control, which every limit must reject.  The benchmark's own
runs never run this.  One JSON line per reading on standard output.
"""
import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", default="")
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--seconds", type=float, default=4.0)
    args = ap.parse_args(argv)
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    from bench import harness
    from bench.reference import PRECISIONS

    _, wl, config, mix = harness.cell(args.workload)
    try:
        devices = harness.chips_present(int(wl["chips"]))[:int(wl["chips"])]
    except harness.BenchError as e:
        print(f"readings: {e}", file=sys.stderr)
        return 2
    harness.use_compile_cache()
    seeds = [int(s) for s in args.seeds.split(",") if s]
    control = {int(s) for s in args.control_seeds.split(",") if s}
    for seed in seeds + sorted(control - set(seeds)):
        drv = harness.driver(mix["entry"])(config, mix, seed, devices)
        drv.setup()
        t0 = time.perf_counter()
        drv.window(args.seconds, harness.WindowTracer(None, 0))
        while getattr(drv, "calls", None) is not None and \
                drv.calls < len(drv.fleets):
            drv.call(drv.calls)
        took = time.perf_counter() - t0
        if seed in seeds:
            t1 = time.perf_counter()
            nums, bad = drv.numbers()
            print(json.dumps({"workload": args.workload, "seed": seed,
                              "kind": "program", "numbers": nums,
                              "bad": bad, "attempted": drv.attempted(),
                              "window_s": took,
                              "reference_s": time.perf_counter() - t1}),
                  flush=True)
        if seed in control:
            nums, bad = drv.numbers(control=PRECISIONS["bfloat16"])
            print(json.dumps({"workload": args.workload, "seed": seed,
                              "kind": "control_bfloat16", "numbers": nums,
                              "bad": bad, "attempted": drv.attempted()}),
                  flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
