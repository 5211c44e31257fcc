"""Run one cell of the chip benchmark once.

    python bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

from the root of a checkout, on a machine with as many TPU chips as the
cell asks for.  Set-up (traffic from the seed, loading or compiling the
program, one warm call) is timed as ``setup_s``; then the cell's entry is
driven for ``--seconds``, what it produced is compared with the plain
reference, and the last line of standard output is the result as JSON.
``--trace 1`` profiles the first calls of the window and reports the
per-layer metrics instead of the end-to-end ones.  The numbers compared
and their limits are the last lines of standard error.

Exits 2 without a result when JAX finds no TPU, or fewer chips than the
cell needs.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    from bench import harness

    try:
        _, wl, _, _ = harness.cell(args.workload)
        devices = harness.chips_present(int(wl["chips"]))[:int(wl["chips"])]
    except harness.BenchError as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    harness.use_compile_cache()
    line, compared = harness.run_cell(
        args.workload, args.seed, args.seconds, bool(args.trace),
        t_start=T_START, devices=devices)
    for name, (value, limit) in compared.items():
        print(f"compared {name}: {value!r} (limit {limit!r})",
              file=sys.stderr)
    print(json.dumps(line))
    return 0


if __name__ == "__main__":
    sys.exit(main())
