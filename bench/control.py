#!/usr/bin/env python3
"""Readings that set a cell's limit: the program's and the control's.

    python bench/control.py --workload <cell> --seeds 1,2,3 [--seconds 1]

One process sets the cell up once (as ``run.py`` does), then for each
seed makes fresh data, drives the cell's traffic for ``--seconds``, and
reads ``max_rel_err`` of the kept calls twice: the compiled program's
against the reference (the lower reading), and the control's, the
reference computed one precision lower, on the same inputs (the upper
reading).  Prints one JSON line per seed and a last line with the
largest program reading and the smallest control reading.  The
benchmark's own runs never run this.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

_HERE = os.path.dirname(os.path.abspath(__file__))
sys.path[:] = [p for p in sys.path if os.path.abspath(p or ".") != _HERE]
sys.path.insert(0, os.path.dirname(_HERE))

from bench import harness  # noqa: E402


def readings(workload, seeds, seconds, *, require_tpu=True, sizes=None):
    """Yield ``{"seed", "program", "control"}`` for each seed."""
    cell = harness.load_cell(workload, sizes)
    setup = harness.build(cell, harness.cell_devices(cell, require_tpu))
    for seed in seeds:
        env = setup.make_env(harness.seed_key(seed))
        window = harness.drive(setup.call, env, cell.traffic, seconds, keep=0)
        yield {"seed": seed, "calls": window.calls,
               "program": max(harness.check_calls(cell, window.kept)),
               "control": min(harness.check_calls(
                   cell, window.kept, cell.reference.control))}


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seeds", required=True,
                        help="comma-separated whole numbers")
    parser.add_argument("--seconds", type=float, default=1.0)
    args = parser.parse_args(argv)

    harness.set_cache_env()
    seeds = [int(s) for s in args.seeds.split(",")]
    rows = []
    try:
        for row in readings(args.workload, seeds, args.seconds):
            rows.append(row)
            print(json.dumps(row), flush=True)
    except harness.NoChip as e:
        print(f"{e}; no readings", file=sys.stderr)
        return 2
    print(json.dumps({
        "workload": args.workload, "seeds": len(rows),
        "limit": harness.load_cell(args.workload).cfg["limits"],
        "program_max": max(r["program"] for r in rows),
        "control_min": min(r["control"] for r in rows),
        "seconds": time.perf_counter() - T_START}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
