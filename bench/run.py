#!/usr/bin/env python3
"""Run one benchmark cell on the chips of this machine.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell is an entry of ``workloads`` in ``BENCHMARK.json``.  Set-up
plans the program with ``omp.compile``, makes the data on the device
from ``--seed``, compiles one ``jax.jit`` of the call (from JAX's
compile cache in ``.jax_cache/`` after the first run) and warms it up.
The window then drives the call as the cell's traffic mix says for
``--seconds`` seconds (``--trace 1``: a shorter window under the
profiler).  Afterwards the calls kept from the window are compared with
the plain reference on their own inputs.

The last line of standard output is one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics`` (the cell's end-to-end metrics,
or with ``--trace 1`` its per-layer ones), ``device``, with ``--trace 1``
``breakdown``, and last ``checks``: each number compared, with its
limit, which also end standard error.  Without a TPU, or with fewer
chips than the cell needs, it exits 2 and prints no result.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

# Run as a script, this directory is sys.path[0]; take the repository
# root instead, so that ``bench`` is a package and shadows nothing.
_HERE = os.path.dirname(os.path.abspath(__file__))
sys.path[:] = [p for p in sys.path if os.path.abspath(p or ".") != _HERE]
sys.path.insert(0, os.path.dirname(_HERE))

from bench import harness  # noqa: E402


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, required=True)
    parser.add_argument("--seconds", type=float, required=True)
    parser.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = parser.parse_args(argv)

    harness.set_cache_env()
    import jax
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    try:
        result, info = harness.measure(args.workload, args.seed, args.seconds,
                                 bool(args.trace), t_start=T_START)
    except harness.NoChip as e:
        print(f"{e}; no result", file=sys.stderr)
        return 2
    print(json.dumps(info), file=sys.stderr)
    for name, c in result["checks"].items():
        print(f"check {name} {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
