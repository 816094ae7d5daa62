"""Benchmark harness. One section per paper table/figure; prints
``name,us_per_call,derived`` CSV rows, and with ``--json out.json``
also writes the machine-readable result set (wall time per benchmark
plus any wire-byte counters parsed out of ``derived``) so the perf
trajectory can be recorded run over run.

Sections:
* polybench_* (paper Fig. 6): seq vs OpenMP-analogue vs OMP2MPI-generated
  execution; ``derived`` is the projected 64-rank speed-up from the
  plan's compute/communication split (this container has one real CPU
  device, so cluster scaling cannot be wall-clocked — the projection is
  the Fig. 6 analogue; real distributed numbers come from the dry-run).
* region_* / stencil_halo_* / heat2d_*: fused-region and halo-vs-gather
  comparisons (8 virtual devices in subprocesses; HLO-measured bytes).
* compile_cache_*: cold vs warm ``omp.compile`` (the structural
  compilation cache); the ``--json`` payload carries the totals in its
  ``compile_cache`` section.
* serving_*: the compile-and-serve service (EXPERIMENTS §Perf-I) —
  cross-process warm start off the persistent AOT store (cold vs
  restored) and concurrent client load over CompileService; the
  committed benchmarks/BENCH_serving.json is this section's --json
  payload.
* resilience_*: fault-tolerant runtime (EXPERIMENTS §Perf-J) —
  injection/retry overheads and cold-vs-warm degraded-mesh recovery;
  the committed benchmarks/BENCH_resilience.json is this section's
  --json payload.
* kernels_*: Pallas interpret-mode kernels vs jnp oracles.
* train_step_* / decode_step_*: smoke-size LM steps (end-to-end
  substrate sanity + µs tracking).

This process only orchestrates: it never imports JAX, and runs every
section in a child of its own (``benchmarks/sections.py`` for the
in-process sections, the section's script for the others), so each
child owns its devices.  A section whose child fails is reported as a
``failed:`` row and makes the run exit non-zero.  Every child shares
the persistent compilation cache of :mod:`repro.jax_cache`.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, os.path.join(ROOT, "src"))

from repro import jax_cache  # noqa: E402  (imports no JAX)

# Every relayed row lands here; ``--json`` serialises it at exit.
RESULTS: list[dict] = []


def _parse_derived(derived: str) -> dict:
    """Split ``k=v;k=v`` derived strings into typed fields (ints/floats
    where they parse; wire-byte counters become machine-readable)."""
    fields: dict = {}
    for part in derived.split(";"):
        if "=" not in part:
            continue
        k, v = part.split("=", 1)
        try:
            fields[k] = int(v)
        except ValueError:
            try:
                fields[k] = float(v)
            except ValueError:
                fields[k] = v
    return fields


def _row(name, us, derived=""):
    print(f"{name},{us:.1f},{derived}", flush=True)
    RESULTS.append({
        "name": name,
        "us_per_call": round(float(us), 1),
        "derived": derived,
        **_parse_derived(derived),
    })


# section -> (argv after the interpreter, relayed row prefixes).  The
# multi-device scripts force their own 8 virtual devices.
SECTIONS = {
    "polybench": (["sections.py", "polybench"], ("polybench_",)),
    "region": (["region_chains.py"], ("region_",)),
    "stencil_halo": (["stencil_halo.py"],
                     ("stencil_halo_", "stencil_multifield_")),
    "heat2d": (["heat2d.py"], ("heat2d_",)),
    "roofline": (["roofline.py"], ("roofline_",)),
    "compile_cache": (["sections.py", "compile_cache"],
                      ("compile_cache_",)),
    "serving": (["serving_load.py"], ("serving_",)),
    "resilience": (["resilience.py"], ("resilience_",)),
    "kernels": (["sections.py", "kernels"], ("kernels_",)),
    "lm": (["sections.py", "lm"], ("loss_", "decode_")),
}


def run_section(name: str, meta: dict) -> bool:
    """Run one section in a child and relay its rows; ``#`` lines fill
    ``meta``.  Returns whether the child succeeded."""
    argv, prefixes = SECTIONS[name]
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(ROOT, "src")
    env.pop("XLA_FLAGS", None)      # scripts force their own device count
    try:
        proc = subprocess.run(
            [sys.executable, os.path.join(HERE, argv[0])] + argv[1:],
            capture_output=True, text=True, env=env, timeout=1800)
    except subprocess.TimeoutExpired:
        _row(name, 0.0, "failed:timeout")
        return False
    for line in proc.stdout.splitlines():
        if line.startswith("#device_count "):
            meta["device_count"] = int(line.split()[1])
        elif line.startswith("#compile_cache "):
            meta["compile_cache"] = json.loads(line.split(" ", 1)[1])
        elif line.startswith(prefixes):
            row, us, derived = line.split(",", 2)
            _row(row, float(us), derived)
    if proc.returncode != 0:
        _row(name, 0.0, f"failed:{proc.stderr[-200:]!r}")
        return False
    return True


def main(argv=None) -> int:
    import argparse

    parser = argparse.ArgumentParser(description=__doc__)
    parser.add_argument(
        "--json", metavar="PATH", default=None,
        help="also write the results as machine-readable JSON "
             "(wall time + wire-byte counters per benchmark)")
    parser.add_argument(
        "--sections", default=None,
        help="comma-separated subset of sections to run "
             f"({','.join(SECTIONS)})")
    args = parser.parse_args(argv)

    wanted = (args.sections.split(",") if args.sections
              else list(SECTIONS))
    unknown = [s for s in wanted if s not in SECTIONS]
    if unknown:
        parser.error(f"unknown sections {unknown}; pick from "
                     f"{sorted(SECTIONS)}")

    jax_cache.enable()
    print("name,us_per_call,derived")
    meta: dict = {}
    failed = [name for name in wanted if not run_section(name, meta)]

    if args.json:
        payload = {
            "schema": "repro-bench-v1",
            "device_count": meta.get("device_count"),
            "sections": wanted,
            "results": RESULTS,
        }
        if "compile_cache" in meta:  # only when the section ran
            payload["compile_cache"] = meta["compile_cache"]
        # The communication snapshot: every row that carries collective
        # ops / wire-byte / launch counters, so the perf trajectory of
        # the comm planner + scheduler is recorded run over run (the
        # committed benchmarks/BENCH_comm.json is this section from
        # `--sections stencil_halo,heat2d`; CI regenerates and uploads
        # it as an artifact).
        comm_rows = [r for r in RESULTS
                     if any(k in r for k in (
                         "collective_ops", "wire_bytes", "modeled_wire",
                         "launches_scheduled", "op_ratio", "ratio"))]
        if comm_rows:
            payload["comm"] = comm_rows
        # The serving snapshot: cross-process warm start + concurrent
        # load rows (the committed benchmarks/BENCH_serving.json is
        # this section from `--sections serving`).
        serving_rows = [r for r in RESULTS
                        if r["name"].startswith("serving_")]
        if serving_rows:
            payload["serving"] = serving_rows
        # The resilience snapshot: fault-injection overheads + cold/warm
        # degraded-mesh recovery (the committed
        # benchmarks/BENCH_resilience.json is this section from
        # `--sections resilience`).
        resilience_rows = [r for r in RESULTS
                           if r["name"].startswith("resilience_")]
        if resilience_rows:
            payload["resilience"] = resilience_rows
        with open(args.json, "w", encoding="utf-8") as fh:
            json.dump(payload, fh, indent=2)
        print(f"# wrote {len(RESULTS)} results to {args.json}", flush=True)
    if failed:
        print(f"# failed sections: {','.join(failed)}", file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
