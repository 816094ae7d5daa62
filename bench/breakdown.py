#!/usr/bin/env python3
"""Where one cell's set-up and device time go, by the program's layers.

    python3 bench/breakdown.py --workload <cell> --seed <n> [--seconds <s>]
                               [--calls <n>] [--save <dir>]

Sets the cell up as ``run.py`` does, timing each phase, drives an
untraced window of ``--seconds`` (default 2) and then a traced one
(``harness.TRACE_SECONDS``, or exactly ``--calls`` calls), and prints
one JSON object:

* ``setup``: ``[phase, seconds]`` from process start to the end of the
  warm-up, summing to ``setup_s``: ``runtime_init`` (process start to
  the return of the first ``jax.devices()``), ``import repro``, each
  ``omp.pass.<name>``,
  the rest of ``omp.compile``, ``executor_trace`` (the program's Python
  trace), ``xla_compile`` (compile or cache load, less the trace),
  ``data`` (``make_env``), ``warmup`` and ``remainder``;
* ``scopes``: seconds per ``omp.`` scope over the traced window, per
  chip, the top ten (``bench/scopes.py``), and ``no_scope_share`` of
  the busy time;
* ``idle_gap_host``: for the first chip's longest idle gaps, the
  runtime host event that overlaps each most;
* ``metrics``: the per-layer metrics the scopes and timers give
  (``stage_ms``, ``layout_ms``, ``exchange_ms``, ``trace_s``,
  ``runtime_init_s``), ``busy_ms_per_call``, and ``step_ms`` of both
  windows (the profiler's cost).

``--save <dir>`` keeps the trace (``<cell>.xplane.pb``) and the scope
map of the ops in it (``<cell>.scopes.json``).  No correctness check is
made: ``run.py`` does that.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

_HERE = os.path.dirname(os.path.abspath(__file__))
sys.path[:] = [p for p in sys.path if os.path.abspath(p or ".") != _HERE]
sys.path.insert(0, os.path.dirname(_HERE))

from bench import harness, scopes  # noqa: E402
from bench import trace as trace_mod  # noqa: E402


def setup_phases(t_start, t_devices, import_s, timing, setup, data_s,
                 warm_s, setup_s) -> list:
    """``[phase, seconds]`` of set-up, summing to ``setup_s``."""
    passes = sorted(timing["pass_seconds"].items(), key=lambda kv: -kv[1])
    trace_s = timing["executor_seconds"]
    phases = [["runtime_init", t_devices - t_start],
              ["import repro", import_s]]
    phases += [[f"omp.pass.{name}", s] for name, s in passes]
    phases += [["omp.compile other", setup.plan_s - sum(s for _n, s in passes)],
               ["executor_trace", trace_s],
               ["xla_compile", setup.xla_compile_s - trace_s],
               ["data", data_s], ["warmup", warm_s]]
    phases.append(["remainder", setup_s - sum(s for _n, s in phases)])
    return phases


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, required=True)
    parser.add_argument("--seconds", type=float, default=2.0)
    parser.add_argument("--calls", type=int, default=0)
    parser.add_argument("--save", default=None)
    args = parser.parse_args(argv)

    harness.set_cache_env()
    import jax
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    cell = harness.load_cell(args.workload)
    try:
        devices = harness.cell_devices(cell)
    except harness.NoChip as e:
        print(f"{e}; no result", file=sys.stderr)
        return 2
    t_devices = time.perf_counter()
    from repro import omp
    t_import = time.perf_counter()
    setup = harness.build(cell, devices)
    # a program without the timers: its passes and trace stay inside
    # ``omp.compile other`` and ``xla_compile``
    timing = (omp.timing_stats() if hasattr(omp, "timing_stats") else
              {"pass_seconds": {}, "executor_runs": 0,
               "executor_seconds": 0.0})
    t0 = time.perf_counter()
    env = jax.block_until_ready(setup.make_env(harness.seed_key(args.seed)))
    t1 = time.perf_counter()
    warm = harness.drive(setup.call, env, cell.traffic, 0.0, keep=-1,
                         min_calls=cell.traffic["warmup_calls"])
    if cell.traffic["feedback"]:
        env = {**env, **warm.kept[-1][1]}
    del warm
    t2 = time.perf_counter()
    setup_s = t2 - T_START

    plain = harness.drive(setup.call, env, cell.traffic, args.seconds, -1)
    if cell.traffic["feedback"]:
        env = {**env, **plain.kept[-1][1]}
    trace_dir = tempfile.mkdtemp(prefix="bench-breakdown-")
    try:
        jax.profiler.start_trace(trace_dir)
        if args.calls:
            window = harness.drive(setup.call, env, cell.traffic, 0.0, -1,
                                   min_calls=args.calls)
        else:
            window = harness.drive(setup.call, env, cell.traffic,
                                   harness.TRACE_SECONDS, -1)
        jax.profiler.stop_trace()
        trace = trace_mod.load(trace_dir)
        host = scopes.load_runtime(trace_dir, trace["devices"])
        if args.save:
            os.makedirs(args.save, exist_ok=True)
            (path,) = [os.path.join(d, f) for d, _s, fs in os.walk(trace_dir)
                       for f in fs if f.endswith(".xplane.pb")]
            shutil.copy(path, os.path.join(args.save,
                                           f"{cell.name}.xplane.pb"))
    finally:
        shutil.rmtree(trace_dir, ignore_errors=True)

    used = [d.id for d in setup.mesh.devices.flat]
    summary = trace_mod.summarize(trace, used, window.seconds)
    smap = scopes.scope_map(setup.call.as_text())
    scope_s = scopes.scope_seconds(summary.op_s, smap)
    if args.save:
        traced = {n for ops in trace["devices"].values() for n, _s, _d in ops}
        with open(os.path.join(args.save, f"{cell.name}.scopes.json"),
                  "w") as f:
            json.dump({n: s for n, s in sorted(smap.items())
                       if n in traced}, f, indent=0)

    def per_call_ms(prefixes):
        return 1e3 * scopes.seconds_under(scope_s, prefixes) / window.calls

    busy = sum(scope_s.values())         # per chip, over the window
    out = {
        "workload": cell.name,
        "device": {"kind": devices[0].device_kind, "count": len(used)},
        "setup_s": setup_s,
        "setup": setup_phases(T_START, t_devices, t_import - t_devices,
                              timing, setup, t1 - t0, t2 - t1, setup_s),
        "metrics": {
            "stage_ms": per_call_ms(scopes.STAGE),
            "layout_ms": per_call_ms(scopes.LAYOUT),
            "exchange_ms": per_call_ms(scopes.EXCHANGE),
            "trace_s": timing["executor_seconds"],
            "runtime_init_s": t_devices - T_START,
            "xla_compile_s": setup.xla_compile_s,
            "busy_ms_per_call": 1e3 * summary.busy_s / window.calls,
            "scopes_ms_per_call": 1e3 * busy / window.calls,
            "step_ms_untraced": 1e3 * plain.seconds / plain.calls,
            "step_ms_traced": 1e3 * window.seconds / window.calls,
            "calls_traced": window.calls,
        },
        "scopes": scopes.top(scope_s),
        "scope_ms_per_call": {k: 1e3 * v / window.calls
                              for k, v in sorted(scope_s.items())},
        "no_scope_share": (scope_s.get(scopes.NO_SCOPE, 0.0) / busy
                           if busy else None),
        "idle_gap_host": scopes.idle_gap_host(trace, host["runtime"],
                                              used[0]),
        "idle_gaps": summary.gaps,
        "omp_host_spans": len(host["omp"]),
    }
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
