"""Sustained-load serving benchmark (EXPERIMENTS.md §Perf-I).

Two phases over the full polybench program set (the paper's Fig. 6
workload, reused as the service's request mix):

* **cross-process warm start** — a child process compiles + first-calls
  every polybench block against an empty persistent store (cold), then
  a second fresh process does the same against the populated store
  (warm).  The warm process restores serialized AOT executables instead
  of re-planning and re-compiling; the ISSUE acceptance bar is >= 10x.
* **concurrent in-process load** — a :class:`repro.serving.CompileService`
  under N client threads x M sweeps of the program mix: throughput,
  warm-hit rate, and the single-flight guarantee (exactly one cold
  compile per structural key, racing clients coalesced).

Run directly (``PYTHONPATH=src python benchmarks/serving_load.py``) or
through ``benchmarks/run.py --sections serving`` (which subprocesses
it).  The committed ``benchmarks/BENCH_serving.json`` is the
``--sections serving --json`` payload.

Both phases run in children (``--child`` and ``--load``): this process
never imports JAX, so no parent holds a device a child needs.
"""
from __future__ import annotations

import json
import os
import subprocess
import sys
import tempfile
import threading
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def _cpu_jax():
    import jax

    jax.config.update("jax_platforms", "cpu")
    return jax


# ---------------------------------------------------------------------------
# child mode: compile + first-call every polybench block in THIS process
# ---------------------------------------------------------------------------


def _child(cache_dir: str) -> None:
    # REPRO_AOT_CACHE_DIR was set by the parent before we imported repro,
    # so the persistent store is already enabled.
    jax = _cpu_jax()
    from benchmarks.polybench import ALL_KERNELS
    from repro import omp
    from repro.compat import make_mesh

    mesh = make_mesh((len(jax.devices()),), ("data",))
    total_s = 0.0
    n = restored = 0
    # time compile + first call per program; program/env construction is
    # identical in both processes and stays outside the clock
    for make in ALL_KERNELS:
        k = make()
        env = k.env_fn(k.n)
        for prog in k.programs:
            t0 = time.perf_counter()
            c = omp.compile(prog, mesh, env_like=env)
            env = c(env)          # first call: build (or restore) + run
            total_s += time.perf_counter() - t0
            n += 1
            restored += int(c.restored)
    stats = omp.compile_cache_stats()
    print(json.dumps({"programs": n, "restored": restored,
                      "total_s": total_s,
                      "disk_hits": stats["disk_hits"],
                      "disk_misses": stats["disk_misses"],
                      "disk_errors": stats["disk_errors"]}))


def _run_child(*args: str, env_extra: dict | None = None) -> str:
    env = dict(os.environ, **(env_extra or {}))
    env["PYTHONPATH"] = os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "src")
    proc = subprocess.run(
        [sys.executable, os.path.abspath(__file__), *args],
        capture_output=True, text=True, env=env, timeout=540)
    if proc.returncode != 0:
        raise RuntimeError(f"child failed: {proc.stderr[-400:]}")
    return proc.stdout


def bench_cross_process() -> None:
    with tempfile.TemporaryDirectory(prefix="repro-aot-bench-") as d:
        cold, warm = (json.loads(_run_child(
            "--child", d, env_extra={"REPRO_AOT_CACHE_DIR": d})
            .strip().splitlines()[-1]) for _ in range(2))
    n = cold["programs"]
    speedup = cold["total_s"] / max(warm["total_s"], 1e-9)
    print(f"serving_cold_process,{cold['total_s'] * 1e6 / n:.1f},"
          f"programs={n};disk_hits={cold['disk_hits']}", flush=True)
    print(f"serving_warm_process,{warm['total_s'] * 1e6 / n:.1f},"
          f"speedup={speedup:.1f};restored={warm['restored']};"
          f"disk_hits={warm['disk_hits']};"
          f"disk_errors={warm['disk_errors']}", flush=True)
    assert warm["restored"] == n, (
        f"warm process restored {warm['restored']}/{n} executables")
    assert speedup >= 10.0, (
        f"cross-process warm start only {speedup:.1f}x (bar: 10x)")


# ---------------------------------------------------------------------------
# concurrent in-process load over CompileService
# ---------------------------------------------------------------------------


def bench_concurrent_load(n_threads: int = 8, sweeps: int = 3) -> None:
    jax = _cpu_jax()
    from benchmarks.polybench import ALL_KERNELS
    from repro import omp
    from repro.compat import make_mesh
    from repro.serving import CompileService

    omp.clear_compile_cache()
    # request mix: every polybench block, each with the env shapes it
    # sees in sequence (later blocks read earlier blocks' outputs)
    pairs = []
    for make in ALL_KERNELS:
        k = make()
        env = k.env_fn(k.n)
        for prog in k.programs:
            pairs.append((prog, dict(env)))
            env = prog(env)

    svc = CompileService(make_mesh((len(jax.devices()),), ("data",)))
    errors: list = []
    barrier = threading.Barrier(n_threads + 1)

    def client():
        try:
            barrier.wait()
            for _ in range(sweeps):
                for prog, env in pairs:
                    svc.run(prog, env)
        except Exception as e:          # pragma: no cover - failure path
            errors.append(e)

    threads = [threading.Thread(target=client) for _ in range(n_threads)]
    for t in threads:
        t.start()
    barrier.wait()
    t0 = time.perf_counter()
    for t in threads:
        t.join()
    wall = time.perf_counter() - t0
    assert not errors, errors
    s = svc.stats
    total = n_threads * sweeps * len(pairs)
    assert s.requests == total
    assert s.cold_compiles == len(pairs), (
        f"single-flight violated: {s.cold_compiles} cold compiles for "
        f"{len(pairs)} structural keys")
    print(f"serving_load_request,{wall * 1e6 / total:.1f},"
          f"throughput_rps={total / wall:.0f};clients={n_threads};"
          f"requests={total};cold_compiles={s.cold_compiles};"
          f"warm_hits={s.warm_hits};coalesced={s.coalesced}", flush=True)


def main() -> None:
    if len(sys.argv) > 1 and sys.argv[1] == "--child":
        _child(sys.argv[2])
        return
    if len(sys.argv) > 1 and sys.argv[1] == "--load":
        bench_concurrent_load()
        return
    print("name,us_per_call,derived")
    bench_cross_process()
    print(_run_child("--load"), end="", flush=True)


if __name__ == "__main__":
    main()
