#!/usr/bin/env python3
"""Chip smoke test: ``omp.compile(program, mesh)(env)`` end to end on TPU.

One process drives the compiler's public facade (``repro.omp``) at
PolyBench/C 4.2 EXTRALARGE sizes and checks every result against the
shared-memory reference (``prog(env)``, i.e. ``run_reference``) computed
on the same devices from the same inputs.  Inputs come from a fixed seed.

Phases (one chip, no arguments):

* ``stencil`` -- jacobi-2d, N = 2800: one fused ``omp.region`` of four
  ping-pong ``collapse(2)`` 5-point sweeps on a 1x1 ``("i", "j")`` mesh,
  default ``Options``.  PolyBench runs TSTEPS = 1000 time steps; the
  compiler has no time-step loop yet, so the region holds four sweeps.
* ``gemm`` -- NI = 2000, NJ = 2300, NK = 2600, one ``parallel_for`` over
  the rows of C (``C[i] = alpha * A[i] @ B + beta * C[i]``) on a ``(1,)``
  ``"data"`` mesh.
* ``pallas`` -- the stencil region and an elementwise map over N * N
  points with ``lowering="pallas"``; every span must run compiled
  (``tpu_custom_call`` in the HLO).  gemm runs there too if its span
  compiles; otherwise ``omp.compile`` must raise ``CompileError``.

``--four-chips`` runs only the stencil on a 2x2 mesh (halos travel as
row and column ``ppermute`` rings) and gemm on a ``(4,)`` mesh, with
their references on the same four devices, and checks that all four did
work.  ``--tiny`` shrinks every size for a rehearsal on the CPU
(``JAX_PLATFORMS=cpu``; add
``XLA_FLAGS=--xla_force_host_platform_device_count=4`` for
``--four-chips``).

Each phase prints its compile seconds, the seconds of one warm call
(ended by ``block_until_ready``), its max error against its limit, the
device kind and ``peak_bytes_in_use``.  Only a full-size run on TPU in
which every phase passed ends with the line
``{"ok": true, "device": {...}}``; any failure exits non-zero, and
without a TPU the script exits non-zero before running anything.

    python chip_smoke.py [--four-chips] [--tiny]
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time
import traceback

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

from repro import jax_cache  # noqa: E402

CACHE_DIR = jax_cache.enable()
os.environ.setdefault("TPU_LOG_DIR", "disabled")  # else libtpu logs in /tmp

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
from jax.sharding import Mesh, NamedSharding, PartitionSpec  # noqa: E402

from repro import omp  # noqa: E402

SEED = 0
FULL = {"n": 2800, "ni": 2000, "nj": 2300, "nk": 2600}
TINY = {"n": 40, "ni": 24, "nj": 20, "nk": 28}
SWEEPS = 4
# Stencil and map: the same f32 operations in the same order on both
# sides, so only a few ulp of rounding may differ.
ELEMENTWISE_LIMIT = 1e-5
# gemm: on TPU an f32 matmul at the default precision multiplies in
# bf16 (one MXU pass) and accumulates in f32.  The compiled program and
# the reference split the dot differently (row chunks, or a Pallas
# kernel whose precision Mosaic picks), so they may round at different
# points: the limit is bf16-level, relative to the largest |C|.
GEMM_REL_LIMIT = 2e-2

# JAX counts a miss only when it writes an entry, i.e. for a compile that
# took over ``jax_persistent_cache_min_compile_time_secs``.
CACHE_EVENTS = {
    "/jax/compilation_cache/compile_requests_use_cache": "requests",
    "/jax/compilation_cache/cache_hits": "hits",
    "/jax/compilation_cache/cache_misses": "misses"}
cache_stats = {"requests": 0, "hits": 0, "misses": 0}


def _count_cache_event(event, **_kw):
    if event in CACHE_EVENTS:
        cache_stats[CACHE_EVENTS[event]] += 1


def jacobi2d(n):
    """Four ping-pong 5-point sweeps over the interior, a -> b -> a..."""
    def sweep(src, dst, name):
        @omp.parallel_for(start=(1, 1), stop=(n - 1, n - 1), collapse=2,
                          name=name)
        def body(i, j, env):
            a = env[src]
            v = 0.2 * (a[i, j] + a[i - 1, j] + a[i + 1, j]
                       + a[i, j - 1] + a[i, j + 1])
            return {dst: omp.at((i, j), v)}
        return body

    pairs = [("a", "b"), ("b", "a")] * (SWEEPS // 2)
    return omp.region(*(sweep(s, d, f"sweep{k + 1}")
                        for k, (s, d) in enumerate(pairs)), name="jacobi2d")


def gemm(ni):
    @omp.parallel_for(stop=ni, name="gemm")
    def body(i, env):
        return {"C": omp.at(i, 1.5 * (env["A"][i] @ env["B"])
                            + 1.2 * env["C"][i])}
    return body


def elementwise_map(n):
    @omp.parallel_for(stop=n, name="map")
    def body(i, env):
        x = env["x"][i]
        return {"y": omp.at(i, 4.0 / (1.0 + x * x))}
    return body


def make_env(shapes, sharding):
    keys = jax.random.split(jax.random.key(SEED), len(shapes))
    return {name: jax.device_put(jax.random.uniform(k, shape, jnp.float32,
                                                    -1.0, 1.0), sharding)
            for k, (name, shape) in zip(keys, sorted(shapes.items()))}


def max_error(got, ref, keys, relative=False):
    err = 0.0
    for k in keys:
        g, r = np.asarray(got[k]), np.asarray(ref[k])
        e = float(np.max(np.abs(g - r)))
        if relative:
            e /= float(np.max(np.abs(r)))
        err = max(err, e)
    return err


def run_phase(name, prog, mesh, env, keys, limit, *, relative=False,
              kernel=False, collective=None, **options):
    """Compile, run once cold and once warm, compare with the reference
    on the same devices; returns the printed record.  ``collective``
    names an HLO op the compiled program must contain."""
    t0 = time.perf_counter()
    compiled = omp.compile(prog, mesh, env_like=env, **options)
    jitted = jax.jit(lambda e: compiled(e)).lower(env).compile()
    compile_s = time.perf_counter() - t0
    hlo = jitted.as_text()
    out = jax.block_until_ready(jitted(env))
    t0 = time.perf_counter()
    out = jax.block_until_ready(jitted(env))
    warm_s = time.perf_counter() - t0
    ref = jax.block_until_ready(jax.jit(lambda e: prog(e))(env))
    err = max_error(out, ref, keys, relative)
    devices = list(mesh.devices.flat)
    rec = {
        "phase": name, "compile_s": compile_s, "warm_s": warm_s,
        "max_err": err, "limit": limit,
        "err_kind": "relative to max|ref|" if relative else "absolute",
        "device_kind": devices[0].device_kind,
        "peak_bytes_in_use": [d.memory_stats().get("peak_bytes_in_use")
                              if d.memory_stats() else None
                              for d in devices],
    }
    if len(devices) > 1:
        rec["bytes_in_use"] = [d.memory_stats().get("bytes_in_use")
                               if d.memory_stats() else None
                               for d in devices]
        rec["out_devices"] = min(len(out[k].sharding.device_set)
                                 for k in keys)
        rec["collectives"] = sorted(
            op for op in ("collective-permute", "all-gather", "all-reduce",
                          "all-to-all", "reduce-scatter") if op in hlo)
    if kernel:
        rec["tpu_custom_call"] = "tpu_custom_call" in hlo
    print(json.dumps(rec), flush=True)
    assert np.isfinite(err) and err <= limit, \
        f"{name}: max error {err} above {limit}"
    if kernel:
        assert rec["tpu_custom_call"] or not on_tpu(), \
            f"{name}: no compiled Pallas kernel in the HLO"
    if len(devices) > 1:
        assert rec["out_devices"] == len(devices), rec["out_devices"]
        if devices[0].platform == "tpu":
            assert all(b and b > 0 for b in rec["bytes_in_use"]), \
                rec["bytes_in_use"]
        if collective:
            assert collective in rec["collectives"], \
                f"{name}: no {collective} in the HLO"
    return rec


def on_tpu() -> bool:
    return jax.devices()[0].platform == "tpu"


def phases_one_chip(size):
    dev = jax.devices()[0]
    n, ni, nj, nk = size["n"], size["ni"], size["nj"], size["nk"]
    mesh2 = Mesh(np.array([dev]).reshape(1, 1), ("i", "j"))
    mesh1 = Mesh(np.array([dev]), ("data",))
    repl2 = NamedSharding(mesh2, PartitionSpec())
    repl1 = NamedSharding(mesh1, PartitionSpec())
    grid = make_env({"a": (n, n), "b": (n, n)}, repl2)
    mats = make_env({"A": (ni, nk), "B": (nk, nj), "C": (ni, nj)}, repl1)
    points = make_env({"x": (n * n,), "y": (n * n,)}, repl1)

    def stencil():
        run_phase("stencil", jacobi2d(n), mesh2, grid, ("a", "b"),
                  ELEMENTWISE_LIMIT)

    def gemm_phase():
        run_phase("gemm", gemm(ni), mesh1, mats, ("C",), GEMM_REL_LIMIT,
                  relative=True)

    def pallas():
        run_phase("pallas_stencil", jacobi2d(n), mesh2, grid, ("a", "b"),
                  ELEMENTWISE_LIMIT, kernel=True, lowering="pallas")
        run_phase("pallas_map", elementwise_map(n * n), mesh1, points,
                  ("y",), ELEMENTWISE_LIMIT, kernel=True, lowering="pallas")
        try:
            omp.compile(gemm(ni), mesh1, env_like=mats, lowering="pallas")
        except omp.CompileError as e:
            print(json.dumps({"phase": "pallas_gemm",
                              "compile_error": str(e)[:400]}), flush=True)
            return
        run_phase("pallas_gemm", gemm(ni), mesh1, mats, ("C",),
                  GEMM_REL_LIMIT, relative=True, kernel=True,
                  lowering="pallas")

    return [("stencil", stencil), ("gemm", gemm_phase), ("pallas", pallas)]


def phases_four_chips(size, tiny):
    devs = jax.devices()
    if len(devs) != 4:
        raise SystemExit(f"--four-chips needs 4 devices, found {len(devs)}")
    n, ni, nj, nk = size["n"], size["ni"], size["nj"], size["nk"]
    mesh2 = Mesh(np.array(devs).reshape(2, 2), ("i", "j"))
    mesh1 = Mesh(np.array(devs), ("data",))

    def stencil():
        grid = make_env({"a": (n, n), "b": (n, n)},
                        NamedSharding(mesh2, PartitionSpec()))
        # at tiny sizes the planner may gather instead of a halo ring
        run_phase("stencil_2x2", jacobi2d(n), mesh2, grid, ("a", "b"),
                  ELEMENTWISE_LIMIT,
                  collective="all-gather" if tiny else "collective-permute")

    def gemm_phase():
        mats = make_env({"A": (ni, nk), "B": (nk, nj), "C": (ni, nj)},
                        NamedSharding(mesh1, PartitionSpec()))
        run_phase("gemm_4", gemm(ni), mesh1, mats, ("C",), GEMM_REL_LIMIT,
                  relative=True, collective="all-gather")

    return [("stencil_2x2", stencil), ("gemm_4", gemm_phase)]


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    parser.add_argument("--four-chips", action="store_true",
                        help="run the 2x2 stencil and the (4,) gemm only")
    parser.add_argument("--tiny", action="store_true",
                        help="tiny sizes for a CPU rehearsal (no ok line)")
    args = parser.parse_args(argv)

    if not args.tiny and not on_tpu():
        print(f"no TPU: JAX found {jax.devices()[0].platform}; "
              "use --tiny for a CPU rehearsal", file=sys.stderr)
        return 2
    jax.monitoring.register_event_listener(_count_cache_event)
    size = TINY if args.tiny else FULL
    phases = (phases_four_chips(size, args.tiny) if args.four_chips
              else phases_one_chip(size))
    failed = []
    for name, fn in phases:
        try:
            fn()
        except Exception:
            failed.append(name)
            print(f"phase {name} FAILED", flush=True)
            traceback.print_exc()
    print(json.dumps({"compile_cache": {"dir": CACHE_DIR, **cache_stats,
                                        "hit": cache_stats["hits"] > 0}}),
          flush=True)
    if failed:
        print(f"failed phases: {', '.join(failed)}", flush=True)
        return 1
    dev = jax.devices()[0]
    if args.tiny or dev.platform != "tpu":
        print(f"rehearsal passed on {dev.platform} at tiny sizes; "
              "no ok line", flush=True)
        return 0
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
