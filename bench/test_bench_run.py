"""``run.py`` without a chip, and whole runs with the timed path broken.

The fault tests skip the harness's look for a TPU and drive the rest of
a run at a tiny size on the CPU, with a fault planted in the compiled
call, and require ``correct`` to come out false: a call that returns its
state unchanged, half of the rows left out, a value altered where it is
produced, and (2x2 mesh, four virtual devices) the halo exchange left
out."""
import importlib
import json
import os
import subprocess
import sys
import time

import jax
import jax.numpy as jnp
import pytest

from bench import harness
from bench.test_bench_reference import gemm_cpu

TINY = {"jacobi2d-xl.stepped": {"N": 40},
        "jacobi2d-xl-2x2.stepped": {"N": 40},
        "gemm-xl.calls": None}


def unchanged(cfg, call):
    return lambda env: {k: env[k] for k in call(env)}


def half_rows(cfg, call):
    def f(env):
        out = call(env)
        return {k: v.at[v.shape[0] // 2:].set(env[k][v.shape[0] // 2:])
                for k, v in out.items()}
    return f


def altered(cfg, call):
    k = importlib.import_module(
        f"bench.programs.{cfg['family']}").OUTPUTS[0]

    def f(env):
        out = call(env)
        mid = tuple(s // 2 for s in out[k].shape)
        return {**out, k: out[k].at[mid].add(0.01)}
    return f


def no_exchange(cfg, call):
    """Each quadrant of the 2x2 mesh sweeps its own block, with zeros
    where its neighbours' halo rows and columns should have arrived."""
    n, h = cfg["N"], cfg["N"] // 2

    def sweeps(a, b):
        for _ in range(cfg["TSTEPS"]):
            for src, dst in ((0, 1), (1, 0)):
                g = (a, b)[src]
                p = jnp.pad(g, 1)
                v = 0.2 * (p[1:-1, 1:-1] + p[:-2, 1:-1] + p[2:, 1:-1]
                           + p[1:-1, :-2] + p[1:-1, 2:])
                if dst:
                    b = v
                else:
                    a = v
        return a, b

    @jax.jit
    def local(env):
        a, b = env["a"], env["b"]
        na, nb = a, b
        for r in (0, h):
            for c in (0, h):
                qa, qb = sweeps(a[r:r + h, c:c + h], b[r:r + h, c:c + h])
                na = na.at[r:r + h, c:c + h].set(qa)
                nb = nb.at[r:r + h, c:c + h].set(qb)
        keep = jnp.zeros((n, n), bool).at[1:-1, 1:-1].set(True)
        return {"a": jnp.where(keep, na, a), "b": jnp.where(keep, nb, b)}

    return lambda env: (call(env), local(env))[1]


def _measure(workload, wrap=None):
    return harness.measure(workload, 5, 0.05, False,
                           t_start=time.perf_counter(), require_tpu=False,
                           sizes=TINY[workload] or gemm_cpu(), wrap=wrap)


@pytest.mark.parametrize("workload", ["jacobi2d-xl.stepped", "gemm-xl.calls"])
@pytest.mark.parametrize("fault", [None, unchanged, half_rows, altered],
                         ids=["sound", "unchanged", "half_rows", "altered"])
def test_fault_makes_run_incorrect(workload, fault):
    result, _info = _measure(workload, fault)
    assert result["correct"] is (fault is None), result["checks"]
    assert list(result)[-1] == "checks"
    assert result["checks"]["window_compile_events"]["value"] == 0


MESH_CHILD = """
import json, sys, time
sys.path.insert(0, {root!r})
from bench import harness, test_bench_run as t
out = {{}}
for name, fault in [("sound", None), ("no_exchange", t.no_exchange),
                    ("unchanged", t.unchanged)]:
    r, _info = t._measure("jacobi2d-xl-2x2.stepped", fault)
    out[name] = [r["correct"], r["device"]["count"]]
print(json.dumps(out))
"""


def test_2x2_exchange_left_out_is_incorrect():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    proc = subprocess.run(
        [sys.executable, "-c", MESH_CHILD.format(root=harness.ROOT)],
        capture_output=True, text=True, env=env, timeout=300)
    assert proc.returncode == 0, proc.stderr[-3000:]
    got = json.loads(proc.stdout.strip().splitlines()[-1])
    assert got == {"sound": [True, 4], "no_exchange": [False, 4],
                   "unchanged": [False, 4]}


def test_run_without_tpu_exits_nonzero_and_prints_no_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, os.path.join(harness.BENCH, "run.py"),
         "--workload", "jacobi2d-xl.stepped", "--seed", "1",
         "--seconds", "1", "--trace", "0"],
        capture_output=True, text=True, env=env, timeout=120)
    assert proc.returncode != 0
    assert "{" not in proc.stdout
    assert "no TPU" in proc.stderr
