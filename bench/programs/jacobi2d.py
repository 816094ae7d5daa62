"""PolyBench/C 4.2.1 ``stencils/jacobi-2d`` as an ``omp`` program.

One call runs ``TSTEPS`` of PolyBench's time steps.  Each time step is
two 5-point sweeps over the interior, ``A -> B`` then ``B -> A``, so a
call is one fused ``omp.region`` of ``2 * TSTEPS`` ping-pong
``collapse(2)`` sweeps.  The boundary rows and columns of both grids are
never written.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

OUTPUTS = ("a", "b")


def build(cfg):
    """The ``omp.region`` of one call, for the sizes in ``cfg``."""
    from repro import omp

    n = cfg["N"]

    def sweep(src, dst, name):
        @omp.parallel_for(start=(1, 1), stop=(n - 1, n - 1), collapse=2,
                          name=name)
        def body(i, j, env):
            a = env[src]
            v = 0.2 * (a[i, j] + a[i - 1, j] + a[i + 1, j]
                       + a[i, j - 1] + a[i, j + 1])
            return {dst: omp.at((i, j), v)}
        return body

    pairs = [("a", "b"), ("b", "a")] * cfg["TSTEPS"]
    return omp.region(*(sweep(s, d, f"sweep{k + 1}")
                        for k, (s, d) in enumerate(pairs)), name="jacobi2d")


def make_inputs(cfg, key):
    """Both grids, uniform in [-1, 1), from ``key``."""
    n = cfg["N"]
    ka, kb = jax.random.split(key)
    return {"a": jax.random.uniform(ka, (n, n), jnp.float32, -1.0, 1.0),
            "b": jax.random.uniform(kb, (n, n), jnp.float32, -1.0, 1.0)}


def min_work(cfg):
    """FLOPs and HBM bytes one call needs, from the shapes alone.

    Bytes: read ``A`` once, write ``A`` and ``B`` once (the boundary of
    ``B`` read in is left out, so this is a lower bound).  FLOPs: four
    adds and one multiply per interior point per sweep."""
    n, sweeps = cfg["N"], 2 * cfg["TSTEPS"]
    return {"flops": 5 * (n - 2) ** 2 * sweeps, "bytes": 3 * n * n * 4}
