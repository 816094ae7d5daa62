"""Plain jacobi-2d, as PolyBench/C 4.2.1 writes it::

    for (t = 0; t < TSTEPS; t++) {
      for (i = 1; i < N - 1; i++) for (j = 1; j < N - 1; j++)
        B[i][j] = 0.2 * (A[i][j] + A[i][j-1] + A[i][1+j] + A[1+i][j] + A[i-1][j]);
      for (i = 1; i < N - 1; i++) for (j = 1; j < N - 1; j++)
        A[i][j] = 0.2 * (B[i][j] + B[i][j-1] + B[i][1+j] + B[1+i][j] + B[i-1][j]);
    }

Each sweep reads only the grid the other sweep wrote, so it is one
whole-array update of the interior, with the adds in PolyBench's order.
"""
from __future__ import annotations

import jax.numpy as jnp


def _sweep(src, dst):
    v = 0.2 * (src[1:-1, 1:-1] + src[1:-1, :-2] + src[1:-1, 2:]
               + src[2:, 1:-1] + src[:-2, 1:-1])
    return dst.at[1:-1, 1:-1].set(v.astype(dst.dtype))


def _steps(cfg, a, b):
    for _ in range(cfg["TSTEPS"]):
        b = _sweep(a, b)
        a = _sweep(b, a)
    return {"a": a, "b": b}


def reference(cfg, env):
    """One call's ``TSTEPS`` time steps in float32."""
    return _steps(cfg, env["a"], env["b"])


def control(cfg, env):
    """The same steps with both grids in bfloat16."""
    out = _steps(cfg, env["a"].astype(jnp.bfloat16),
                 env["b"].astype(jnp.bfloat16))
    return {k: v.astype(jnp.float32) for k, v in out.items()}
