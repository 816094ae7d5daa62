"""Plain HPCG 3.1 conjugate-gradient iteration, from ``src/CG.cpp`` with
``doPreconditioning = false`` (so ``z = r``) and ``ComputeSPMV_ref.cpp``::

    ComputeSPMV(A, p, Ap);                       // Ap = A*p
    ComputeDotProduct(nrow, p, Ap, pAp, ...);    // alpha = p'*Ap
    alpha = rtz/pAp;
    ComputeWAXPBY(nrow, 1.0, x, alpha, p, x);    // x = x + alpha*p
    ComputeWAXPBY(nrow, 1.0, r, -alpha, Ap, r);  // r = r - alpha*Ap
    ComputeDotProduct(nrow, r, r, normr, ...);
    oldrtz = rtz; rtz = r'*z; beta = rtz/oldrtz;
    ComputeWAXPBY(nrow, 1.0, z, beta, p, p);     // p = beta*p + z

    for (i = 0; i < nrow; i++) {                 // ComputeSPMV_ref
      double sum = 0.0;
      for (j = 0; j < nnz[i]; j++) sum += vals[i][j] * x[ind[i][j]];
      y[i] = sum;
    }

and ``src/main.cpp``'s timed sets, each a CG call of 50 iterations
started from ``ZeroVector(x)`` (so ``r = b``, ``p = r``).

One call is one iteration, the loop of ``CG.cpp`` rotated so that the
``p`` update ends it; ``rho`` carries ``rtz`` from call to call, and
``k`` counts the iterations of the current set: once it reaches
``SET_ITERS`` the call starts a new set from ``x = 0``.  The
matrix comes in as the 27-wide rows ``GenerateProblem_ref.cpp`` stores
(``vals``, ``cols``, padded slots 0).  Departures from the source:
float32 for double; the dot products summed in ``jax.numpy``'s order,
not left to right (each row sum is left to right, padded slots adding
0);
no ``normr`` or tolerance test (a call always takes its step, as
HPCG's timed sets run with tolerance 0); the first set starts from
whatever ``x``, ``r``, ``p`` and ``rho`` come in.  Every
input comes out, changed or not.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

# The vectors the control rounds; the matrix and the scalars it leaves.
VECTORS = ("x", "r", "p")


def _iteration(cfg, env, rounded):
    new = env["k"] >= cfg["SET_ITERS"]
    b = env["b"]
    x = rounded(jnp.where(new, 0.0, env["x"]))
    r = rounded(jnp.where(new, b, env["r"]))
    p = rounded(jnp.where(new, b, env["p"]))
    rho = jnp.where(new, jnp.sum(b * b), env["rho"])
    vals, cols = env["vals"], env["cols"]
    # the row sums slot by slot, in the stored order: no (n, 27)
    # temporary (on the TPU one is padded to 128 lanes, 3.6 GB at 192^3)
    ap = vals[:, 0] * p[cols[:, 0]]
    for j in range(1, vals.shape[1]):
        ap = ap + vals[:, j] * p[cols[:, j]]
    pap = jnp.sum(p * ap)
    alpha = rho / pap
    x = rounded(x + alpha * p)
    r = rounded(r - alpha * ap)
    rr = jnp.sum(r * r)
    beta = rr / rho
    p = rounded(r + beta * p)
    return {**env, "x": x, "r": r, "p": p, "Ap": ap, "pAp": pap,
            "alpha": alpha, "rr": rr, "beta": beta, "rho": rr,
            "k": jnp.where(new, 1, env["k"] + 1)}


def reference(cfg, env):
    """One CG iteration in float32."""
    return _iteration(cfg, env, lambda v: v)


def control(cfg, env):
    """The same iteration with ``x``, ``r`` and ``p`` rounded to
    bfloat16 (kept in float32) where they are read and written."""
    info = jnp.finfo(jnp.bfloat16)
    return _iteration(cfg, env, lambda v: jax.lax.reduce_precision(
        v, exponent_bits=info.nexp, mantissa_bits=info.nmant))
