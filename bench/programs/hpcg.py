"""HPCG 3.1's conjugate-gradient iteration as an ``omp`` program.

The matrix is ``GenerateProblem_ref.cpp``'s 27-point operator on a local
``NX x NY x NZ`` grid, stored as HPCG stores it: 27 slots per row
(``vals``, ``cols``), the diagonal 26 and every in-grid neighbour -1,
with ``b = 26 - (nnz - 1)`` so that ``A * 1 = b``.  One call is one
iteration of ``CG.cpp`` without the preconditioner (``z = r``), rotated
so that the ``p`` update ends it: one ``omp.region`` of

* ``start``: serial, zeroes the two dot-product accumulators (HPCG's
  ``ComputeDotProduct`` sets its result, where a reduction clause adds
  to the variable's prior value), and after ``SET_ITERS`` iterations
  starts a new CG set from ``x = 0`` as HPCG's ``main.cpp`` does
  (``r = b``, ``p = r``, ``rho = r . r``), counting in ``k``;
* ``spmv``: ``Ap = A p`` row by row, ``p`` read through ``cols``, and
  ``pAp = p . Ap``;
* ``alpha``: serial, ``alpha = rho / pAp``;
* ``update``: ``x += alpha p``, ``r -= alpha Ap`` and ``rr = r . r``;
* ``beta``: serial, ``beta = rr / rho``, ``rho = rr``;
* ``direction``: ``p = r + beta p``.
"""
from __future__ import annotations

import itertools

import jax
import jax.numpy as jnp

OUTPUTS = ("x", "r", "p", "Ap")

# The 27 stencil offsets (sz, sy, sx) in GenerateProblem_ref's order.
OFFSETS = tuple(itertools.product((-1, 0, 1), repeat=3))


def build(cfg):
    """The ``omp.region`` of one call, for the sizes in ``cfg``."""
    from repro import omp

    n = cfg["NX"] * cfg["NY"] * cfg["NZ"]
    set_iters = cfg["SET_ITERS"]

    @omp.serial(reads=("k", "b", "x", "r", "p", "rho"), name="start")
    def start(env):
        new = env["k"] >= set_iters
        b, zero = env["b"], jnp.zeros((), jnp.float32)
        return {"x": jnp.where(new, zero, env["x"]),
                "r": jnp.where(new, b, env["r"]),
                "p": jnp.where(new, b, env["p"]),
                "rho": jnp.where(new, jnp.sum(b * b), env["rho"]),
                "k": jnp.where(new, 1, env["k"] + 1),
                "pAp": zero, "rr": zero}

    @omp.parallel_for(stop=n, reduction={"pAp": "+"}, name="spmv")
    def spmv(i, env):
        p = env["p"]
        row = jnp.sum(env["vals"][i] * p[env["cols"][i]])
        return {"Ap": omp.at(i, row), "pAp": omp.red(p[i] * row)}

    @omp.serial(reads=("rho", "pAp"), name="alpha")
    def alpha(env):
        return {"alpha": env["rho"] / env["pAp"]}

    @omp.parallel_for(stop=n, reduction={"rr": "+"}, name="update")
    def update(i, env):
        a = env["alpha"]
        r = env["r"][i] - a * env["Ap"][i]
        return {"x": omp.at(i, env["x"][i] + a * env["p"][i]),
                "r": omp.at(i, r), "rr": omp.red(r * r)}

    @omp.serial(reads=("rr", "rho"), name="beta")
    def beta(env):
        return {"beta": env["rr"] / env["rho"], "rho": env["rr"]}

    @omp.parallel_for(stop=n, name="direction")
    def direction(i, env):
        return {"p": omp.at(i, env["r"][i] + env["beta"] * env["p"][i])}

    return omp.region(start, spmv, alpha, update, beta, direction,
                      name="hpcg_cg")


def matrix(cfg):
    """``vals``, ``cols`` (``(n, 27)``) and ``b`` of HPCG's 27-point
    operator, from iota arithmetic on the device.  Row ``i`` is grid
    point ``(ix, iy, iz)`` with ``i = iz*NX*NY + iy*NX + ix``; a slot
    whose neighbour lies outside the grid holds 0 at column ``i``."""
    nx, ny, nz = cfg["NX"], cfg["NY"], cfg["NZ"]
    row = jnp.arange(nx * ny * nz, dtype=jnp.int32)[:, None]
    sz, sy, sx = (jnp.array(o, jnp.int32) for o in zip(*OFFSETS))
    jx = row % nx + sx
    jy = row // nx % ny + sy
    jz = row // (nx * ny) + sz
    inside = ((jx >= 0) & (jx < nx) & (jy >= 0) & (jy < ny)
              & (jz >= 0) & (jz < nz))
    cols = jnp.where(inside, jz * (nx * ny) + jy * nx + jx, row)
    diag = (sx == 0) & (sy == 0) & (sz == 0)
    vals = jnp.where(inside, jnp.where(diag, 26.0, -1.0), 0.0)
    nnz = jnp.sum(inside, axis=1, dtype=jnp.int32)
    b = (27 - nnz).astype(jnp.float32)
    return vals.astype(jnp.float32), cols, b


def make_inputs(cfg, key):
    """The matrix, ``x0`` uniform in [-1, 1) from ``key``, and CG's
    start from it: ``r0 = b - A x0``, ``p0 = r0``, ``rho0 = r0 . r0``;
    the first set starts here, at ``k = 0``."""
    vals, cols, b = matrix(cfg)
    x = jax.random.uniform(key, b.shape, jnp.float32, -1.0, 1.0)
    r = b - jnp.sum(vals * x[cols], axis=1)
    zero = jnp.zeros((), jnp.float32)
    return {"vals": vals, "cols": cols, "b": b, "x": x, "r": r, "p": r,
            "rho": jnp.sum(r * r), "Ap": jnp.zeros_like(r), "pAp": zero,
            "rr": zero, "alpha": zero, "beta": zero,
            "k": jnp.zeros((), jnp.int32)}


def min_work(cfg):
    """FLOPs and HBM bytes one call needs, from the shapes alone.

    ``n`` rows.  FLOPs: a multiply and an add per stored entry (54n),
    two per element in each dot product (4n), and in the three vector
    updates (6n): 64n.  Bytes: ``vals`` and ``cols`` read once (216n),
    ``x``, ``r`` and ``p`` read and written once (24n): 240n.
    ``indirect_bytes``: what the row sums read through ``cols`` --
    ``vals``, ``cols`` and the gathered ``p``, each once (220n)."""
    n = cfg["NX"] * cfg["NY"] * cfg["NZ"]
    return {"flops": 64 * n, "bytes": 240 * n, "indirect_bytes": 220 * n}
