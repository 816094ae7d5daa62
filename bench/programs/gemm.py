"""PolyBench/C 4.2.1 ``linear-algebra/blas/gemm`` as an ``omp`` program.

``C = alpha * A @ B + beta * C``, one ``parallel_for`` over the rows of
``C``.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

OUTPUTS = ("C", "A", "B")


def build(cfg):
    """The ``parallel_for`` of one call, for the sizes in ``cfg``."""
    from repro import omp

    alpha, beta = cfg["alpha"], cfg["beta"]

    @omp.parallel_for(stop=cfg["NI"], name="gemm")
    def body(i, env):
        return {"C": omp.at(i, alpha * (env["A"][i] @ env["B"])
                            + beta * env["C"][i])}
    return body


def make_inputs(cfg, key):
    """``A``, ``B`` and ``C``, uniform in [-1, 1), from ``key``."""
    ni, nj, nk = cfg["NI"], cfg["NJ"], cfg["NK"]
    ka, kb, kc = jax.random.split(key, 3)
    return {"A": jax.random.uniform(ka, (ni, nk), jnp.float32, -1.0, 1.0),
            "B": jax.random.uniform(kb, (nk, nj), jnp.float32, -1.0, 1.0),
            "C": jax.random.uniform(kc, (ni, nj), jnp.float32, -1.0, 1.0)}


def min_work(cfg):
    """FLOPs and HBM bytes one call needs, from the shapes alone.

    FLOPs: a multiply and an add per term of ``A @ B``, then three per
    element of ``C`` for the two scalings and the sum.  Bytes: read
    ``A``, ``B`` and ``C`` once and write ``C`` once, in float32."""
    ni, nj, nk = cfg["NI"], cfg["NJ"], cfg["NK"]
    return {"flops": 2 * ni * nj * nk + 3 * ni * nj,
            "bytes": 4 * (ni * nk + nk * nj + 2 * ni * nj)}
