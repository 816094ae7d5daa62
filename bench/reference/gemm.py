"""Plain gemm, as PolyBench/C 4.2.1 writes it::

    for (i = 0; i < NI; i++) {
      for (j = 0; j < NJ; j++) C[i][j] *= beta;
      for (k = 0; k < NK; k++) for (j = 0; j < NJ; j++)
        C[i][j] += alpha * A[i][k] * B[k][j];
    }

The configuration states the precision of the products
(``assumed.matmul_operands``): ``A`` and ``B`` are rounded to that type,
each product is exact in float32 and the sum is kept in float32.
``A`` and ``B`` come out as they went in.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

# The next precision below each operand type, for the control.
LOWER = {"float32": "bfloat16", "bfloat16": "float8_e4m3fn"}


def _rounded(x, dtype):
    """``x`` rounded to ``dtype``'s precision, kept in float32.
    ``reduce_precision`` is never elided, where XLA may drop a
    ``convert`` to a narrower type and back."""
    if jnp.dtype(dtype) == jnp.float32:
        return x
    info = jnp.finfo(dtype)
    return jax.lax.reduce_precision(x, exponent_bits=info.nexp,
                                    mantissa_bits=info.nmant)


def _gemm(cfg, env, operands):
    ab = jnp.dot(_rounded(env["A"], operands), _rounded(env["B"], operands),
                 precision=jax.lax.Precision.HIGHEST,
                 preferred_element_type=jnp.float32)
    return {"A": env["A"], "B": env["B"],
            "C": cfg["beta"] * env["C"] + cfg["alpha"] * ab}


def reference(cfg, env):
    """``C`` with products at the stated operand precision."""
    return _gemm(cfg, env, cfg["assumed"]["matmul_operands"])


def control(cfg, env):
    """``C`` with products one operand precision lower."""
    return _gemm(cfg, env, LOWER[cfg["assumed"]["matmul_operands"]])
