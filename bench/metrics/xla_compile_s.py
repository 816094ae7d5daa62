"""Seconds of ``jax.jit(...).lower(...).compile()`` of the call: the
XLA and Mosaic compile, or its load from JAX's compile cache."""


def read(r):
    return r.xla_compile_s
