"""Seconds of ``omp.compile(program, mesh, env_like=...)``: the facade
and its planning passes."""


def read(r):
    return r.plan_s
