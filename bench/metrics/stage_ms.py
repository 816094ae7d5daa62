"""Device milliseconds per call per chip in the generated program's
stages: ops under ``omp.stage.*`` (a stage's chunk loop) and
``omp.kernel.*`` (a fused Pallas span); nothing where the compiled
call names no such scope."""
from bench import scopes


def read(r):
    return scopes.per_call_ms(r, scopes.STAGE)
