"""Device milliseconds per call per chip in the generated program's
communication inside the region: ops under ``omp.exchange.*`` (halo
exchanges with their packing), ``omp.gather.*`` (in-region
all-gathers) and ``omp.combine*`` (cross-device combines); nothing
where there are none."""
from bench import scopes


def read(r):
    return scopes.per_call_ms(r, scopes.EXCHANGE)
