"""Device milliseconds per call per chip in the generated program's
entry and exit layout: ops under ``omp.entry`` (replicated inputs to
local slabs) and ``omp.exit`` (slabs to the output layout, with the
exit all-gathers); nothing where the compiled call names no such
scope."""
from bench import scopes


def read(r):
    return scopes.per_call_ms(r, scopes.LAYOUT)
