"""Device milliseconds per call per chip in the generated program's
reads through an index array: ops under ``omp.indirect.*`` (the chunk
compute of a stage that gathers through an index array, ``x[idx[i]]``);
nothing where the program traced no such stage."""
from bench import scopes

INDIRECT = ("omp.indirect.",)


def read(r):
    timing = scopes.program_timing()
    if not timing or not timing.get("indirect_reads"):
        return None
    return scopes.per_call_ms(r, INDIRECT)
