"""Seconds the generated program spent in its executor during set-up
(``omp.timing_stats()["executor_seconds"]``): the Python trace of the
call inside ``xla_compile_s``; nothing where the program counts no
executor entries."""
from bench import scopes


def read(r):
    timing = scopes.program_timing()
    if not timing or not timing["executor_runs"]:
        return None
    return timing["executor_seconds"]
