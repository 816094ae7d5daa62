"""Device milliseconds per call in collective ops (halo
``collective-permute`` rings, all-gathers, all-reduces), per chip;
nothing where there are none."""
import re

COLLECTIVE = re.compile(
    r"(collective-permute|all-gather|all-reduce|reduce-scatter|all-to-all)")


def read(r):
    if r.trace is None:
        return None
    s = r.trace.ops_matching(lambda name: COLLECTIVE.match(name) is not None)
    return 1e3 * s / r.window.calls if s > 0 else None
