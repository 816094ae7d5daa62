"""The lax lowering runs a rank-2 stage over its whole local chunk stack.

``transform._run_local_chunks2`` evaluates the body once over every
local ``(chunk_i, chunk_j)`` pair, serving each window read as a slice
(``tile_eval.eval_local_chunks2``), and falls back to a scan of vmapped
chunks where the evaluator refuses a read.  Each case here runs both
paths (the scan forced by making the evaluator refuse) and requires the
same outputs to float32 rounding, and counts which path each stage took.
"""
import json
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh

from repro import omp
from repro.core import nest, tile_eval

EPS = float(np.finfo(np.float32).eps)


def mesh(shape):
    n = int(np.prod(shape))
    return Mesh(np.array(jax.devices()[:n]).reshape(shape), ("i", "j"))


def data(shape, seed):
    return jnp.asarray(np.random.default_rng(seed).uniform(-1, 1, shape),
                       jnp.float32)


def jacobi(n, m, sweeps=2):
    """PolyBench jacobi-2d's ping-pong 5-point sweeps on an n x m grid."""
    def sweep(src, dst, name):
        @omp.parallel_for(start=(1, 1), stop=(n - 1, m - 1), collapse=2,
                          name=name)
        def body(i, j, env):
            a = env[src]
            return {dst: omp.at((i, j), 0.2 * (
                a[i, j] + a[i - 1, j] + a[i + 1, j] + a[i, j - 1]
                + a[i, j + 1]))}
        return body

    pairs = [("a", "b"), ("b", "a")] * (sweeps // 2)
    reg = omp.region(*(sweep(s, d, f"sweep{k}")
                       for k, (s, d) in enumerate(pairs)), name="jacobi2d")
    return reg, {"a": data((n, m), 1), "b": data((n, m), 2)}


def reduce_region(n, m):
    """A sweep, then a stage that writes a grid and folds two reductions
    over the sweep's output."""
    @omp.parallel_for(start=(1, 1), stop=(n - 1, m - 1), collapse=2,
                      name="smooth")
    def smooth(i, j, env):
        a = env["a"]
        return {"b": omp.at((i, j), 0.5 * (a[i - 1, j] + a[i, j + 1]))}

    @omp.parallel_for(stop=(n, m), collapse=2, reduction={"s": "+",
                                                          "hi": "max"},
                      name="fold")
    def fold(i, j, env):
        b = env["b"]
        return {"c": omp.at((i, j), b[i, j] * 3.0),
                "s": omp.red(b[i, j] * b[i, j]),
                "hi": omp.red(b[i, j])}

    env = {"a": data((n, m), 3), "b": jnp.zeros((n, m), jnp.float32),
           "c": jnp.zeros((n, m), jnp.float32), "s": jnp.float32(0.5)}
    return omp.region(smooth, fold, name="reduce2"), env


def block(n, m):
    """A standalone collapse(2) loop with 2-D and 1-D slab reads."""
    @omp.parallel_for(start=(1, 0), stop=(n, m - 1), collapse=2, name="blk")
    def blk(i, j, env):
        a = env["a"]
        return {"y": omp.at((i, j), a[i - 1, j] * env["x"][i]
                            + a[i, j + 1])}

    env = {"a": data((n, m), 4), "x": data((n,), 5),
           "y": jnp.zeros((n, m), jnp.float32)}
    return blk, env


def strided(n):
    """``a[2*i, j]``: the planner replicates ``a`` (only unit-stride reads
    are chunk windows), so the evaluator serves the read from the whole
    array and the stage still takes the sliced path."""
    @omp.parallel_for(stop=(n, n), collapse=2, name="strided")
    def body(i, j, env):
        return {"y": omp.at((i, j), env["a"][2 * i, j] + 1.0)}

    return body, {"a": data((2 * n, n), 6),
                  "y": jnp.zeros((n, n), jnp.float32)}


# name -> (builder, mesh shape, compile options, rank-2 stages per trace)
CASES = {
    "jacobi_1x1": (lambda: jacobi(24, 24, sweeps=4), (1, 1), {}, 4),
    "jacobi_2x2": (lambda: jacobi(40, 40), (2, 2), {}, 2),
    "uneven": (lambda: jacobi(25, 31), (1, 1), {}, 2),
    "reduce": (lambda: reduce_region(19, 23), (1, 1), {}, 2),
    "block": (lambda: block(21, 26), (1, 1), {"shard": "slice"}, 1),
    "strided_read": (lambda: strided(9), (1, 1), {}, 1),
}


def _refuse(*args, **kwargs):
    raise nest.SubstitutionFailed("forced: serve no window read")


def compare(name: str) -> dict:
    """Run a case on both paths: each output's largest difference over
    its float32 rounding allowance, and the paths each stage took."""
    make, shape, options, _ = CASES[name]
    prog, env = make()
    m = mesh(shape)
    got = {}
    for path in ("sliced", "scan"):
        compiled = omp.compile(prog, m, **options)
        saved = tile_eval.eval_local_chunks2
        if path == "scan":
            tile_eval.eval_local_chunks2 = _refuse
        try:
            out = jax.jit(lambda e: compiled(e))(env)
        finally:
            tile_eval.eval_local_chunks2 = saved
        got[path] = ({k: np.asarray(v) for k, v in out.items()},
                     (compiled.chunk_eval_sliced, compiled.chunk_eval_scan))
    (sliced, counts_sliced), (scan, counts_scan) = got["sliced"], got["scan"]
    # a fold of n terms rounds by at most about n ulps of its terms' sizes
    terms = max(x.size for x in env.values())
    err = {}
    for k in scan:
        allow = terms * EPS * max(1.0, float(np.max(np.abs(scan[k]))))
        err[k] = float(np.max(np.abs(sliced[k] - scan[k]))) / allow
    return {"err": err, "sliced": counts_sliced, "scan": counts_scan}


CHILD = """
import json, sys
sys.path.insert(0, {tests!r})
import test_chunk_eval as t
print(json.dumps(t.compare({name!r})))
"""


@pytest.mark.parametrize("name", sorted(CASES))
def test_sliced_chunk_stack_matches_scan(name, multidevice):
    shape, stages = CASES[name][1], CASES[name][3]
    if np.prod(shape) > 1:
        got = json.loads(multidevice(
            CHILD.format(tests=os.path.dirname(__file__), name=name),
            n_devices=int(np.prod(shape))).strip().splitlines()[-1])
    else:
        got = compare(name)
    assert all(e <= 1.0 for e in got["err"].values()), got
    assert tuple(got["sliced"]) == (stages, 0)
    assert tuple(got["scan"]) == (0, stages)


OP = re.compile(r"= (?:\([^()]*\)|\S+) ([a-z][\w-]*)\(")
OP_NAME = re.compile(r'op_name="([^"]*)"')


def stage_ops(text: str) -> set:
    """Under an ``omp.stage.*`` scope of the optimized HLO: the opcodes,
    and the JAX primitives the ops came from (a backend may wrap a loop
    in a call)."""
    ops = set()
    for line in text.splitlines():
        op, scope = OP.search(line), OP_NAME.search(line)
        if op and scope and "/omp.stage." in "/" + scope.group(1):
            ops.update((op.group(1), scope.group(1).split("/")[-1]))
    return ops


def test_sliced_stages_compile_to_no_gather_and_no_loop():
    reg, env = jacobi(24, 24, sweeps=4)
    compiled = omp.compile(reg, mesh((1, 1)))
    text = jax.jit(lambda e: compiled(e)).lower(env).compile().as_text()
    assert (compiled.chunk_eval_sliced, compiled.chunk_eval_scan) == (4, 0)
    ops = stage_ops(text)
    assert ops and not ops & {"gather", "while"}, ops

    # the scan it falls back to keeps its chunk loop
    before = omp.timing_stats()
    scan = omp.compile(reg, mesh((1, 1)))
    saved, tile_eval.eval_local_chunks2 = (tile_eval.eval_local_chunks2,
                                           _refuse)
    try:
        text = jax.jit(lambda e: scan(e)).lower(env).compile().as_text()
    finally:
        tile_eval.eval_local_chunks2 = saved
    assert (scan.chunk_eval_sliced, scan.chunk_eval_scan) == (0, 4)
    assert "while" in stage_ops(text)
    after = omp.timing_stats()
    assert after["chunk_eval_scan"] - before["chunk_eval_scan"] == 4
    assert after["chunk_eval_sliced"] == before["chunk_eval_sliced"]
