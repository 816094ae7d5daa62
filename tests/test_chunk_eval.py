"""The lax lowering runs a stage over its whole local chunk stack.

``transform._run_local_chunks`` evaluates the body once over every
local chunk (rank 1) or ``(chunk_i, chunk_j)`` pair (rank 2), serving
each window read, and each unit-stride read of a replicated buffer, as
a slice (``tile_eval.eval_local_chunks``), and falls back to a scan of
vmapped chunks where the evaluator refuses a read.  Each case here runs
both paths (the scan forced by making the evaluator refuse) and
requires the same outputs to float32 rounding, and counts which path
each stage took.
"""
import json
import os
import re
import sys

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
    axes = ("i", "j") if len(shape) == 2 else ("data",)
    return Mesh(np.array(jax.devices()[:n]).reshape(shape), axes)


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


def replicated2(n, m):
    """``a[i+1, j-1]`` of a grid that is also summed whole, so
    replicated: served from rows of it cut per ``(chunk_i, chunk_j)``."""
    @omp.parallel_for(start=(0, 1), stop=(n - 1, m), collapse=2,
                      name="replicated2")
    def body(i, j, env):
        a = env["a"]
        return {"y": omp.at((i, j), a[i + 1, j - 1] * jnp.sum(a))}

    return body, {"a": data((n, m), 14), "y": jnp.zeros((n, m), jnp.float32)}


def hpcg_region():
    """The benchmark's HPCG CG iteration on an 8x6x4 grid: an SpMV
    reading ``p`` through ``cols``, two dot products, vector updates and
    serial glue."""
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    if root not in sys.path:
        sys.path.insert(0, root)
    from bench.programs import hpcg

    cfg = {"NX": 8, "NY": 6, "NZ": 4, "SET_ITERS": 50}
    return hpcg.build(cfg), hpcg.make_inputs(cfg, jax.random.key(7))


def gemm(ni, nk, nj):
    """PolyBench gemm's row loop: ``A[i]`` and ``C[i]`` row reads."""
    @omp.parallel_for(stop=ni, name="gemm")
    def body(i, env):
        return {"C": omp.at(i, 1.5 * (env["A"][i] @ env["B"])
                            + 1.2 * env["C"][i])}

    return body, {"A": data((ni, nk), 7), "B": data((nk, nj), 8),
                  "C": data((ni, nj), 9)}


def jacobi1d(n):
    """Two ping-pong 3-point sweeps over ``n - 2`` points, so the last
    chunk is padded."""
    def sweep(src, dst, name):
        @omp.parallel_for(start=1, stop=n - 1, name=name)
        def body(i, env):
            x = env[src]
            return {dst: omp.at(i, (x[i - 1] + x[i] + x[i + 1]) / 3.0)}
        return body

    return (omp.region(sweep("a", "b", "s0"), sweep("b", "a", "s1"),
                       name="jacobi1d"),
            {"a": data((n,), 10), "b": data((n,), 11)})


def shifted(n):
    """``x[i+3]`` and ``x[i-1]`` of a buffer that is also summed whole,
    so replicated: the evaluator serves both reads from rows cut out of
    it, and ``x[i+3]`` reaches the buffer's last element, where a slice
    that started unpadded would clamp."""
    @omp.parallel_for(start=1, stop=n - 3, name="shifted")
    def body(i, env):
        x = env["x"]
        return {"y": omp.at(i, x[i + 3] - x[i - 1] + jnp.sum(x))}

    return body, {"x": data((n,), 12), "y": jnp.zeros(n, jnp.float32)}


def scatter_put(n):
    """A strided write (``scatter``), the last iteration's value
    (``put``) and a reduction of one loop."""
    @omp.parallel_for(stop=n, reduction={"s": "+"}, name="scatter_put")
    def body(i, env):
        x = env["x"][i]
        return {"z": omp.at(3 * i + 2, x * 2.0),
                "w": omp.put(jnp.full((4,), 1.0, jnp.float32) * x),
                "s": omp.red(x)}

    return body, {"x": data((n,), 13), "z": -jnp.ones(3 * n + 2, jnp.float32),
                  "w": jnp.zeros(4, jnp.float32), "s": jnp.float32(0.5)}


# name -> (builder, mesh shape, compile options, stages per trace)
CASES = {
    "jacobi_1x1": (lambda: jacobi(24, 24, sweeps=4), (1, 1), {}, 4),
    "jacobi_2x2": (lambda: jacobi(40, 40), (2, 2), {}, 2),
    "uneven": (lambda: jacobi(25, 31), (1, 1), {}, 2),
    "reduce": (lambda: reduce_region(19, 23), (1, 1), {}, 2),
    "block": (lambda: block(21, 26), (1, 1), {"shard": "slice"}, 1),
    "strided_read": (lambda: strided(9), (1, 1), {}, 1),
    "replicated_2d": (lambda: replicated2(13, 11), (1, 1), {}, 1),
    "hpcg_1": (hpcg_region, (1,), {}, 3),
    "hpcg_4": (hpcg_region, (4,), {}, 3),
    "gemm_rows": (lambda: gemm(23, 17, 19), (1,), {}, 1),
    "uneven_rank1": (lambda: jacobi1d(37), (1,), {}, 2),
    "replicated_tail": (lambda: shifted(41), (1,), {}, 1),
    "scatter_put": (lambda: scatter_put(23), (4,), {}, 1),
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
        saved = tile_eval.eval_local_chunks
        if path == "scan":
            tile_eval.eval_local_chunks = _refuse
        try:
            out = jax.jit(lambda e: compiled(e))(env)
        finally:
            tile_eval.eval_local_chunks = saved
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


def stage_ops(text: str, scope: str = "omp.stage.") -> set:
    """Under the scope ``scope`` (by default any ``omp.stage.*``) of the
    optimized HLO: the opcodes, and the JAX primitives the ops came from
    (a backend may wrap a loop in a call)."""
    ops = set()
    for line in text.splitlines():
        op, name = OP.search(line), OP_NAME.search(line)
        if op and name and "/" + scope in "/" + name.group(1):
            ops.update((op.group(1), name.group(1).split("/")[-1]))
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
    saved, tile_eval.eval_local_chunks = (tile_eval.eval_local_chunks,
                                           _refuse)
    try:
        text = jax.jit(lambda e: scan(e)).lower(env).compile().as_text()
    finally:
        tile_eval.eval_local_chunks = saved
    assert (scan.chunk_eval_sliced, scan.chunk_eval_scan) == (0, 4)
    assert "while" in stage_ops(text)
    after = omp.timing_stats()
    assert after["chunk_eval_scan"] - before["chunk_eval_scan"] == 4
    assert after["chunk_eval_sliced"] == before["chunk_eval_sliced"]


GATHER = re.compile(r"= \w+\[([\d,]*)\]\S* gather\(.*slice_sizes=\{([\d,]*)\}")


def test_rank1_sliced_stages_read_slices_and_gather_only_through_cols():
    """HPCG's rank-1 stages: the vector updates compile to no gather and
    no loop; the SpMV's only gathers are of ``p`` through ``cols`` (one
    entry per stored slot), its rows of ``vals`` and ``cols`` and its
    ``p[i]`` read as slices."""
    prog, env = hpcg_region()
    compiled = omp.compile(prog, mesh((1,)))
    text = jax.jit(lambda e: compiled(e)).lower(env).compile().as_text()
    assert (compiled.chunk_eval_sliced, compiled.chunk_eval_scan) == (3, 0)
    for stage in ("update", "direction"):
        ops = stage_ops(text, f"omp.stage.{stage}/")
        assert ops and not ops & {"gather", "while"}, (stage, ops)
    gathers = [GATHER.search(line).groups() for line in text.splitlines()
               if " gather(" in line
               and "omp.stage.spmv/omp.indirect.p/" in line]
    # scalars of p, 27 per row (one per stored slot): no row of vals or
    # cols (slices of 27) and no p[i] (one per row)
    assert gathers and all(
        sizes == "1" and np.prod([int(n) for n in shape.split(",")]) % 27
        == 0 for shape, sizes in gathers), gathers
