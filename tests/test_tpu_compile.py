"""Compiles for a described TPU v5e, at PolyBench EXTRALARGE widths and
HPCG's benchmark grid.

Nothing here runs: each test lowers and compiles the program the chip
would run, for a v5e chip that is described and not attached (the TPU
compiler is installed with JAX).  That catches what the CPU backend and
interpret-mode Pallas accept but the chip refuses: block shapes off the
(8, 128) tiling, gathers Mosaic cannot lower, VMEM overflows.

The topology is described only inside a fixture of this module: only
one process at a time may load the TPU library.
"""
import os
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec

from repro import omp

N = 2800                       # jacobi-2d EXTRALARGE
NI, NJ, NK = 2000, 2300, 2600  # gemm EXTRALARGE


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    os.environ.setdefault("TPU_LOG_DIR", "disabled")  # else logs in /tmp
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


def _mesh(topo, shape, axes):
    devs = np.asarray(topo.devices[:int(np.prod(shape))]).reshape(shape)
    return Mesh(devs, axes)


def _avals(mesh, shapes):
    rep = NamedSharding(mesh, PartitionSpec())
    return {k: jax.ShapeDtypeStruct(s, jnp.float32, sharding=rep)
            for k, s in shapes.items()}


def _compile(prog, mesh, shapes, **options):
    avals = _avals(mesh, shapes)
    c = omp.compile(prog, mesh, env_like=avals, **options)
    return jax.jit(lambda e: c(e)).lower(avals).compile()


def jacobi2d(n, schedule=None, sweeps=4):
    clause = {} if schedule is None else {"schedule": schedule}

    def sweep(src, dst, name):
        @omp.parallel_for(start=(1, 1), stop=(n - 1, n - 1), collapse=2,
                          name=name, **clause)
        def body(i, j, env):
            a = env[src]
            v = 0.2 * (a[i, j] + a[i - 1, j] + a[i + 1, j]
                       + a[i, j - 1] + a[i, j + 1])
            return {dst: omp.at((i, j), v)}
        return body

    pairs = [("a", "b"), ("b", "a")] * (sweeps // 2)
    return omp.region(*(sweep(s, d, f"sweep{k}")
                        for k, (s, d) in enumerate(pairs)), name="jacobi2d")


def jacobi1d(n):
    def sweep(src, dst, name):
        @omp.parallel_for(start=1, stop=n - 1, name=name)
        def body(i, env):
            x = env[src]
            return {dst: omp.at(i, (x[i - 1] + x[i] + x[i + 1]) / 3.0)}
        return body

    return omp.region(sweep("a", "b", "p1"), sweep("b", "a", "p2"),
                      name="jacobi1d")


def gemm(ni):
    @omp.parallel_for(stop=ni, name="gemm")
    def body(i, env):
        return {"C": omp.at(i, 1.5 * (env["A"][i] @ env["B"])
                            + 1.2 * env["C"][i])}
    return body


def elementwise_map(n):
    @omp.parallel_for(stop=n, name="map")
    def body(i, env):
        x = env["x"][i]
        return {"y": omp.at(i, 4.0 / (1.0 + x * x))}
    return body


GRID = {"a": (N, N), "b": (N, N)}
MATS = {"A": (NI, NK), "B": (NK, NJ), "C": (NI, NJ)}


def test_fused_stencil_compiles_on_one_chip(topo):
    co = _compile(jacobi2d(N, omp.static()), _mesh(topo, (1, 1), ("i", "j")),
                  GRID)
    assert co.memory_analysis().argument_size_in_bytes >= 2 * N * N * 4


def test_fused_stencil_halos_are_ppermute_rings_on_2x2(topo):
    co = _compile(jacobi2d(N), _mesh(topo, (2, 2), ("i", "j")), GRID)
    assert "collective-permute" in co.as_text()


@pytest.mark.parametrize("ranks", [1, 4])
def test_collective_gemm_compiles(topo, ranks):
    co = _compile(gemm(NI), _mesh(topo, (ranks,), ("data",)), MATS,
                  lowering="collective")
    text = co.as_text()
    assert "tpu_custom_call" not in text
    assert ("all-gather" in text) == (ranks > 1)


PALLAS_FAMILIES = {
    "map": (lambda: elementwise_map(N * N), (1,), ("data",),
            {"x": (N * N,), "y": (N * N,)}),
    "stencil_rank1": (lambda: jacobi1d(N * N), (1,), ("data",),
                      {"a": (N * N,), "b": (N * N,)}),
    "stencil_rank2": (lambda: jacobi2d(N, omp.static()), (1, 1), ("i", "j"),
                      GRID),
    "stencil_rank2_2x2": (lambda: jacobi2d(N), (2, 2), ("i", "j"), GRID),
    "gemm_rows": (lambda: gemm(NI), (1,), ("data",), MATS),
}


@pytest.mark.parametrize("family", sorted(PALLAS_FAMILIES))
def test_pallas_span_compiles(topo, family):
    make, shape, axes, shapes = PALLAS_FAMILIES[family]
    co = _compile(make(), _mesh(topo, shape, axes), shapes,
                  lowering="pallas")
    assert "tpu_custom_call" in co.as_text()


def _bench_cell(name):
    """A benchmark cell (``bench/``, beside ``src/`` in the checkout)."""
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    if root not in sys.path:
        sys.path.insert(0, root)
    from bench import harness
    return harness.load_cell(name)


def test_hpcg_cg_region_compiles_at_192(topo):
    """The ``hpcg-192`` region (one CG iteration on a 192^3 grid, its
    SpMV gathering ``p`` through ``cols``) fits one chip's 16 GB, its
    row sums under ``omp.indirect.p`` inside ``omp.stage.spmv``."""
    cell = _bench_cell("hpcg-192.stepped")
    mesh = _mesh(topo, (1,), ("data",))
    rep = NamedSharding(mesh, PartitionSpec())
    like = jax.eval_shape(lambda k: cell.program.make_inputs(cell.cfg, k),
                          jax.random.key(0))
    like = {k: jax.ShapeDtypeStruct(v.shape, v.dtype, sharding=rep)
            for k, v in like.items()}
    c = omp.compile(cell.program.build(cell.cfg), mesh, env_like=like)
    co = jax.jit(lambda e: c(e)).lower(like).compile()
    assert "omp.stage.spmv/omp.indirect.p/" in co.as_text()
    mem = co.memory_analysis()
    assert mem.argument_size_in_bytes >= 2 * 27 * 192 ** 3 * 4
    assert (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes) < 16e9
    # the scan of vmapped chunks needed 4,532,523,008 bytes of
    # temporaries; evaluating stages over the local chunk stack may add
    # at most 5% (a vmap over the stack would hold every chunk's gather)
    assert mem.temp_size_in_bytes <= 4_759_149_158


def test_pallas_span_mosaic_refuses_raises_compile_error(topo):
    """A read that is not ``x[i+b]`` stays a gather, which Mosaic cannot
    lower: ``omp.compile`` names the span instead of running it in
    interpret mode."""
    n = 4096

    @omp.parallel_for(stop=n, name="scramble")
    def body(i, env):
        return {"y": omp.at(i, env["x"][(i * 7) % n])}

    mesh = _mesh(topo, (1,), ("data",))
    with pytest.raises(omp.CompileError, match="span 'scramble'"):
        omp.compile(body, mesh, env_like=_avals(mesh, {"x": (n,), "y": (n,)}),
                    lowering="pallas")


def test_pallas_interpret_rejected_on_tpu_mesh(topo):
    mesh = _mesh(topo, (1,), ("data",))
    with pytest.raises(omp.CompileError, match="interpret"):
        omp.compile(elementwise_map(1024), mesh, lowering="pallas",
                    pallas_interpret=True)
