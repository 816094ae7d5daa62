"""HPCG's CG iteration: the matrix against ``GenerateProblem_ref``'s
loops, the program against the plain reference on one and on four
virtual devices (and the bfloat16 control beyond the limit), ``min_work``
and the readers of the indirect-read scope, at tiny sizes on the CPU.

``NX, NY, NZ = 8, 6, 4`` so that a swapped axis order shows; a CG set of
a few iterations (``SET_ITERS``) so that the window starts new sets."""
import itertools
import json
import os
import subprocess
import sys
import time
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec

from bench import control, harness, scopes, trace
from bench.metrics import indirect_ms, indirect_roofline_share
from bench.programs import hpcg

TINY = {"NX": 8, "NY": 6, "NZ": 4, "SET_ITERS": 3}
WORKLOAD = "hpcg-192.stepped"


def hpcg_rows(nx, ny, nz):
    """Each row's (column, value) list in GenerateProblem_ref's loop
    order, written as its C loops are."""
    rows = []
    for iz, iy, ix in itertools.product(range(nz), range(ny), range(nx)):
        row = iz * nx * ny + iy * nx + ix
        entries = []
        for sz, sy, sx in itertools.product((-1, 0, 1), repeat=3):
            if 0 <= iz + sz < nz and 0 <= iy + sy < ny \
                    and 0 <= ix + sx < nx:
                col = row + sz * nx * ny + sy * nx + sx
                entries.append((col, 26.0 if col == row else -1.0))
        rows.append(entries)
    return rows


def test_matrix_is_hpcgs_27_point_operator():
    nx, ny, nz = TINY["NX"], TINY["NY"], TINY["NZ"]
    vals, cols, b = (np.asarray(a) for a in hpcg.matrix(TINY))
    assert vals.shape == cols.shape == (nx * ny * nz, 27)
    rows = hpcg_rows(nx, ny, nz)
    for i, entries in enumerate(rows):
        stored = vals[i] != 0
        assert list(zip(cols[i][stored], vals[i][stored])) == entries
        assert (cols[i][~stored] == i).all()
    # nonzeros per row: the product over axes of the in-range neighbours
    reach = lambda k, n: 1 + (k > 0) + (k < n - 1)
    nnz = [reach(ix, nx) * reach(iy, ny) * reach(iz, nz)
           for iz, iy, ix in itertools.product(range(nz), range(ny),
                                               range(nx))]
    assert [len(e) for e in rows] == nnz
    assert (np.count_nonzero(vals, axis=1) == nnz).all()
    # A * 1 = b, with b = 26 - (nnz - 1)
    np.testing.assert_array_equal(vals.sum(axis=1), b)
    np.testing.assert_array_equal(b, 27 - np.array(nnz))


def test_seed_makes_the_data():
    cell = harness.load_cell(WORKLOAD, TINY)
    make = lambda s: cell.program.make_inputs(cell.cfg, harness.seed_key(s))
    same, other, far = make(7), make(7), make(2 ** 40 + 7)
    assert all(jnp.array_equal(same[k], other[k]) for k in same)
    assert not jnp.array_equal(same["x"], far["x"])
    r = same["b"] - jnp.sum(same["vals"] * same["x"][same["cols"]], axis=1)
    np.testing.assert_allclose(same["r"], r, rtol=1e-6)
    assert float(same["rho"]) == pytest.approx(float(jnp.sum(r * r)))


def test_min_work():
    cfg = harness.load_cell(WORKLOAD).cfg
    n = 192 ** 3
    assert hpcg.min_work(cfg) == {"flops": 64 * n, "bytes": 240 * n,
                                  "indirect_bytes": 220 * n}
    assert 240 * n == 1_698_693_120


def _step_and_check(cfg, calls=8):
    """``calls`` stepped calls of the compiled region, each compared with
    the reference on its own inputs: the largest error."""
    from repro import omp
    from repro.compat import make_mesh

    n = int(np.prod(cfg["mesh"]["shape"]))
    mesh = make_mesh((n,), ("data",))
    # committed as the outputs are, so that the call is traced once
    env = jax.device_put(hpcg.make_inputs(cfg, harness.seed_key(2 ** 33)),
                         NamedSharding(mesh, PartitionSpec()))
    compiled = omp.compile(hpcg.build(cfg), mesh, env_like=env)
    call = jax.jit(lambda e: compiled(e))
    cell = harness.load_cell(WORKLOAD, cfg)
    ref = jax.jit(lambda e: cell.reference.reference(cfg, e))
    worst = 0.0
    for _ in range(calls):
        out = call(env)
        worst = max(worst, harness.rel_err(out, ref(env)))
        env = {**env, **out}
    return worst, int(env["k"]), compiled.indirect_reads


MESH_CHILD = """
import json, sys
sys.path.insert(0, {root!r})
from bench import control, test_bench_hpcg as t
sizes = dict(t.TINY, mesh={{"shape": [4], "axes": ["data"]}}, chips=4)
rows = list(control.readings(t.WORKLOAD, [3, 2 ** 31 + 11], 0.05,
                             require_tpu=False, sizes=sizes))
print(json.dumps(rows))
"""


@pytest.mark.parametrize("devices", [1, 4])
def test_program_within_limit_and_control_beyond(devices):
    limit = harness.load_cell(WORKLOAD).cfg["limits"]["max_rel_err"]
    if devices == 1:
        rows = list(control.readings(WORKLOAD, [3, 2 ** 31 + 11], 0.05,
                                     require_tpu=False, sizes=TINY))
    else:
        env = dict(os.environ, JAX_PLATFORMS="cpu",
                   XLA_FLAGS="--xla_force_host_platform_device_count=4")
        proc = subprocess.run(
            [sys.executable, "-c", MESH_CHILD.format(root=harness.ROOT)],
            capture_output=True, text=True, env=env, timeout=300)
        assert proc.returncode == 0, proc.stderr[-3000:]
        rows = json.loads(proc.stdout.strip().splitlines()[-1])
    for row in rows:
        assert row["program"] <= limit < row["control"], row


def test_every_call_across_new_sets_within_limit():
    """Eight stepped calls over CG sets of three, each checked against
    the reference on its own inputs: the third set has begun."""
    cfg = harness.load_cell(WORKLOAD, TINY).cfg
    worst, k, indirect_reads = _step_and_check(cfg)
    assert worst <= cfg["limits"]["max_rel_err"]
    assert k == 2 and indirect_reads == 1


def _reading(summary, calls):
    cfg = harness.load_cell(WORKLOAD).cfg
    return SimpleNamespace(trace=summary, window=SimpleNamespace(calls=calls),
                           cell=SimpleNamespace(name="cell"), chips=1,
                           work=hpcg.min_work(cfg),
                           peaks={"flops_per_s": 197e12,
                                  "hbm_bytes_per_s": 819e9})


def _synthetic():
    path = os.path.join(harness.BENCH, "testdata", "trace_synthetic.json")
    with open(path) as f:
        return trace.summarize(json.load(f), [0, 1], 2e-6)


def test_indirect_readers(monkeypatch):
    """``indirect_ms`` reads the ops under ``omp.indirect.*``, and the
    roofline share puts ``indirect_bytes`` at the HBM peak over it."""
    r = _reading(_synthetic(), 2)
    monkeypatch.setattr(scopes, "_RUN", {
        "cell": "cell", "smap": {"fusion.1": "omp.indirect.p",
                                 "all-gather.2": "omp.stage.spmv"},
        "timing": {"indirect_reads": 1, "indirect_elems": 27}})
    ms = 1e3 * 200e-9 / 2
    assert indirect_ms.read(r) == pytest.approx(ms)
    least_s = 220 * 192 ** 3 / 819e9
    assert indirect_roofline_share.read(r) == pytest.approx(
        100 * least_s / (1e-3 * ms))


@pytest.mark.parametrize("timing", [
    None,                                   # a program that keeps none
    {"executor_runs": 1},                   # one that counts no indirect
    {"indirect_reads": 0, "indirect_elems": 0},
])
def test_indirect_readers_find_nothing_without_indirect_stages(
        monkeypatch, timing):
    monkeypatch.setattr(scopes, "_RUN", {
        "cell": "cell", "smap": {"fusion.1": "omp.stage.spmv"},
        "timing": timing})
    r = _reading(_synthetic(), 2)
    assert indirect_ms.read(r) is None
    assert indirect_roofline_share.read(r) is None
    assert indirect_ms.read(_reading(None, 2)) is None


def test_cell_rehearsal_reads_correct():
    """A whole run of the cell at the tiny size, as ``run.py`` makes it,
    reads ``correct`` with no compile in the window."""
    result, _info = harness.measure(
        WORKLOAD, 2 ** 31 + 77, 0.05, False, t_start=time.perf_counter(),
        require_tpu=False, sizes=TINY)
    assert result["correct"], result["checks"]
    assert result["checks"]["window_compile_events"]["value"] == 0
