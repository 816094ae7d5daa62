"""Each family's plain reference against ``omp.compile`` at a tiny size
on the CPU, and the control, one precision lower, failing the limit the
configuration sets.

The CPU multiplies float32 matrices in float32, so gemm is checked here
with ``matmul_operands`` stated as float32 (control: bfloat16); on the
TPU the configuration states bfloat16, JAX's default precision there
(control: float8)."""
import pytest

from bench import control, harness

JACOBI = {"N": 40}


def gemm_cpu():
    assumed = dict(harness.load_cell("gemm-xl.calls").cfg["assumed"],
                   matmul_operands="float32")
    return {"NI": 24, "NJ": 20, "NK": 28, "assumed": assumed}


CASES = [("jacobi2d-xl.stepped", JACOBI),
         ("jacobi2d-xl.stepped", dict(JACOBI, options={"lowering": "pallas"})),
         ("gemm-xl.calls", None)]


@pytest.mark.parametrize("workload,sizes", CASES,
                         ids=["jacobi2d", "jacobi2d-pallas", "gemm"])
def test_program_within_limit_and_control_beyond(workload, sizes):
    sizes = sizes or gemm_cpu()
    limit = harness.load_cell(workload).cfg["limits"]["max_rel_err"]
    rows = list(control.readings(workload, [3, 2 ** 31 + 11], 0.05,
                                 require_tpu=False, sizes=sizes))
    for row in rows:
        assert row["program"] <= limit < row["control"], row


def test_seed_makes_the_data():
    import jax.numpy as jnp

    cell = harness.load_cell("jacobi2d-xl.stepped", JACOBI)
    make = lambda s: cell.program.make_inputs(cell.cfg, harness.seed_key(s))
    same, other, far = make(7), make(7), make(2 ** 40 + 7)
    assert all(jnp.array_equal(same[k], other[k]) for k in same)
    assert not jnp.array_equal(same["a"], far["a"])
