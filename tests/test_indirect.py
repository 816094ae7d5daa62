"""Reads through an index array (``x[idx[i]]``): Context Analysis calls
them ``ReadKind.INDIRECT`` and names the index buffer; the planner
replicates them as it does whole reads; the executor wraps the stage's
chunk compute in the device scope ``omp.indirect.<keys>`` inside its
``omp.stage.<name>`` and counts the stage and the index entries it
looks up (``Compiled.indirect_reads`` / ``indirect_elems``,
``omp.timing_stats()``)."""
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh

from repro import omp
from repro.core.context import ReadKind, analyze_context
from repro.core.nest import LoopNest

N, W = 16, 5
OP_NAME = re.compile(r'op_name="([^"]*)"')


def env_of():
    rng = np.random.default_rng(0)
    return {"x": jnp.asarray(rng.standard_normal(N), jnp.float32),
            "vals": jnp.asarray(rng.standard_normal((N, W)), jnp.float32),
            "cols": jnp.asarray(rng.integers(0, N, (N, W)), jnp.int32),
            "y": jnp.zeros(N, jnp.float32), "tot": jnp.float32(0)}


def reads(body):
    pf = omp.parallel_for(stop=N)(body)
    ctx = analyze_context(pf, env_of(), LoopNest.from_program(pf))
    return {k: (v.read.kind, v.read.index_key, v.read.index_elems)
            for k, v in ctx.vars.items()}


def test_gather_through_a_sliced_index_read_is_indirect():
    got = reads(lambda i, env: {"y": omp.at(i, jnp.sum(
        env["vals"][i] * env["x"][env["cols"][i]]) + env["x"][i])})
    assert got["x"] == (ReadKind.INDIRECT, "cols", W)
    assert got["cols"][0] == ReadKind.SLICED
    assert got["vals"][0] == ReadKind.SLICED


def test_scalar_read_through_one_index_entry_is_indirect():
    got = reads(lambda i, env: {"y": omp.at(
        i, env["x"][env["cols"][i][2]] + env["x"][env["cols"][i, 3]])})
    assert got["x"] == (ReadKind.INDIRECT, "cols", 2)


@pytest.mark.parametrize("key,value", [
    ("x", lambda i, env: jnp.sum(env["x"])),                 # whole array
    ("x", lambda i, env: env["x"][(i * 7) % N]),             # non-affine
    ("x", lambda i, env: env["x"][env["cols"][i, 0] + i]),   # index + i
    ("x", lambda i, env: env["x"][env["cols"][i, 0]] + jnp.max(env["x"])),
    ("cols", lambda i, env:                                  # through itself
        env["cols"][env["cols"][i, 0], 0].astype(jnp.float32)),
], ids=["sum", "strided", "index_plus_i", "indirect_and_whole", "self"])
def test_other_non_slice_uses_stay_whole(key, value):
    got = reads(lambda i, env: {"y": omp.at(i, value(i, env))})
    assert got[key] == (ReadKind.WHOLE, None, 0), got


def spmv_loop():
    @omp.parallel_for(stop=N, reduction={"tot": "+"}, name="spmv")
    def spmv(i, env):
        row = jnp.sum(env["vals"][i] * env["x"][env["cols"][i]])
        return {"y": omp.at(i, row), "tot": omp.red(env["x"][i] * row)}
    return spmv


def spmv_region():
    @omp.parallel_for(stop=N, name="scale")
    def scale(i, env):
        return {"y": omp.at(i, 2.0 * env["y"][i])}
    return omp.region(spmv_loop(), scale, name="cg")


def mesh1():
    return Mesh(np.array(jax.devices()[:1]), ("data",))


@pytest.mark.parametrize("make", [spmv_loop, spmv_region],
                         ids=["parallel_for", "region"])
def test_indirect_stage_scope_and_counters(make):
    env = env_of()
    compiled = omp.compile(make(), mesh1(), env_like=env)
    assert "indirect via cols" in compiled.report()
    before = omp.timing_stats()
    text = jax.jit(lambda e: compiled(e)).lower(env).compile().as_text()
    after = omp.timing_stats()
    paths = OP_NAME.findall(text)
    assert any("omp.stage.spmv/omp.indirect.x/" in p for p in paths)
    assert not any("omp.indirect." in p and "omp.stage.spmv/" not in p
                   for p in paths)
    assert (compiled.indirect_reads, compiled.indirect_elems) == (1, N * W)
    assert after["indirect_reads"] - before["indirect_reads"] == 1
    assert after["indirect_elems"] - before["indirect_elems"] == N * W
    out = jax.jit(lambda e: compiled(e))(env)
    want = make()(env)
    np.testing.assert_allclose(out["y"], want["y"], rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(out["tot"], want["tot"], rtol=1e-5)


def test_no_indirect_read_no_scope_no_count():
    @omp.parallel_for(stop=N, name="axpy")
    def axpy(i, env):
        return {"y": omp.at(i, env["x"][i] + env["vals"][i, 0])}

    env = env_of()
    compiled = omp.compile(axpy, mesh1(), env_like=env)
    text = jax.jit(lambda e: compiled(e)).lower(env).compile().as_text()
    assert "omp.indirect" not in text
    assert (compiled.indirect_reads, compiled.indirect_elems) == (0, 0)
    assert "indirect via" not in compiled.report()
