"""The generated program names its parts in the compiled HLO, and the
compiler times its passes and executor entries.

Each executor path is compiled at a tiny size and the ``omp.`` scopes
that ``jax.named_scope`` left in the optimized HLO's ``op_name``
metadata are checked: the rank-1 and rank-2 fused regions, the rank-1
and rank-2 collective blocks, a Pallas span in interpret mode, and on
four virtual devices the 2x2 region (with its exchanges and exit
all-gathers), the staged region and master/worker.
"""
import re
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh

from repro import omp

OP_NAME = re.compile(r'op_name="([^"]*)"')
ALL_GATHER = re.compile(r'= \S+ all-gather\(.*op_name="([^"]*)"')


def hlo_text(compiled, env) -> str:
    return jax.jit(lambda e: compiled(e)).lower(env).compile().as_text()


def hlo_scopes(compiled, env) -> set:
    """The ``omp.`` path components of every op of the optimized HLO."""
    return {part for path in OP_NAME.findall(hlo_text(compiled, env))
            for part in path.split("/") if part.startswith("omp.")}


def mesh(shape, axes):
    n = int(np.prod(shape))
    return Mesh(np.array(jax.devices()[:n]).reshape(shape), axes)


def chain(n=32):
    """A rank-1 region: an elementwise loop, a stencil over its output,
    a sum, and a loop whose output leaves the region as slabs."""
    @omp.parallel_for(stop=n, name="scale")
    def scale(i, env):
        return {"b": omp.at(i, env["a"][i] * 2.0)}

    @omp.parallel_for(start=1, stop=n - 1, name="smooth")
    def smooth(i, env):
        return {"c": omp.at(i, env["b"][i - 1] + env["b"][i + 1])}

    @omp.parallel_for(stop=n, reduction={"tot": "+"}, name="total")
    def total(i, env):
        return {"tot": omp.red(env["c"][i])}

    @omp.parallel_for(start=1, stop=n, name="tail")
    def tail(i, env):
        return {"d": omp.at(i, env["a"][i] + 1.0)}

    env = {"a": jnp.arange(n, dtype=jnp.float32), "b": jnp.zeros(n),
           "c": jnp.zeros(n), "d": jnp.zeros(n), "tot": jnp.float32(0)}
    return omp.region(scale, smooth, total, tail, name="chain"), env


def grid(n=24):
    """A rank-2 region of two ping-pong 5-point sweeps."""
    def sweep(src, dst, name):
        @omp.parallel_for(start=(1, 1), stop=(n - 1, n - 1), collapse=2,
                          name=name)
        def body(i, j, env):
            a = env[src]
            return {dst: omp.at((i, j), 0.2 * (
                a[i, j] + a[i - 1, j] + a[i + 1, j] + a[i, j - 1]
                + a[i, j + 1]))}
        return body

    env = {"a": jnp.ones((n, n), jnp.float32),
           "b": jnp.zeros((n, n), jnp.float32)}
    return omp.region(sweep("a", "b", "sw1"), sweep("b", "a", "sw2"),
                      name="grid"), env


def test_rank1_region_names_its_parts():
    reg, env = chain()
    got = hlo_scopes(omp.compile(reg, mesh((1,), ("data",))), env)
    assert {"omp.region.chain", "omp.entry", "omp.stage.scale",
            "omp.stage.smooth", "omp.stage.total", "omp.exit"} <= got
    assert not any(s.startswith("omp.block.") for s in got)


def test_rank2_region_on_one_chip_names_its_parts():
    reg, env = grid()
    got = hlo_scopes(omp.compile(reg, mesh((1, 1), ("i", "j"))), env)
    assert {"omp.region.grid", "omp.entry", "omp.stage.sw1",
            "omp.stage.sw2", "omp.exit"} <= got


def test_blocks_name_their_parts():
    n = 16

    @omp.parallel_for(start=1, stop=n - 1, reduction={"s": "+"}, name="dot")
    def dot(i, env):
        return {"y": omp.at(i, env["x"][i + 1] * 3.0),
                "s": omp.red(env["x"][i])}

    @omp.parallel_for(start=(1, 0), stop=(n, n), collapse=2, name="outer")
    def outer(i, j, env):
        return {"m": omp.at((i, j), env["x"][i - 1] * env["x"][j])}

    env = {"x": jnp.arange(n, dtype=jnp.float32), "y": jnp.zeros(n),
           "s": jnp.float32(0), "m": jnp.zeros((n, n))}
    got = hlo_scopes(omp.compile(dot, mesh((1,), ("data",)),
                                 shard="slice"), env)
    assert {"omp.block.dot", "omp.entry", "omp.stage.dot", "omp.exit",
            "omp.combine"} <= got
    got2 = hlo_scopes(omp.compile(outer, mesh((1, 1), ("i", "j"))), env)
    assert {"omp.block.outer", "omp.stage.outer", "omp.exit"} <= got2


def test_pallas_span_is_named_like_its_kernel():
    reg, env = grid(16)
    compiled = omp.compile(reg, mesh((1, 1), ("i", "j")), env_like=env,
                           lowering="pallas")
    spans = compiled.kernel_plan.spans
    got = hlo_scopes(compiled, env)
    for span in spans:
        assert "omp.kernel." + "_".join(span.stage_names) in got
    assert "omp.stage.sw1" in got


MESH_CHILD = """
import json, sys
sys.path.insert(0, {tests!r})
import jax, jax.numpy as jnp
from repro import omp
import test_scopes as t

reg, env = t.grid(96)          # large enough that halos beat gathers
text = t.hlo_text(omp.compile(reg, t.mesh((2, 2), ("i", "j"))), env)
out = {{"region2x2": sorted({{p for path in t.OP_NAME.findall(text)
                             for p in path.split("/")
                             if p.startswith("omp.")}}),
        "exit_gathers": t.ALL_GATHER.findall(text)}}
reg1, env1 = t.chain(64)
m4 = t.mesh((4,), ("data",))
out["staged"] = sorted(t.hlo_scopes(
    omp.compile(reg1, m4, lowering="collective"), env1))
out["master_worker"] = sorted(t.hlo_scopes(
    omp.compile(reg1.stages[0], m4, lowering="master_worker"), env1))
print(json.dumps(out))
"""


def test_mesh_paths_name_their_parts(multidevice):
    import json
    import os

    got = json.loads(multidevice(
        MESH_CHILD.format(tests=os.path.dirname(__file__)),
        n_devices=4).strip().splitlines()[-1])
    region = set(got["region2x2"])
    assert {"omp.region.grid", "omp.entry", "omp.stage.sw1",
            "omp.stage.sw2", "omp.exit"} <= region
    assert any(s.startswith("omp.exchange.") for s in region), region
    assert got["exit_gathers"] and all(
        "/omp.exit/" in p for p in got["exit_gathers"])
    staged = set(got["staged"])
    assert {"omp.region.chain", "omp.block.scale", "omp.block.smooth",
            "omp.stage.smooth", "omp.combine"} <= staged
    mw = set(got["master_worker"])
    assert {"omp.block.scale", "omp.entry", "omp.stage.scale",
            "omp.exit"} <= mw


def test_pass_seconds_and_executor_entries():
    reg, env = chain()
    m = mesh((1,), ("data",))
    omp.clear_compile_cache()
    t0 = time.perf_counter()
    compiled = omp.compile(reg, m, env_like=env)
    wall = time.perf_counter() - t0
    passes = compiled.passes
    assert [p.name for p in passes] == ["analyze", "schedule", "plan",
                                        "plan_comm", "schedule_comm",
                                        "lower"]
    assert all(p.seconds >= 0 for p in passes)
    assert 0 < sum(p.seconds for p in passes) <= wall
    assert "ms)" in passes[0].describe()

    # a cache hit reuses the build's records, seconds and all
    again = omp.compile(reg, m, env_like=env)
    assert again.cache_hit
    assert [p.seconds for p in again.passes[:-1]] == \
        [p.seconds for p in passes[:-1]]


@omp.parallel_for(stop=8, name="twice")
def twice(i, env):
    return {"y": omp.at(i, env["x"][i] * 2.0)}


def test_executor_entries_are_counted():
    env = {"x": jnp.arange(8, dtype=jnp.float32), "y": jnp.zeros(8)}
    compiled = omp.compile(twice, mesh((1,), ("data",)), env_like=env)
    before = omp.timing_stats()
    assert compiled.executor_runs == 0
    call = jax.jit(lambda e: compiled(e)).lower(env).compile()
    assert compiled.executor_runs == 1 and compiled.executor_seconds > 0
    call(env)
    assert compiled.executor_runs == 1       # the compiled call: no entry
    compiled(env)
    compiled(env)
    assert compiled.executor_runs == 3
    after = omp.timing_stats()
    assert after["executor_runs"] - before["executor_runs"] == 3
    assert after["executor_seconds"] - before["executor_seconds"] \
        == pytest.approx(compiled.executor_seconds, rel=1e-9)
