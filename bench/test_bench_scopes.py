"""``bench/scopes.py`` and the readers of the program's scopes and
timers, on small HLO texts and traces kept in ``bench/testdata``."""
import json
import os
import shutil
from types import SimpleNamespace

import pytest

from bench import breakdown, harness, scopes, trace
from bench.metrics import (collective_ms, exchange_ms, idle_share,
                           layout_ms, roofline_share, stage_ms, trace_s)

DATA = os.path.join(harness.BENCH, "testdata")

HLO = """\
HloModule jit__lambda, entry_computation_layout={(f32[8]{0})->f32[8]{0}}

%fused_computation (param_0: f32[8]) -> f32[8] {
  %param_0 = f32[8]{0} parameter(0)
  ROOT %add.1 = f32[8]{0} add(%param_0, %param_0), metadata={op_name="jit(f)/omp.region.r/shard_map/omp.stage.s1/add"}
}

%body (arg: (s32[], f32[8])) -> (s32[], f32[8]) {
  %arg = (s32[], f32[8]{0}) parameter(0)
  %gte = f32[8]{0} get-tuple-element(%arg), index=1
  %copy.3 = f32[8]{0} copy(%gte)
  %fusion.2 = f32[8]{0} fusion(%copy.3), kind=kLoop, calls=%fused_computation, metadata={op_name="jit(f)/omp.region.r/shard_map/omp.stage.s1/add"}
  ROOT %tuple = (s32[], f32[8]{0}) tuple(%gte, %fusion.2)
}

%cond (arg.1: (s32[], f32[8])) -> pred[] {
  %arg.1 = (s32[], f32[8]{0}) parameter(0)
  ROOT %lt = pred[] constant(true)
}

ENTRY %main.9 (p: f32[8]) -> f32[8] {
  %p = f32[8]{0} parameter(0)
  %slice.4 = f32[8]{0} slice(%p), slice={[0:8]}, metadata={op_name="jit(f)/omp.region.r/omp.entry/slice"}
  %while.5 = (s32[], f32[8]{0}) while(%t), condition=%cond, body=%body, metadata={op_name="jit(f)/omp.region.r/shard_map/omp.stage.s1/while"}
  %copy.6 = f32[8]{0} copy(%p)
  %all-gather.7 = f32[8]{0} all-gather(%copy.6), dimensions={0}, metadata={op_name="jit(f)/omp.region.r/omp.exit/reshape"}
  %bitcast.8 = f32[8]{0} bitcast(%p), metadata={op_name="jit(f)/omp.region.r/reshape"}
  ROOT %collective-permute-done.3 = f32[8]{0} copy(%p), metadata={op_name="jit(f)/omp.region.r/shard_map/omp.exchange.a+b/ppermute"}
}
"""

# the synthetic trace's ops (bench/testdata/trace_synthetic.json)
SYNTHETIC_SCOPES = {"fusion.1": "omp.stage.sweep1",
                    "omp_sweep1_sweep2": "omp.kernel.sweep1_sweep2",
                    "collective-permute-done.3": "omp.exchange.a",
                    "all-gather.2": "omp.exit"}


def _load(name):
    with open(os.path.join(DATA, name)) as f:
        return json.load(f)


def test_scope_of_takes_the_innermost_part():
    assert scopes.scope_of("jit(f)/omp.region.r/shard_map/omp.stage.s/"
                           "while/body/omp.kernel.s/pallas_call") \
        == "omp.kernel.s"
    assert scopes.scope_of("jit(f)/omp.block.b/reshape") == "omp.block.b"
    assert scopes.scope_of("jit(f)/omp.region.r/omp.block.b/add") \
        == "omp.block.b"
    assert scopes.scope_of("jit(f)/reshape") is None


def test_scope_map_reads_metadata_and_loop_bodies():
    smap = scopes.scope_map(HLO)
    assert smap["slice.4"] == "omp.entry"
    assert smap["while.5"] == "omp.stage.s1"
    # no metadata, inside the stage's loop body: the stage's
    assert smap["copy.3"] == "omp.stage.s1"
    assert smap["fusion.2"] == "omp.stage.s1"
    # no metadata at the top level: no scope
    assert smap["copy.6"] == scopes.NO_SCOPE
    assert smap["all-gather.7"] == "omp.exit"
    assert smap["bitcast.8"] == "omp.region.r"
    assert smap["collective-permute-done.3"] == "omp.exchange.a+b"
    assert scopes.scope_map("HloModule empty") == {}


def test_synthetic_trace_by_scope_sums_to_its_ops():
    s = trace.summarize(_load("trace_synthetic.json"), [0, 1], 2e-6)
    scope_s = scopes.scope_seconds(s.op_s, SYNTHETIC_SCOPES)
    assert sum(scope_s.values()) == pytest.approx(sum(s.op_s.values()))
    assert scope_s == pytest.approx({
        "omp.stage.sweep1": 200e-9, "omp.kernel.sweep1_sweep2": 150e-9,
        "omp.exchange.a": 25e-9, "omp.exit": 100e-9})
    # an op the map does not know counts, under no scope
    partial = scopes.scope_seconds(s.op_s, {"fusion.1": "omp.stage.s"})
    assert partial[scopes.NO_SCOPE] == pytest.approx(275e-9)
    assert scopes.top(scope_s, 2) == [["omp.stage.sweep1",
                                       pytest.approx(200e-9)],
                                      ["omp.kernel.sweep1_sweep2",
                                       pytest.approx(150e-9)]]


def _reading(summary, calls, name="cell"):
    return SimpleNamespace(trace=summary, window=SimpleNamespace(calls=calls),
                           cell=SimpleNamespace(name=name), chips=2,
                           work={"flops": 0, "bytes": 819e9 * 2 * 1e-7},
                           peaks={"flops_per_s": 197e12,
                                  "hbm_bytes_per_s": 819e9})


def test_scope_readers_on_the_synthetic_trace(monkeypatch):
    s = trace.summarize(_load("trace_synthetic.json"), [0, 1], 2e-6)
    r = _reading(s, 2)
    monkeypatch.setattr(scopes, "_RUN", {
        "cell": "cell", "smap": SYNTHETIC_SCOPES,
        "timing": {"pass_seconds": {}, "executor_runs": 1,
                   "executor_seconds": 0.25}})
    assert stage_ms.read(r) == pytest.approx(1e3 * 350e-9 / 2)
    assert layout_ms.read(r) == pytest.approx(1e3 * 100e-9 / 2)
    assert exchange_ms.read(r) == pytest.approx(1e3 * 25e-9 / 2)
    assert trace_s.read(r) == 0.25
    # the readers that were there read as they did
    assert collective_ms.read(r) == pytest.approx(1e3 * 125e-9 / 2)
    assert idle_share.read(r) == pytest.approx(100 * (1 - 475e-9 / 2e-6))
    assert roofline_share.read(r) == pytest.approx(
        100 * 1e-7 / (475e-9 / 2))


def test_scope_readers_find_nothing_without_scopes(monkeypatch):
    """A program that names no scope (and counts no executor entry)
    gives none of the metrics, and raises nothing."""
    s = trace.summarize(_load("trace_synthetic.json"), [0, 1], 2e-6)
    monkeypatch.setattr(scopes, "_RUN", {
        "cell": "cell", "smap": {"fusion.1": scopes.NO_SCOPE},
        "timing": None})
    r = _reading(s, 2)
    assert stage_ms.read(r) is None and layout_ms.read(r) is None
    assert exchange_ms.read(r) is None and trace_s.read(r) is None
    assert stage_ms.read(_reading(None, 2)) is None


def test_idle_gaps_are_named_by_runtime_events():
    t = {"devices": {"0": [["fusion.1", 0, 100], ["fusion.2", 300, 100],
                           ["fusion.3", 1000, 100]]},
         "host": []}
    runtime = [["PJRT_LoadedExecutable_Execute", 150, 100],
               ["ReadSyncFlag", 420, 500], ["CompleteCallbacks", 900, 80],
               ["tpu::System::Execute", 5000, 10]]
    got = scopes.idle_gap_host(t, runtime, 0)
    assert got == [["ReadSyncFlag", pytest.approx(500e-9),
                    pytest.approx(600e-9)],
                   ["PJRT_LoadedExecutable_Execute", pytest.approx(100e-9),
                    pytest.approx(200e-9)]]


def test_chip_trace_gaps_name_runtime_events(tmp_path):
    """The six gemm-xl calls of ``gemm_6calls.xplane.pb``: each long gap
    overlaps a runtime event of the host; the harness's readings of the
    same trace are unchanged."""
    shutil.copy(os.path.join(DATA, "gemm_6calls.xplane.pb"),
                tmp_path / "t.xplane.pb")
    t = trace.load(str(tmp_path))
    host = scopes.load_runtime(str(tmp_path), t["devices"])
    assert host["omp"] == []
    names = {n for n, _s, _d in host["runtime"]}
    assert "PJRT_LoadedExecutable_Execute" in names
    assert not any(n.startswith(("$", "bench.")) for n in names)
    gaps = scopes.idle_gap_host(t, host["runtime"], 0)
    s = trace.summarize(t, [0], 1.0)
    for (event, overlap, gap), (_label, length) in zip(gaps[:5], s.gaps):
        assert gap == pytest.approx(length)
        assert event is not None and 0 < overlap <= gap
    assert s.busy_s / 6 == pytest.approx(0.4557e-3, rel=1e-3)


def test_setup_phases_sum_to_setup_s():
    setup = SimpleNamespace(plan_s=0.05, xla_compile_s=1.5)
    timing = {"pass_seconds": {"analyze": 0.02, "plan": 0.01},
              "executor_runs": 1, "executor_seconds": 0.4}
    phases = breakdown.setup_phases(10.0, 13.0, 0.5, timing, setup, 0.2,
                                    0.3, 12.0)
    assert sum(s for _n, s in phases) == pytest.approx(12.0)
    got = dict(phases)
    assert got["runtime_init"] == 3.0 and got["executor_trace"] == 0.4
    assert got["xla_compile"] == pytest.approx(1.1)
    assert got["omp.compile other"] == pytest.approx(0.02)


def test_scope_map_of_a_cell_rebuilt_matches_its_first_build():
    """The readers build the cell's call again for its scope map: the
    instruction names must be those of the call the window ran."""
    cell = harness.load_cell("jacobi2d-xl.stepped", {"N": 40})
    devices = harness.cell_devices(cell, require_tpu=False)
    first = scopes.scope_map(harness.build(cell, devices).call.as_text())
    stages = [n for n, sc in first.items() if sc.startswith("omp.stage.")]
    entry = [n for n, sc in first.items() if sc == "omp.entry"]
    assert stages and entry
    ops = {stages[0]: 3e-3, entry[0]: 1e-3, "not-an-op": 5e-3}
    r = SimpleNamespace(cell=cell, window=SimpleNamespace(calls=2),
                        trace=SimpleNamespace(op_s=ops))
    scopes._RUN.clear()
    try:
        assert stage_ms.read(r) == pytest.approx(1.5)
        assert layout_ms.read(r) == pytest.approx(0.5)
        assert scopes._RUN["smap"] == first
    finally:
        scopes._RUN.clear()


def test_chip_trace_with_scopes(tmp_path):
    """Six gemm-xl calls traced on a TPU v5e with the program's scopes
    (``bench/breakdown.py --calls 6 --save``; the HLO metadata plane,
    which no reduction reads, left out), and the scope map of the ops in
    it: the scopes sum to the ops, the readers that were there read it
    as before, and each long gap is named by a runtime event."""
    shutil.copy(os.path.join(DATA, "gemm_6calls_scoped.xplane.pb"),
                tmp_path / "t.xplane.pb")
    smap = _load("gemm_6calls_scoped.scopes.json")
    t = trace.load(str(tmp_path))
    assert len(t["devices"][0]) == 462
    s = trace.summarize(t, [0], 1.0)
    scope_s = scopes.scope_seconds(s.op_s, smap)
    assert sum(scope_s.values()) == pytest.approx(sum(s.op_s.values()),
                                                  rel=1e-12)
    assert sum(scope_s.values()) == pytest.approx(s.busy_s, rel=1e-6)
    assert set(scope_s) == {"omp.stage.gemm", scopes.NO_SCOPE}
    assert 1e3 * scope_s["omp.stage.gemm"] / 6 == pytest.approx(0.3015,
                                                                rel=1e-3)
    assert scope_s[scopes.NO_SCOPE] / s.busy_s == pytest.approx(0.338,
                                                                abs=1e-3)
    r = SimpleNamespace(trace=s, window=SimpleNamespace(calls=6))
    assert collective_ms.read(r) is None
    assert s.busy_s / 6 == pytest.approx(0.4557e-3, rel=1e-3)

    host = scopes.load_runtime(str(tmp_path), t["devices"])
    gaps = scopes.idle_gap_host(t, host["runtime"], 0)
    assert [g[0] for g in s.gaps[:5]] == ["bench.block"] * 5
    assert [g[0] for g in gaps[:5]] == [
        "ReadSyncFlag", "ReadSyncFlag",
        "tpu::System::AllocateAndFillTupleIndexTable=>Done",
        "ReadSyncFlag", "ReadSyncFlag"]
    assert all(0 < g[1] <= g[2] for g in gaps[:5])
