"""``bench/trace.py`` and the trace metrics on small traces kept in
``bench/testdata``."""
import json
import os
from types import SimpleNamespace

import pytest

from bench import harness, trace
from bench.metrics import collective_ms, idle_share, roofline_share

DATA = os.path.join(harness.BENCH, "testdata")


def _load(name):
    with open(os.path.join(DATA, name)) as f:
        return json.load(f)


def test_synthetic_trace_reduces_exactly():
    s = trace.summarize(_load("trace_synthetic.json"), [0, 1], 2e-6)
    assert s.busy_s == pytest.approx((550 + 400) / 2 * 1e-9)
    assert s.op_s == pytest.approx({
        "fusion.1": 200e-9, "omp_sweep1_sweep2": 150e-9,
        "collective-permute-done.3": 25e-9, "all-gather.2": 100e-9})
    assert s.gaps[0] == ["bench.call", pytest.approx(350e-9)]
    assert [g[0] for g in s.gaps[1:]] == ["bench.block", "bench.block"]
    bd = s.breakdown()
    assert bd["device_ops"][0] == ["fusion.1", pytest.approx(200e-9)]
    assert len(bd["idle_gaps"]) == 3

    r = SimpleNamespace(trace=s, window=SimpleNamespace(calls=2), chips=2,
                        work={"flops": 0, "bytes": 819e9 * 2 * 1e-7},
                        peaks={"flops_per_s": 197e12,
                               "hbm_bytes_per_s": 819e9})
    assert collective_ms.read(r) == pytest.approx(1e3 * 125e-9 / 2)
    assert idle_share.read(r) == pytest.approx(100 * (1 - 475e-9 / 2e-6))
    assert roofline_share.bound(r) == (pytest.approx(1e-7), "bytes")
    assert roofline_share.read(r) == pytest.approx(
        100 * 1e-7 / (475e-9 / 2))


def test_trace_without_the_chip_is_refused():
    with pytest.raises(ValueError, match="chips \\[2\\]"):
        trace.summarize(_load("trace_synthetic.json"), [0, 2], 1.0)


def test_readers_find_nothing_without_their_ops():
    s = trace.summarize({"devices": {"0": [["fusion.1", 0, 10]]},
                         "host": []}, [0], 1e-6)
    r = SimpleNamespace(trace=s, window=SimpleNamespace(calls=1))
    assert collective_ms.read(r) is None


def test_chip_trace_of_six_gemm_calls(tmp_path):
    """A TPU v5e trace of six blocked gemm-xl calls, as the profiler
    wrote it."""
    (tmp_path / "t.xplane.pb").write_bytes(
        open(os.path.join(DATA, "gemm_6calls.xplane.pb"), "rb").read())
    t = trace.load(str(tmp_path))
    assert list(t["devices"]) == [0] and len(t["devices"][0]) == 462
    assert {n for n, _s, _d in t["host"]} == {"bench.call", "bench.block"}
    assert all(" = " not in n for n, _s, _d in t["devices"][0])
    s = trace.summarize(t, [0], 1.0)
    assert s.busy_s / 6 == pytest.approx(0.4557e-3, rel=1e-3)
    assert sum(s.op_s.values()) == pytest.approx(s.busy_s, rel=1e-6)
    assert s.gaps[0][0] == "bench.call" and s.gaps[0][1] > 1e-3
