"""One run of one cell: set-up, the measured window, the check.

``run.py`` is the command; ``control.py`` reads the limits' readings
with the same set-up and window.  Everything that belongs to one
configuration, traffic mix or metric is found by its name in
``BENCHMARK.json``: ``configs/<config>.json``, ``programs/<family>.py``,
``reference/<family>.py``, ``traffic/<mix>.json`` and
``metrics/<metric>.py``.  Import JAX only after :func:`set_cache_env`.
"""
from __future__ import annotations

import collections
import dataclasses
import importlib
import json
import os
import shutil
import statistics
import sys
import tempfile
import time
from typing import Any, Callable

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
CACHE_DIR = os.path.join(ROOT, ".jax_cache")

# Seconds of the window that a ``--trace 1`` run records.
TRACE_SECONDS = 0.5
# JAX monitoring events that mean a program was traced for a compile.
COMPILE_EVENTS = frozenset({
    "/jax/compilation_cache/compile_requests_use_cache",
    "/jax/core/compile/backend_compile_duration"})


class NoChip(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell asks for."""


def set_cache_env() -> None:
    """Keep JAX's compile cache in ``<checkout>/.jax_cache`` and libtpu's
    logs off disk.  Call before JAX is imported."""
    os.environ["JAX_COMPILATION_CACHE_DIR"] = CACHE_DIR
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    src = os.path.join(ROOT, "src")
    if src not in sys.path:
        sys.path.insert(0, src)


def load_json(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


@dataclasses.dataclass
class Cell:
    name: str
    cfg: dict
    traffic: dict
    program: Any            # programs/<family>.py
    reference: Any          # reference/<family>.py
    end_to_end: list
    per_layer: list


def _applies(metric: dict, cell: str, reported: set | None = None) -> bool:
    if "workloads" in metric:
        return cell in metric["workloads"]
    return reported is None or metric["moves"] in reported


def load_cell(workload: str, sizes: dict | None = None) -> Cell:
    """The cell named ``workload`` in ``BENCHMARK.json``; ``sizes``
    replaces keys of its configuration (tests run tiny sizes)."""
    bench = load_json(ROOT, "BENCHMARK.json")
    entry = {w["name"]: w for w in bench["workloads"]}.get(workload)
    if entry is None:
        raise SystemExit(f"no workload {workload!r} in BENCHMARK.json")
    spec = {c["name"]: c for c in bench["configs"]}[entry["config"]]
    cfg = dict(load_json(ROOT, spec["file"]), **(sizes or {}))
    e2e = [m for m in bench["end_to_end"] if _applies(m, workload)]
    reported = {m["name"] for m in e2e}
    return Cell(
        name=workload, cfg=cfg,
        traffic=load_json(BENCH, "traffic", entry["traffic"] + ".json"),
        program=importlib.import_module(f"bench.programs.{cfg['family']}"),
        reference=importlib.import_module(
            f"bench.reference.{cfg['family']}"),
        end_to_end=e2e,
        per_layer=[m for m in bench["per_layer"]
                   if _applies(m, workload, reported)])


def seed_key(seed: int):
    """A PRNG key for any whole ``seed``: the low 32 bits make the key,
    the rest is folded in, so seeds past 2**32 stay distinct."""
    import jax
    seed %= 2 ** 64
    return jax.random.fold_in(jax.random.key(seed & 0xFFFFFFFF), seed >> 32)


def cell_devices(cell: Cell, require_tpu: bool = True) -> list:
    import jax
    devs = jax.devices()
    if require_tpu and devs[0].platform != "tpu":
        raise NoChip(f"no TPU: JAX found {devs[0].platform}")
    if len(devs) < cell.cfg["chips"]:
        raise NoChip(f"cell {cell.name} needs {cell.cfg['chips']} chips, "
                     f"JAX found {len(devs)}")
    return devs


class CompileCounter:
    """Counts JAX's compile events while ``active``."""

    def __init__(self):
        import jax
        self.active = False
        self.count = 0
        jax.monitoring.register_event_listener(self._event)
        jax.monitoring.register_event_duration_secs_listener(self._event)

    def _event(self, event, *_args, **_kw):
        if self.active and event in COMPILE_EVENTS:
            self.count += 1


@dataclasses.dataclass
class Setup:
    """What set-up leaves for the window: the mesh, the data and the
    compiled call, with the seconds of its parts."""
    mesh: Any
    sharding: Any
    make_env: Callable
    call: Callable
    plan_s: float
    xla_compile_s: float


def build(cell: Cell, devices: list) -> Setup:
    """Plan with ``omp.compile`` and compile one ``jax.jit`` of the
    call, for the cell's mesh and shapes."""
    import jax
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec

    from repro import omp

    cfg = cell.cfg
    shape = tuple(cfg["mesh"]["shape"])
    mesh = Mesh(np.array(devices[:int(np.prod(shape))]).reshape(shape),
                tuple(cfg["mesh"]["axes"]))
    sharding = NamedSharding(mesh, PartitionSpec())
    make_env = jax.jit(lambda key: cell.program.make_inputs(cfg, key),
                       out_shardings=sharding)
    like = jax.eval_shape(make_env, seed_key(0))
    like = {k: jax.ShapeDtypeStruct(v.shape, v.dtype, sharding=sharding)
            for k, v in like.items()}
    t0 = time.perf_counter()
    compiled = omp.compile(cell.program.build(cfg), mesh, env_like=like,
                           **cfg["options"])
    t1 = time.perf_counter()
    call = jax.jit(lambda env: compiled(env), in_shardings=sharding,
                   out_shardings=sharding).lower(like).compile()
    t2 = time.perf_counter()
    return Setup(mesh, sharding, make_env, call, t1 - t0, t2 - t1)


@dataclasses.dataclass
class Window:
    calls: int
    seconds: float
    latencies: list          # seconds per call, where the mix waits on each
    kept: list               # (inputs, outputs) of the calls to check


def drive(call, env, traffic: dict, seconds: float, keep: int,
          min_calls: int = 1) -> Window:
    """Call ``call`` in a closed loop for ``seconds`` as ``traffic``
    says, and return when the last call has completed.

    ``feedback``: each call's outputs are the next call's inputs.
    ``wait`` = ``"each"``: every call is waited for and timed; otherwise
    up to ``max_in_flight`` calls are queued ahead.  The inputs and
    outputs of call ``keep`` and of the last call are kept."""
    import jax
    from jax.profiler import TraceAnnotation

    each = traffic["wait"] == "each"
    depth = traffic.get("max_in_flight", 1)
    pending = collections.deque()
    latencies, kept = [], []
    calls = 0
    t0 = time.perf_counter()
    while True:
        t = time.perf_counter()
        if t - t0 >= seconds and calls >= max(min_calls, keep + 1):
            break
        with TraceAnnotation("bench.call"):
            out = call(env)
        if calls == keep:
            kept.append((env, out))
        if each:
            with TraceAnnotation("bench.block"):
                jax.block_until_ready(out)
            latencies.append(time.perf_counter() - t)
        else:
            pending.append(out)
            if len(pending) > depth:
                with TraceAnnotation("bench.block"):
                    jax.block_until_ready(pending.popleft())
        last_in = env
        if traffic["feedback"]:
            env = {**env, **out}
        calls += 1
    with TraceAnnotation("bench.block"):
        jax.block_until_ready(out)
    elapsed = time.perf_counter() - t0
    if calls - 1 != keep:
        kept.append((last_in, out))
    return Window(calls, elapsed, latencies, kept)


def rel_err(out: dict, ref: dict) -> float:
    """Largest ``max|out - ref| / max|ref|`` over the output arrays
    (NaN where any output is not finite, or the two name different
    arrays)."""
    import jax
    import jax.numpy as jnp

    if set(out) != set(ref):
        return float("nan")

    @jax.jit
    def errs(o, r):
        return jnp.stack([
            jnp.where(jnp.all(jnp.isfinite(o[k])),
                      jnp.max(jnp.abs(o[k] - r[k])) / jnp.max(jnp.abs(r[k])),
                      jnp.nan) for k in sorted(r)])
    values = [float(e) for e in errs(out, ref)]
    return float("nan") if any(v != v for v in values) else max(values)


def check_calls(cell: Cell, kept: list, fn=None) -> list:
    """``rel_err`` of each kept call against the reference on that
    call's own inputs; with ``fn`` (e.g. the control) in the program's
    place, that of ``fn`` instead."""
    import jax
    ref = jax.jit(lambda e: cell.reference.reference(cell.cfg, e))
    other = fn and jax.jit(lambda e: fn(cell.cfg, e))
    errs = []
    for env, out in kept:
        errs.append(rel_err(other(env) if fn else out, ref(env)))
    return errs


def peaks(kind: str) -> dict:
    table = load_json(BENCH, "peaks.json")["devices"]
    if kind not in table:
        raise KeyError(f"no peaks for device kind {kind!r} in peaks.json")
    return table[kind]


def memory_peak(devices) -> int | None:
    stats = [d.memory_stats() for d in devices]
    vals = [s.get("peak_bytes_in_use") for s in stats if s]
    return max(vals) if vals else None


@dataclasses.dataclass
class Reading:
    """What the metric readers see of one run."""
    cell: Cell
    setup_s: float
    plan_s: float
    xla_compile_s: float
    window: Window
    work: dict
    peaks: dict
    chips: int
    trace: Any = None        # trace.Summary of a --trace 1 run


def read_metrics(specs: list, reading: Reading) -> dict:
    out = {}
    for m in specs:
        reader = importlib.import_module(f"bench.metrics.{m['name']}")
        value = reader.read(reading)
        if value is not None:
            out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out


def measure(workload: str, seed: int, seconds: float, trace: bool, *,
            t_start: float, require_tpu: bool = True,
            sizes: dict | None = None, wrap=None) -> tuple[dict, dict]:
    """One run of a cell: the result line as a dict, and what else it
    learned (seconds of the check, the calls checked and their errors).

    ``t_start`` is the clock when the process began its set-up.  Tests
    pass ``require_tpu=False``, tiny ``sizes`` and ``wrap``, which
    replaces the compiled call (a planted fault)."""
    import jax
    import numpy as np
    from jax.profiler import TraceAnnotation

    from bench import trace as trace_mod

    cell = load_cell(workload, sizes)
    devices = cell_devices(cell, require_tpu)
    kind = devices[0].device_kind
    chip_peaks = peaks(kind) if require_tpu else {}
    counter = CompileCounter()
    with TraceAnnotation("bench.setup"):
        setup = build(cell, devices)
        call = wrap(cell.cfg, setup.call) if wrap else setup.call
        env = setup.make_env(seed_key(seed))
        warm = drive(call, env, cell.traffic, 0.0, keep=-1,
                     min_calls=cell.traffic["warmup_calls"])
        env = {**env, **warm.kept[-1][1]} if cell.traffic["feedback"] \
            else env
        del warm
    setup_s = time.perf_counter() - t_start
    used = list(setup.mesh.devices.flat)
    keep = int(np.random.default_rng(seed % 2 ** 64).integers(
        0, cell.traffic["check_sample_from"]))

    if trace:
        trace_dir = tempfile.mkdtemp(prefix="bench-trace-")
        jax.profiler.start_trace(trace_dir)
    counter.active = True
    window = drive(call, env, cell.traffic,
                   min(seconds, TRACE_SECONDS) if trace else seconds, keep)
    counter.active = False
    summary = None
    if trace:
        jax.profiler.stop_trace()
        try:
            summary = trace_mod.summarize(
                trace_mod.load(trace_dir), [d.id for d in used],
                window.seconds)
        finally:
            shutil.rmtree(trace_dir, ignore_errors=True)
    peak = memory_peak(used)
    del env

    t0 = time.perf_counter()
    with TraceAnnotation("bench.compare"):
        errs = check_calls(cell, window.kept)
    check_s = time.perf_counter() - t0
    limit = cell.cfg["limits"]["max_rel_err"]
    failed = sum(1 for e in errs if not e <= limit)
    worst = float("nan") if any(e != e for e in errs) else max(errs)
    checks = {"max_rel_err": {"value": worst, "limit": limit},
              "window_compile_events": {"value": counter.count, "limit": 0}}
    correct = failed == 0 and counter.count == 0

    reading = Reading(cell, setup_s, setup.plan_s, setup.xla_compile_s,
                      window, cell.program.min_work(cell.cfg), chip_peaks,
                      len(used), summary)
    metrics = read_metrics(cell.per_layer if trace else cell.end_to_end,
                           reading)
    device = {"platform": devices[0].platform, "kind": kind,
              "count": len(devices), "memory_peak_bytes": peak}
    result = {"correct": correct, "attempted": window.calls,
              "failed": failed, "metrics": metrics, "device": device}
    if summary is not None:
        device["busy_s"] = summary.busy_s
        device["window_s"] = summary.window_s
        result["breakdown"] = summary.breakdown()
    result["checks"] = checks
    info = {"setup_s": setup_s, "check_s": check_s,
            "checked_calls": [keep, window.calls - 1], "errs": errs}
    return result, info


def quantile(values: list, q: float) -> float:
    """The ``q`` quantile of ``values`` by ``statistics.quantiles``."""
    if len(values) < 2:
        return values[0]
    cuts = statistics.quantiles(values, n=100, method="inclusive")
    return cuts[round(q * 100) - 1]
