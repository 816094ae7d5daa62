"""``BENCHMARK.json`` and the files it names; ``min_work`` of each
configuration.  No JAX device work: these run in a fraction of a
second."""
import ast
import importlib
import json
import os
import re

import pytest

from bench import harness

BENCH = harness.load_json(harness.ROOT, "BENCHMARK.json")
NAME = re.compile(r"[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}")


def _cfg(name):
    spec = {c["name"]: c for c in BENCH["configs"]}[name]
    return harness.load_json(harness.ROOT, spec["file"])


@pytest.mark.parametrize("name,flops,nbytes", [
    ("jacobi2d-xl", 5 * 2798 ** 2 * 4, 94_080_000),
    ("jacobi2d-xl-2x2", 5 * 2798 ** 2 * 4, 94_080_000),
    ("gemm-xl", 23_920_000_000 + 3 * 2000 * 2300, 81_520_000),
])
def test_min_work(name, flops, nbytes):
    cfg = _cfg(name)
    work = importlib.import_module(
        f"bench.programs.{cfg['family']}").min_work(cfg)
    assert work == {"flops": flops, "bytes": nbytes}


def test_every_name_resolves_to_its_files():
    names = ([c["name"] for c in BENCH["configs"]]
             + [w["name"] for w in BENCH["workloads"]]
             + [m["name"] for m in BENCH["end_to_end"] + BENCH["per_layer"]])
    assert len(names) == len(set(names))
    assert all(NAME.fullmatch(n) for n in names), names
    for w in BENCH["workloads"]:
        cell = harness.load_cell(w["name"])
        assert cell.cfg["chips"] == w["chips"]
        assert cell.cfg["name"] == w["config"]
        assert cell.end_to_end and cell.per_layer
        assert {"setup_s", "step_ms"} <= {m["name"] for m in cell.end_to_end}
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        assert callable(importlib.import_module(
            f"bench.metrics.{m['name']}").read)


def test_references_import_nothing_of_the_program():
    ref_dir = os.path.join(harness.BENCH, "reference")
    for fname in os.listdir(ref_dir):
        if not fname.endswith(".py"):
            continue
        with open(os.path.join(ref_dir, fname)) as f:
            tree = ast.parse(f.read())
        for node in ast.walk(tree):
            if isinstance(node, ast.Import):
                mods = [a.name for a in node.names]
            elif isinstance(node, ast.ImportFrom):
                mods = [node.module or ""]
            else:
                continue
            assert not any(m.split(".")[0] in ("repro", "bench")
                           for m in mods), (fname, mods)


def test_peaks_cover_the_chip():
    assert harness.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        harness.peaks("cpu")


def test_command_stays_inside_paths():
    assert BENCH["command"][1].startswith(BENCH["paths"][0] + "/")
    assert json.dumps(BENCH).count("..") == 0
