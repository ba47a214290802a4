"""The benchmark's own trace-reduction tests (``benchmarks/tests/
test_trace_reduction.py``: the sweep of PR 37 against the walk it replaced,
61 cases) under ``testpaths``, and what ``benchmarks/run.py`` promises of
every cell: each is data found by name."""
import importlib
import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmarks")
for _p in (os.path.join(BENCH, "tests"), ROOT):
    if _p not in sys.path:
        sys.path.insert(0, _p)

import test_trace_reduction as _reduction  # noqa: E402

# collected here as they are there: the functions carry their own
# parametrisation
globals().update({k: v for k, v in vars(_reduction).items()
                  if k.startswith("test_")})

from benchmarks import run as harness  # noqa: E402


def _bench():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


@pytest.mark.parametrize("cell", [w["name"] for w in _bench()["workloads"]])
def test_every_cell_finds_its_files_by_name(cell):
    """What ``run.py`` ``prepare`` and ``read_layer_metrics`` look up for a
    cell, without a device: the configuration and its family's modules,
    the traffic and its kind, the limits, a reader for every per-layer
    metric that lists the cell, and an end-to-end metric besides
    ``setup_s``."""
    bench = _bench()
    found, _, rehearsal = harness.find_cell(cell)
    assert found["name"] == cell and not rehearsal
    names = [c["name"] for c in bench["configs"]]
    entry = bench["configs"][names.index(found["config"])]
    cfg = harness.load_json(ROOT, entry["file"])
    assert entry["file"] == f"benchmarks/configs/{found['config']}/config.json"
    assert cfg["source"] == entry["source"]
    assert sorted(cfg["reduced"]) == sorted(entry["reduced"])
    for k in ("build", "weights", "reference", "flops"):
        mod = importlib.import_module(f"benchmarks.families.{cfg['family']}.{k}")
        assert mod is not None
    fam = f"benchmarks.families.{cfg['family']}"
    assert callable(importlib.import_module(fam + ".build").build)
    assert callable(importlib.import_module(fam + ".weights").make)
    assert callable(importlib.import_module(fam + ".flops").forward_flops_per_token)
    traffic = harness.load_json(BENCH, "traffic", found["traffic"] + ".json")
    kind = importlib.import_module("benchmarks.kinds." + traffic["kind"])
    assert callable(kind.run)
    if traffic["kind"] != "fit_cycle":
        ref = importlib.import_module(fam + ".reference")
        assert callable(ref.token_gaps) and callable(ref.CONTROL)
    limits = harness.load_json(BENCH, "limits", cell + ".json")
    assert limits and all(isinstance(v, (int, float)) for v in limits.values())
    mine = [m for m in bench["per_layer"]
            if cell in m.get("workloads", [cell])]
    assert mine
    for m in mine:
        assert callable(harness.load_reader(m["name"]).read)
    e2e = [m["name"] for m in bench["end_to_end"]
           if cell in m.get("workloads", [cell])]
    assert "setup_s" in e2e and len(e2e) >= 2
    for m in mine:
        assert m["moves"] in e2e, (m["name"], m["moves"])
