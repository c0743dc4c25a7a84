"""Every cell of BENCHMARK.json resolves by name to its files, and the file
keeps the benchmark's own rules of shape."""

import json
import re

import pytest

from bench.lib import harness
from bench.tests.tiny_cells import ROOT

BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
CELLS = [w["name"] for w in BENCH["workloads"]]
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


@pytest.mark.parametrize("cell", CELLS)
def test_cell_resolves_to_its_files(cell):
    c = harness.resolve(ROOT, cell)
    assert c["system"].is_file()
    assert (ROOT / "bench" / "traffic"
            / f"{c['cell']['traffic']}.json").is_file()
    assert {m["name"] for m in c["end_to_end"]} >= {"setup_s"}
    assert len(c["end_to_end"]) >= 2 and c["per_layer"]
    for name, path in c["readers"].items():
        assert callable(harness.load_module(path).read), name
    conf = next(x for x in BENCH["configs"] if x["name"] == c["cell"]["config"])
    assert (ROOT / conf["file"]).is_file()
    assert json.loads((ROOT / conf["file"]).read_text()) == c["config"]


def test_unknown_cell_is_refused():
    with pytest.raises(KeyError):
        harness.resolve(ROOT, "no.such.cell")


def test_names_units_and_metric_cells():
    metrics = BENCH["end_to_end"] + BENCH["per_layer"]
    names = ([m["name"] for m in metrics] + CELLS
             + [c["name"] for c in BENCH["configs"]])
    assert len(set(names)) == len(names)
    assert all(NAME.match(n) for n in names)
    assert all(re.match(r"^[A-Za-z0-9_/%.-]{1,16}$", m["unit"])
               for m in metrics)
    e2e = {m["name"]: set(m.get("workloads", CELLS))
           for m in BENCH["end_to_end"]}
    assert e2e["setup_s"] == set(CELLS)
    for m in BENCH["per_layer"]:
        # a per-layer metric's cells all report what it moves
        assert set(m["workloads"]) <= e2e[m["moves"]], m["name"]
        if m["name"].endswith("_roofline") or "mfu" in m["name"]:
            assert m["unit"] == "%"
    for cell in CELLS:
        assert len([n for n, ws in e2e.items() if cell in ws]) >= 2


@pytest.mark.parametrize("kernel", ["paged_decode", "flash_prefill"])
def test_kernel_files_resolve(kernel):
    k = harness.load_module(ROOT / "bench" / "kernels" / f"{kernel}.py")
    assert k.PROGRAM and k.OP and callable(k.work)
