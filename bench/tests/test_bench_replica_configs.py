"""A replica configuration names its reference, and the reference module
holds all that depends on the model's architecture: so a second
architecture lands as new files and entries, with no existing file of
``bench/`` edited."""

import json
import shutil
from types import SimpleNamespace

import pytest

from bench.lib import harness
from bench.tests.tiny_cells import ROOT, run_tiny, tiny_cell

BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
REPLICA_CONFIGS = [c for c in BENCH["configs"]
                   if json.loads((ROOT / c["file"]).read_text())["system"]
                   == "replica"]
INTERFACE = ("WIDTHS", "STRUCTURE", "TINY", "program_params",
             "reference_logits", "served_gaps")
SEED = 2 ** 31 + 161


@pytest.mark.parametrize("conf", REPLICA_CONFIGS, ids=lambda c: c["name"])
def test_replica_config_names_a_reference_with_the_interface(conf):
    cell = next(w["name"] for w in BENCH["workloads"]
                if w["config"] == conf["name"])
    path = harness.resolve(ROOT, cell)["reference"]
    assert path.parent == ROOT / "bench" / "reference"
    ref = harness.load_module(path)
    for name in INTERFACE:
        assert hasattr(ref, name), name
    model = json.loads((ROOT / conf["file"]).read_text())["model"]
    assert set(ref.WIDTHS) <= set(model)
    assert set(ref.TINY) <= set(ref.WIDTHS)


def _root(tmp_path, reference: str, config=None):
    """A checkout of the benchmark's files under ``tmp_path`` with one more
    replica configuration (a copy of starcoder2-3b's, naming ``reference``)
    and one more cell, ``new.chat``; the repo's files are copied as they
    are."""
    root = tmp_path / "root"
    for d in ("configs", "traffic", "reference"):
        shutil.copytree(ROOT / "bench" / d, root / "bench" / d,
                        ignore=shutil.ignore_patterns("__pycache__"))
    (root / "bench" / "systems").mkdir()
    shutil.copy(ROOT / "bench" / "systems" / "replica.py",
                root / "bench" / "systems")
    conf = dict(json.loads((root / "bench" / "configs"
                            / "starcoder2-3b.json").read_text()),
                reference=reference, **(config or {}))
    (root / "bench" / "configs" / "new-arch.json").write_text(
        json.dumps(conf))
    bench = json.loads(json.dumps(BENCH))
    bench["configs"].append(dict(bench["configs"][0], name="new-arch",
                                 file="bench/configs/new-arch.json"))
    bench["workloads"].append(dict(bench["workloads"][0], name="new.chat",
                                   config="new-arch"))
    next(m for m in bench["end_to_end"]
         if m["name"] == "itl_p95_ms")["workloads"].append("new.chat")
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    return root


def test_missing_reference_is_refused(tmp_path):
    root = _root(tmp_path, "no_such_reference")
    with pytest.raises(FileNotFoundError):
        harness.resolve(root, "new.chat")
    args = SimpleNamespace(workload="new.chat", rate=None)
    assert harness.run_cell(root, args) == 3
    assert harness.resolve(root, "sc2.chat.steady")["reference"].is_file()


def _new_reference(root, structure=None):
    """A second reference: ``dense_decoder.py`` copied under another name,
    with tiny widths of its own and, given, another structure."""
    src = (root / "bench" / "reference" / "dense_decoder.py").read_text()
    src += "\nTINY = dict(TINY, num_layers=3, d_ff=128)\n"
    if structure:
        src += f"STRUCTURE = dict(STRUCTURE, **{structure!r})\n"
    (root / "bench" / "reference" / "dense_copy.py").write_text(src)


def test_new_architecture_lands_as_new_files_and_entries(tmp_path):
    root = _root(tmp_path, "dense_copy")
    _new_reference(root)
    # every file of the repo's benchmark that the root holds is as it is
    for path in (root / "bench").rglob("*"):
        mine = ROOT / path.relative_to(root)
        if path.is_file() and mine.exists():
            assert path.read_bytes() == mine.read_bytes(), path
    bench = json.loads((root / "BENCHMARK.json").read_text())
    for key in ("configs", "workloads"):
        assert bench[key][:len(BENCH[key])] == BENCH[key]
    # the tiny widths are the new reference's own
    m = tiny_cell("new.chat", root)["config"]["model"]
    assert (m["num_layers"], m["d_ff"]) == (3, 128)
    line, _ = run_tiny("new.chat", SEED, 3.0, root=root)
    assert line["correct"] is True, line
    assert line["checks"]["max_logit_gap"]["value"] < 0.05
    assert set(line["metrics"]) == {"itl_p95_ms", "setup_s"}


def test_structure_is_the_named_references(tmp_path):
    root = _root(tmp_path, "dense_copy")
    _new_reference(root, structure={"mlp_type": "swiglu"})
    with pytest.raises(ValueError, match="mlp_type"):
        run_tiny("new.chat", SEED, 1.0, root=root)
