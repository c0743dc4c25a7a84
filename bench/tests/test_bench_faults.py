"""A run drives the timed path and decides ``correct`` by the reference:
sound runs pass, and a run whose answers are altered where they are
produced comes out not correct. The command itself refuses to run where
there is no TPU, or where the system under test is missing."""

import os
import shutil
import subprocess
import sys

import numpy as np
import pytest

from bench.tests.tiny_cells import ROOT, run_tiny

SEED = 2 ** 31 + 77


def test_sound_replica_run_is_correct():
    line, out = run_tiny("sc2.chat.steady", SEED, 3.0)
    assert line["correct"] is True, line
    assert line["attempted"] >= 1 and line["failed"] == 0
    assert list(line)[-1] == "checks"
    assert line["checks"]["max_logit_gap"]["value"] < 0.05
    assert line["window_compiles"] == 0
    assert set(line["metrics"]) == {"itl_p95_ms", "setup_s"}


def test_replica_control_is_not_correct():
    line, _ = run_tiny("sc2.chat.steady", SEED, 3.0, control=True)
    gap = line["checks"]["max_logit_gap"]
    assert line["correct"] is False, line
    assert gap["value"] > gap["limit"] > line["program_max_logit_gap"]


def test_token_altered_where_produced_is_caught(monkeypatch):
    from repro.runtime.batching import ContinuousBatcher

    orig = ContinuousBatcher.step

    def bad_step(self):
        active = [req for _, req in self.slots.items()]
        n = orig(self)
        for req in active:  # each request's second token
            if len(req.tokens) == 2:
                req.tokens[-1] = (req.tokens[-1] + 64) % 128
        return n

    monkeypatch.setattr(ContinuousBatcher, "step", bad_step)
    line, _ = run_tiny("sc2.chat.steady", SEED, 3.0)
    assert line["correct"] is False, line


FLEET_CELLS = ["fleet.flash_crowd.point", "fleet.flash_crowd.cube"]


@pytest.mark.parametrize("cell", FLEET_CELLS)
def test_sound_fleet_runs_are_correct(cell):
    line, _ = run_tiny(cell, SEED, 1.0)
    assert line["correct"] is True, line
    assert line["checks"]["violations"]["value"] == 0
    assert set(line["metrics"]) == {"sim_req_per_s", "setup_s"}


@pytest.mark.parametrize("cell", FLEET_CELLS)
def test_fleet_control_is_not_correct(cell):
    line, _ = run_tiny(cell, SEED, 1.0, control=True)
    c = line["checks"]
    assert line["correct"] is False, line
    assert c["violations"]["value"] > 0
    assert c["wait_gap"]["value"] > c["wait_gap"]["limit"]


#: ticks by which the "delayed" fault shifts every request: ~36% of the
#: tiny point cell's mean wait (831 ticks in the reference)
DELAY = 300


@pytest.mark.parametrize("fault", ["late_start", "early_finish",
                                   "over_capacity", "delayed", "no_step"])
def test_fleet_answer_altered_where_produced_is_caught(fault):
    def alter(out):
        fin = np.asarray(out["finish"])
        i = int(np.argmax(fin >= 0))
        out = dict(out)
        if fault == "late_start":
            out["start"] = out["start"].at[i].add(1)
        elif fault == "early_finish":
            out["finish"] = out["finish"].at[i].add(-1)
        elif fault == "over_capacity":
            out["busy"] = out["busy"].at[7].set(out["cap"][7] + 1)
        elif fault == "delayed":  # every request later, its length kept
            for k in ("start", "finish"):
                out[k] = np.where(out[k] >= 0, out[k] + DELAY, out[k])
        else:  # the tick scan's step leaves its state as it was
            for k in ("start", "finish"):
                out[k] = np.full_like(np.asarray(out[k]), -1)
            out["busy"] = np.zeros_like(np.asarray(out["busy"]))
        return out

    line, _ = run_tiny("fleet.flash_crowd.point", SEED, 1.0, alter=alter)
    assert line["correct"] is False, line
    if fault == "delayed":  # the reference's trajectory tells it apart
        gap = line["checks"]["wait_gap"]
        assert gap["value"] > gap["limit"]


def test_half_the_grid_left_out_is_caught():
    def alter(out):  # the K=6 points answered by the K=12 ones
        return {k: v.at[0::2].set(v[1::2]) if getattr(v, "ndim", 0) else v
                for k, v in out.items()}

    line, _ = run_tiny("fleet.flash_crowd.cube", SEED, 1.0, alter=alter)
    assert line["correct"] is False, line


def _cli(cwd, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **(env_extra or {}))
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "sc2.chat.steady",
         "--seed", str(SEED), "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_no_tpu_no_result():
    r = _cli(ROOT)
    assert r.returncode != 0
    assert r.stdout.strip() == ""
    assert "TPU" in r.stderr


def test_benchmark_files_alone_do_not_run(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    r = _cli(tmp_path)
    assert r.returncode != 0
    assert r.stdout.strip() == ""
