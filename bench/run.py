#!/usr/bin/env python3
"""Run one benchmark cell once, on the accelerator, and print one JSON line.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Everything a cell is made of is found by name: the cell in
``BENCHMARK.json``, its configuration in ``bench/configs/<config>.json``
(whose ``system`` names the code that runs it,
``bench/systems/<system>.py``, and whose ``reference``, where it has one,
names the plain reference, ``bench/reference/<reference>.py``), its traffic in
``bench/traffic/<traffic>.json``, each per-layer metric's reader in
``bench/metrics/<metric>.py`` and each kernel's work function in
``bench/kernels/<kernel>.py``.

A run makes its weights and inputs from ``--seed``, warms the cell's own
shapes (JAX's persistent compilation cache lives in ``<checkout>/.jax_cache``),
measures for ``--seconds``,
then checks what the timed path produced against the plain reference. With
``--trace 0`` it reports the cell's end-to-end metrics, with ``--trace 1``
its per-layer metrics, read from a profiler trace of part of the window.

It exits non-zero, printing no result, where JAX finds no TPU or fewer chips
than the cell asks for.

``--rate`` (the knee sweep only) replaces the mix's arrivals by a Poisson
process at that rate and adds the queue's length at the window's close to
the result.
``--control 1`` (the control runs only) puts the control in the program's
place in the comparison that decides ``correct``: the reference computed one
precision step below the configuration's (replica cells), or answers that
break the fleet's slot guarantee (fleet cells). Such a run has to come out
not correct.
"""

from __future__ import annotations

import argparse
import pathlib
import sys

ROOT = pathlib.Path(__file__).resolve().parents[1]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rate", type=float, default=None)
    ap.add_argument("--control", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if args.seed < 0 or args.seconds <= 0:
        ap.error("--seed must be >= 0 and --seconds > 0")
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    from bench.lib import harness

    return harness.run_cell(ROOT, args)


if __name__ == "__main__":
    sys.exit(main())
