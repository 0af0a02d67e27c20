#!/usr/bin/env python3
"""The benchmark's one command.

    python3 perfbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One process; it touches JAX itself and starts no other. The cell is looked up
in ``BENCHMARK.json``; its configuration (``perfbench/configs/``), traffic mix
(``perfbench/traffic/``), runner (``perfbench/runners/``, named by the
configuration) and per-layer readers (``perfbench/layer_metrics/``, named by
the metric) are found by name, so a new cell is new files and new entries.

Every line before the last is a progress note on stdout. The last line is the
result: ``correct``, ``attempted``, ``failed``, ``metrics``, ``device`` and,
in a traced run, ``breakdown``. Without a TPU, or with fewer chips than the
cell asks for, the run exits non-zero before any phase and prints no result.
"""

from __future__ import annotations

import time

T_PROCESS = time.perf_counter()  # set-up is counted from here

import argparse  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
# run as a script, sys.path[0] is perfbench/: the checkout holds the program
sys.path.insert(0, str(ROOT))

from perfbench import harness  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    cell = harness.load_cell(ROOT, args.workload)
    result = harness.run_cell(cell, seed=args.seed, seconds=args.seconds,
                              trace=bool(args.trace), t_process=T_PROCESS)
    print(result, flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
