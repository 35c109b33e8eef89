#!/usr/bin/env python3
"""Run one cell of BENCHMARK.json once and print its result as the last line.

    python3 benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

``--trace 0`` gives the cell's end-to-end metrics, ``--trace 1`` its
per-layer metrics and the breakdown. Needs the TPU chips the cell asks for
and exits 1 with no result otherwise. ``--rehearsal`` runs the same code at
a tiny size on whatever JAX finds, marks every earlier line, and its numbers
mean nothing. ``--control 1`` also computes the control (the reference in
bfloat16 in the program's place) and prints how it compares.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time


def _process_start() -> float:
    """The clock (``time.perf_counter``) at which this process started."""
    now = time.perf_counter()
    try:
        with open("/proc/self/stat", encoding="ascii") as f:
            start_ticks = int(f.read().rsplit(")", 1)[1].split()[19])
        with open("/proc/uptime", encoding="ascii") as f:
            up = float(f.read().split()[0])
        return now - max(0.0, up - start_ticks / os.sysconf("SC_CLK_TCK"))
    except (OSError, ValueError, IndexError):
        return now


T_PROCESS = _process_start()
BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, BENCH_DIR)
sys.path.insert(0, os.path.dirname(BENCH_DIR))      # the program


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearsal", action="store_true")
    args = ap.parse_args(argv)

    from harness.manifest import Cell, load_manifest
    from harness.runner import run_cell

    cell = Cell(load_manifest(), args.workload)
    prefix = "[REHEARSAL tiny size, numbers mean nothing] " \
        if args.rehearsal else ""

    def say(msg: str) -> None:
        print(prefix + msg, flush=True)

    say(f"workload {cell.name}: config {cell.config_name}, traffic "
        f"{cell.traffic_name}, seed {args.seed}, seconds {args.seconds:g}, "
        f"trace {args.trace}")
    result, rc = run_cell(cell, seed=args.seed, seconds=args.seconds,
                          trace=bool(args.trace), t_process=T_PROCESS,
                          rehearsal=args.rehearsal,
                          control=bool(args.control), say=say)
    if result is not None:
        print(json.dumps(result), flush=True)
    return rc


if __name__ == "__main__":
    sys.exit(main())
