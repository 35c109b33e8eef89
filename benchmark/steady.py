#!/usr/bin/env python3
"""What the check will see: a cell run 2n times as two sets with the same
seeds, and per end-to-end metric each set's median and spread beside half
the bound in BENCHMARK.json.

    python3 benchmark/steady.py --workload <name> --runs <n> [--seconds <s>] [--seed0 <k>]

Each run is a process of its own (``benchmark/run.py``); this process never
touches JAX, so the chip is free for each child. A spread is the distance
between the first and third quartile (``statistics.quantiles(values, n=4)``)
as a share of the median. Prints to the terminal only.
"""

from __future__ import annotations

import argparse
import json
import os
import statistics
import subprocess
import sys

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, BENCH_DIR)


def main(argv=None) -> int:
    from harness.manifest import ROOT, Cell, load_manifest
    from harness.stats import spread

    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--runs", type=int, default=6)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--seed0", type=int, default=2_200_000_001)
    args = ap.parse_args(argv)
    manifest = load_manifest()
    cell = Cell(manifest, args.workload)
    seconds = args.seconds or manifest["run_seconds"]
    cmd = manifest["command"] + ["--workload", cell.name, "--seconds",
                                 str(seconds), "--trace", "0"]
    sets: list = [[], []]
    ok = True
    for s in range(2):
        for k in range(args.runs):
            seed = args.seed0 + 7919 * k
            proc = subprocess.run(cmd + ["--seed", str(seed)], cwd=ROOT,
                                  capture_output=True, text=True)
            lines = proc.stdout.strip().splitlines()
            try:
                result = json.loads(lines[-1])
            except (IndexError, ValueError):
                print(f"set {s + 1} run {k + 1} seed {seed}: rc "
                      f"{proc.returncode}, no result line\n"
                      f"{proc.stderr[-2000:]}", flush=True)
                ok = False
                continue
            for line in lines[:-1]:
                if line.startswith(("gc in window", "throughput by",
                                    "compile:", "setup_s parts",
                                    "latency sample", "longest block")):
                    print(f"    {line}")
            values = {k2: v["value"] for k2, v in result["metrics"].items()}
            print(f"set {s + 1} run {k + 1} seed {seed}: correct "
                  f"{result['correct']} "
                  + " ".join(f"{k2}={v:.6g}" for k2, v in values.items()),
                  flush=True)
            ok &= bool(result["correct"])
            sets[s].append(values)
    print(f"\n{cell.name} at {seconds:g} s, {args.runs} runs a set")
    for m in cell.end_to_end:
        name, bound = m["name"], m["bound"]
        row = [f"{name} (bound {bound:.3g}, half {bound / 2:.3g})"]
        meds = []
        for s in range(2):
            vals = [v[name] for v in sets[s] if name in v]
            if name == "setup_s":
                vals = vals[1:] if s == 0 else vals     # the cold run
            if len(vals) < 2:
                row.append(f"set {s + 1}: too few runs")
                continue
            med = statistics.median(vals)
            meds.append(med)
            row.append(f"set {s + 1}: median {med:.6g} spread "
                       f"{spread(vals):.4f}")
        if len(meds) == 2:
            row.append(f"second/first median {meds[1] / meds[0] - 1:+.4f}")
        print("  " + " | ".join(row))
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
