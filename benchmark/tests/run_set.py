#!/usr/bin/env python3
"""Run one cell several times, each run a new process and another seed,
and print what a bound is set from.

    python3 benchmark/tests/run_set.py lm-dense --seeds 11 12 13 14 15 16 [--first-is-cold]

For each end-to-end metric: every run's value, the median, the spread
(distance between the quartiles over the median, as the driver takes
it) and the widest single run's distance from the median. Run by hand
on the chip (through the chip tool, all runs of a cell in one call);
this process never imports jax, so each run gets the chip. With
``--first-is-cold`` the first run only fills the compile cache: it is
printed and left out of the figures, and the set stops if the second
run's set-up is not clearly shorter (the cache is not being hit).
"""

import argparse
import json
import os
import statistics
import subprocess

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def one_run(command, workload, seed, seconds, trace):
    out = subprocess.run(
        command + ["--workload", workload, "--seed", str(seed),
                   "--seconds", str(seconds), "--trace", str(trace)],
        cwd=ROOT, capture_output=True, text=True,
    )
    lines = out.stdout.strip().splitlines()
    for line in lines[:-1]:
        print("   ", line[:400])
    if out.returncode != 0 or not lines:
        print(out.stderr[-3000:])
        raise SystemExit(f"{workload} seed {seed}: exit code {out.returncode}")
    last = json.loads(lines[-1])
    if not last["correct"]:
        raise SystemExit(f"{workload} seed {seed}: not correct: {lines[-1][:500]}")
    return last


def main() -> None:
    parser = argparse.ArgumentParser()
    parser.add_argument("workload")
    parser.add_argument("--seeds", type=int, nargs="+", required=True)
    parser.add_argument("--first-is-cold", action="store_true")
    parser.add_argument("--trace", type=int, default=0)
    args = parser.parse_args()
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    runs = []
    for i, seed in enumerate(args.seeds):
        last = one_run(bench["command"], args.workload, seed, bench["run_seconds"], args.trace)
        values = {k: v["value"] for k, v in last["metrics"].items()}
        print(f"RUN {args.workload} seed {seed}: {json.dumps(values)} "
              f"device {json.dumps(last['device'])}", flush=True)
        if args.trace and "breakdown" in last:
            print(f"BREAKDOWN {args.workload}: {json.dumps(last['breakdown'])[:3000]}")
        runs.append(values)
        if args.first_is_cold and i == 1 and "setup_s" in values:
            if values["setup_s"] > 0.8 * runs[0]["setup_s"] and runs[0]["setup_s"] > 100:
                raise SystemExit("the second run's set-up is as long as the first's: "
                                 "the compile cache is not being hit")
    measured = runs[1:] if args.first_is_cold else runs
    if len(measured) < 2:
        return
    for name in measured[0]:
        xs = [r[name] for r in measured if name in r]
        med = statistics.median(xs)
        q = statistics.quantiles(xs, n=4, method="inclusive")
        widest = max(abs(x - med) for x in xs)
        print(f"SET {args.workload} {name}: n={len(xs)} median={med:.6g} "
              f"spread={(q[2] - q[0]) / med if med else float('nan'):.5%} "
              f"widest_run={widest / med if med else float('nan'):.5%} "
              f"min={min(xs):.6g} max={max(xs):.6g}", flush=True)


if __name__ == "__main__":
    main()
