#!/usr/bin/env python3
"""Prove a cell on the chip as the benchmark's contract asks: a first run
(which builds and compiles), sets of runs with one seed per run and the
same seeds in every set, then the traced runs; each run its own process, one
after the other, so that one process holds the chip at a time.

    chiprun -- python3 benchmarks/prove.py --workload <name> [--sets 2 --runs 6]

Prints each metric's median and spread per set (spread: distance between
the quartiles of ``statistics.quantiles(values, n=4)`` over the median) and
writes every run's result and detail line to ``chiprun_out/<name>.jsonl``.
Touches no jax itself.
"""

import argparse
import json
import os
import statistics
import subprocess
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
SEED0 = 2147483659  # beyond 32 signed bits, like the driver's


def one_run(workload, seed, seconds, trace, tag, out, extra=()):
    cmd = [sys.executable, os.path.join(HERE, "run.py"),
           "--workload", workload, "--seed", str(seed),
           "--seconds", str(seconds), "--trace", str(trace), *extra]
    t0 = time.perf_counter()
    proc = subprocess.run(cmd, cwd=ROOT, capture_output=True, text=True)
    wall = time.perf_counter() - t0
    lines = proc.stdout.strip().splitlines()
    record = {"tag": tag, "workload": workload, "seed": seed, "trace": trace,
              "rc": proc.returncode, "wall_s": wall, "result": None,
              "detail": None}
    for line in lines:
        if line.startswith("[bench] detail "):
            record["detail"] = json.loads(line[len("[bench] detail "):])
    if proc.returncode == 0 and lines:
        record["result"] = json.loads(lines[-1])
    else:
        record["stderr_tail"] = proc.stderr[-2000:]
    with open(out, "a") as f:
        f.write(json.dumps(record) + "\n")
    values = {k: v["value"] for k, v in
              ((record["result"] or {}).get("metrics") or {}).items()}
    print("%s rc=%d wall=%.1f %s" % (tag, proc.returncode, wall,
                                     json.dumps(values)), flush=True)
    if proc.returncode != 0:
        print(proc.stderr[-2000:], flush=True)
    return record, values


def spread(values):
    q = statistics.quantiles(values, n=4)
    return (q[2] - q[0]) / statistics.median(values)


def main():
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--workload", required=True)
    p.add_argument("--seconds", type=float, default=None)
    p.add_argument("--sets", type=int, default=2)
    p.add_argument("--runs", type=int, default=6)
    p.add_argument("--trace", type=int, default=1,
                   help="traced runs after the sets, each with its own seed")
    p.add_argument("--seed0", type=int, default=SEED0,
                   help="every run's seed is this plus a fixed offset")
    p.add_argument("--no-first", action="store_true")
    p.add_argument("--keep-trace", action="store_true",
                   help="copy the traced run's .xplane.pb to chiprun_out/")
    args = p.parse_args()
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    seconds = args.seconds or bench["run_seconds"]
    os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
    out = os.path.join(ROOT, "chiprun_out", args.workload + ".jsonl")

    if not args.no_first:
        one_run(args.workload, args.seed0, seconds, 0, "first", out)
    per_set = []
    for s in range(args.sets):
        got = {}
        for r in range(args.runs):
            _, values = one_run(args.workload, args.seed0 + 7919 * (r + 1),
                                seconds, 0, "set%d.run%d" % (s, r), out)
            for k, v in values.items():
                got.setdefault(k, []).append(v)
        per_set.append(got)
        for k, vals in got.items():
            if len(vals) >= 2:
                print("SET %d %s median=%.6g spread=%.4f min=%.6g max=%.6g n=%d"
                      % (s, k, statistics.median(vals), spread(vals),
                         min(vals), max(vals), len(vals)), flush=True)
    for t in range(args.trace):
        record, _ = one_run(args.workload, args.seed0 + 1 + t, seconds, 1,
                            "traced%d" % t, out)
        if record["result"]:
            print("TRACED " + json.dumps(record["result"]), flush=True)
    if args.trace and args.keep_trace:
        import glob
        import shutil

        found = sorted(glob.glob(os.path.join(
            ROOT, ".bench_run", args.workload, "trace", "plugins",
            "profile", "*", "*.xplane.pb")))
        if found:
            shutil.copy(found[-1], os.path.join(
                ROOT, "chiprun_out", args.workload + ".xplane.pb"))
    for k in (per_set[0] if per_set else {}):
        spreads = [spread(g[k]) for g in per_set if len(g.get(k, [])) >= 2]
        if not spreads:
            continue
        widest = max(spreads)
        print("WIDEST %s spread=%.4f -> bound of five times: %.4f"
              % (k, widest, 5 * widest), flush=True)


if __name__ == "__main__":
    main()
