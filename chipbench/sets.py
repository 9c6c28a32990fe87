"""A set of benchmark runs of one cell, one process after another, and the
spread of each metric over the set.

    python chipbench/sets.py --workload <cell> --seeds 1,2,3,4,5,6 \
        [--seconds 10] [--trace 0] --out chiprun_out/<file>.jsonl

Each run is `chipbench/run.py` in a child process from the checkout's root
(this process never starts JAX, so each child has the chips to itself).
Every run appends one JSON line to ``--out``: the seed, exit code, wall
time, the run's result line and the end of its standard error.  At the
end one summary line per metric goes to standard output: its values, the
median, and the quartile spread, the distance between the first and third
quartile (`statistics.quantiles(values, n=4)`) as a share of the median.
"""
from __future__ import annotations

import argparse
import json
import os
import statistics
import subprocess
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def spread(values: list) -> float:
    q1, _, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / statistics.median(values)


def one(workload: str, seed: int, seconds: float, trace: int) -> dict:
    cmd = [sys.executable, "chipbench/run.py", "--workload", workload,
           "--seed", str(seed), "--seconds", str(seconds),
           "--trace", str(trace)]
    t0 = time.time()
    try:
        p = subprocess.run(cmd, cwd=ROOT, capture_output=True, text=True,
                           timeout=1200)
        rc, out, err = p.returncode, p.stdout, p.stderr
    except subprocess.TimeoutExpired as e:
        rc, out, err = 124, str(e.stdout or ""), str(e.stderr or "")
    rec = {"workload": workload, "seed": seed, "trace": trace, "rc": rc,
           "wall_s": time.time() - t0,
           "stderr_tail": [l for l in err.splitlines()
                           if l.startswith("chipbench")][-12:]}
    lines = out.strip().splitlines()
    try:
        rec["result"] = json.loads(lines[-1])
    except (IndexError, ValueError):
        rec["stderr_tail"] = err.splitlines()[-20:]
    return rec


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=10)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--out", required=True)
    args = ap.parse_args(argv)
    values, ok = {}, []
    os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
    for seed in (int(s) for s in args.seeds.split(",")):
        rec = one(args.workload, seed, args.seconds, args.trace)
        with open(args.out, "a") as f:
            f.write(json.dumps(rec) + "\n")
        res = rec.get("result", {})
        ok.append(res.get("correct"))
        for name, m in res.get("metrics", {}).items():
            values.setdefault(name, []).append(m["value"])
        print(json.dumps({"seed": seed, "rc": rec["rc"],
                          "wall_s": round(rec["wall_s"], 1),
                          "correct": res.get("correct"),
                          "checks": res.get("checks"),
                          "metrics": {k: m["value"] for k, m in
                                      res.get("metrics", {}).items()},
                          "memory_peak_bytes": res.get("device", {}).get(
                              "memory_peak_bytes")}), flush=True)
        if rec["rc"] != 0:
            print("\n".join(rec["stderr_tail"]), flush=True)
            return rec["rc"]
    for name, v in values.items():
        print(json.dumps({"metric": name, "values": v,
                          "median": statistics.median(v),
                          "spread": spread(v) if len(v) > 1 else None}),
              flush=True)
    print(json.dumps({"correct": ok}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
