"""Readings that set a training cell's correctness limits (run on the chip
by hand; the benchmark's own runs do not run this).

    python chipbench/control.py --workload <cell> --seeds 1,2,... \\
        --control-seeds 7,8,9 [--control int8,float8_e4m3fn]

For each of ``--seeds``: the trainer's set-up and the followed slots
(`TrainCell.follow`, the calls and programs a run makes) against the
reference: the lower readings.  For each of ``--control-seeds``: the
reference computed with every matmul in each ``--control`` precision, put
in the trainer's place (the upper readings); and the faults a training cell
can have, planted in the reference put in the trainer's place: half of
each batch left out of the loss, and, where the followed slots mix, the
exchange left out.  One JSON line per reading on standard output.
"""
from __future__ import annotations

import argparse
import json
import sys
import time

import run as bench


def reading(kind: str, seed: int, got: dict, ref: dict, t0: float) -> None:
    """One JSON line: every number `compare.numbers` reads, where each is
    worst, and the loss and median-leaf change gaps of each followed slot
    alone."""
    import compare
    nums = compare.numbers(got, ref)
    print(json.dumps({"kind": kind, "seed": seed,
                      **{k: v for k, (v, _) in nums.items()},
                      "loss_by_slot": compare.loss_by_slot(got, ref),
                      "change_median_by_slot":
                          compare.change_median_by_slot(got, ref),
                      "where": {k: w for k, (_, w) in nums.items()},
                      "s": round(time.time() - t0, 1)}), flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", default="")
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--control", default="int8")
    args = ap.parse_args(argv)
    cell = bench.load_cell(args.workload)
    sys.path.insert(0, bench.os.path.join(bench.ROOT, "src"))
    bench.enable_cache()
    devices = bench.tpu_devices(cell["chips"])
    import cell_train
    import reference
    from weights import Spec

    spec, train = Spec.from_json(cell["config"]), cell["traffic"]
    slots = train["check_slots"]
    for seed in [int(s) for s in args.seeds.split(",") if s]:
        t0 = time.time()
        tc = cell_train.TrainCell(cell["config_name"], cell["config"], train,
                                  seed, devices)
        prog = tc.follow(slots)
        tc.free()
        ref = reference.run(spec, train, seed, train["gate_seed"],
                            cell_train.first_batches(spec, train, seed,
                                                     slots[-1]), devices)
        reading("program", seed, prog, ref, t0)
    mixes = any(reference.phase(k, train["tau"], train["q"])
                for k in range(1, slots[-1] + 1))
    faults = {f"control.{c}": {"lowp": c} for c in args.control.split(",")}
    faults["half_batch"] = {"keep": 0.5}
    if mixes:
        faults["no_exchange"] = {"exchange": False}
    for seed in [int(s) for s in args.control_seeds.split(",") if s]:
        batches = cell_train.first_batches(spec, train, seed, slots[-1])
        ref = reference.run(spec, train, seed, train["gate_seed"], batches,
                            devices)
        for kind, kw in faults.items():
            t0 = time.time()
            got = reference.run(spec, train, seed, train["gate_seed"], batches,
                                devices, **kw)
            reading(kind, seed, got, ref, t0)
    return 0


if __name__ == "__main__":
    sys.exit(main())
