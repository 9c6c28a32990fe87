"""Run the full dry-run matrix as subprocesses, one JSON per combo.

Each combo runs `python -m repro.launch.dryrun` in a fresh process (the
dry-run needs 512 placeholder devices; everything else in the repo must see
1 device).  Results land in results/dryrun/<arch>_<shape>_<mesh>[_<tag>].json
and are skipped when already present, so the sweep is resumable.

  PYTHONPATH=src python -m benchmarks.dryrun_sweep [--phases] [--only substr]
"""
from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import time

ARCHES = (
    "grok-1-314b", "chatglm3-6b", "xlstm-125m", "musicgen-large",
    "qwen2-vl-72b", "jamba-v0.1-52b", "stablelm-3b", "qwen2-0.5b",
    "qwen3-moe-235b-a22b", "qwen3-1.7b",
)
SHAPES = ("train_4k", "prefill_32k", "decode_32k", "long_500k")
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
OUT_DIR = os.path.join(ROOT, "results", "dryrun")


def combo_path(arch, shape, mesh, tag=""):
    name = f"{arch}_{shape}_{mesh}" + (f"_{tag}" if tag else "")
    return os.path.join(OUT_DIR, name.replace("/", "-") + ".json")


def run_combo(arch, shape, *, multipod=False, phase="dynamic",
              extra=(), tag="", timeout=1800):
    mesh = "pod2x16x16" if multipod else "16x16"
    path = combo_path(arch, shape, mesh, tag or (phase if phase != "dynamic" else ""))
    if os.path.exists(path):
        return "cached", path
    cmd = [sys.executable, "-m", "repro.launch.dryrun",
           "--arch", arch, "--shape", shape, "--phase", phase,
           "--out", path, *extra]
    if multipod:
        cmd.append("--multipod")
    # the dry-run compiles for 512 forced HOST devices: pin the child to the
    # CPU so it never claims an attached accelerator
    env = dict(os.environ, PYTHONPATH=os.path.join(ROOT, "src"),
               JAX_PLATFORMS="cpu")
    t0 = time.time()
    p = subprocess.run(cmd, env=env, capture_output=True, text=True,
                       timeout=timeout)
    if p.returncode != 0 or not os.path.exists(path):
        err = {"arch": arch, "shape": shape, "mesh": mesh, "phase": phase,
               "error": p.stderr[-4000:], "returncode": p.returncode}
        with open(path, "w") as f:
            json.dump([err], f, indent=1)
        return "FAIL", path
    return f"ok {time.time()-t0:.0f}s", path


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--phases", action="store_true",
                    help="also lower each MLL phase for train combos")
    ap.add_argument("--only", default="")
    args = ap.parse_args(argv)
    os.makedirs(OUT_DIR, exist_ok=True)

    jobs = []
    for arch in ARCHES:
        for shape in SHAPES:
            for mp in (False, True):
                jobs.append(dict(arch=arch, shape=shape, multipod=mp))
    if args.phases:
        for arch in ARCHES:
            for mp in (False, True):
                for ph in ("local", "subnet", "hub"):
                    jobs.append(dict(arch=arch, shape="train_4k", multipod=mp,
                                     phase=ph))
    for j in jobs:
        if args.only and args.only not in f"{j['arch']}_{j['shape']}":
            continue
        status, path = run_combo(**j)
        print(f"{status:10s} {os.path.basename(path)}", flush=True)


if __name__ == "__main__":
    main()
