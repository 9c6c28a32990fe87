"""§Perf hillclimb runner: lowers one (arch x shape) variant in a fresh
512-device subprocess and prints/saves its roofline terms next to the
baseline for the EXPERIMENTS.md iteration log.

  PYTHONPATH=src python -m benchmarks.perf_iter --arch grok-1-314b \\
      --shape train_4k --tag moe_groups16 --kw moe_groups=16
"""
from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import textwrap

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PERF_DIR = os.path.join(ROOT, "results", "perf")


def run_variant(arch: str, shape: str, tag: str, kwargs: dict,
                multipod: bool = False, timeout: int = 3000) -> dict:
    os.makedirs(PERF_DIR, exist_ok=True)
    mesh = "pod2x16x16" if multipod else "16x16"
    out_path = os.path.join(PERF_DIR, f"{arch}_{shape}_{mesh}_{tag}.json")
    if os.path.exists(out_path):
        with open(out_path) as f:
            return json.load(f)
    script = textwrap.dedent(f"""
        import json
        from repro.launch.dryrun import run_one
        r = run_one({arch!r}, {shape!r}, multi_pod={multipod!r}, **{kwargs!r})
        with open({out_path!r}, "w") as f:
            json.dump(r, f, indent=1)
    """)
    env = dict(os.environ, PYTHONPATH=os.path.join(ROOT, "src"))
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
    env["JAX_PLATFORMS"] = "cpu"    # host devices only; never claim a chip
    p = subprocess.run([sys.executable, "-c", script], env=env,
                       capture_output=True, text=True, timeout=timeout)
    if p.returncode != 0:
        raise RuntimeError(f"variant {tag} failed:\n{p.stderr[-3000:]}")
    with open(out_path) as f:
        return json.load(f)


def summarize(r: dict, label: str = "") -> str:
    rl = r["roofline"]
    mem = r.get("memory_analysis", {})
    return (f"{label:28s} compute={rl['compute_s']:.3e} "
            f"memory={rl['memory_s']:.3e} coll={rl['collective_s']:.3e} "
            f"dcn={rl.get('dcn_s', 0):.3e} dom={rl['dominant']:10s} "
            f"temp={mem.get('temp_size_in_bytes', 0)/1e9:7.1f}GB "
            f"MF/HF={r.get('useful_fraction', 0):.2f}")


def _parse_kw(items):
    out = {}
    for it in items or ():
        k, v = it.split("=", 1)
        if v in ("None", "null"):
            out[k] = None
        elif v.isdigit():
            out[k] = int(v)
        elif v in ("True", "False"):
            out[k] = v == "True"
        else:
            out[k] = v
    return out


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--shape", required=True)
    ap.add_argument("--tag", required=True)
    ap.add_argument("--multipod", action="store_true")
    ap.add_argument("--kw", nargs="*", default=[])
    args = ap.parse_args(argv)
    r = run_variant(args.arch, args.shape, args.tag, _parse_kw(args.kw),
                    multipod=args.multipod)
    print(summarize(r, f"{args.arch[:16]}/{args.shape}/{args.tag}"))


if __name__ == "__main__":
    main()
