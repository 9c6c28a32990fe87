"""Chip benchmark of the MLL-SGD trainer.

    python chipbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Run from the root of a checkout on a machine that holds the chips the cell
asks for.  The cell is found by name in ``BENCHMARK.json``; its
configuration in ``chipbench/configs/<config>.json``, its traffic in
``chipbench/traffic/<traffic>.json``, the limits of its correctness check
in ``chipbench/limits/<cell>.json``, and each per-layer metric's reader in
``chipbench/metrics/<metric>.py``.  Adding a cell, configuration or metric
adds files and entries; no file here changes.

The run exits non-zero, printing no result, where JAX finds no TPU or fewer
chips than the cell asks for, the program is missing, or the traffic's
``mesh`` does not fit the cell.  With
``--trace 0`` it reports the cell's end-to-end metrics; with ``--trace 1``
its per-layer metrics, read from a profiler trace of the window.  Either
way it then checks what the timed path produced against the plain
reference (`compare.py`), prints each compared number beside its limit as
its last lines on standard error, and prints one JSON object as the last
line of standard output.
"""
from __future__ import annotations

import time

START = time.time()

import argparse  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import types  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
CACHE = os.path.join(ROOT, ".chipbench_cache")
# the trainer's host spans (`repro.launch.spans`): idle-gap labels
LABELS = ("run_span", "draw_batch", "stack_batches", "local_scan",
          "event_step.1", "event_step.2", "dense_step", "skip_idle")


def _json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def load_cell(name: str) -> dict:
    """Everything a run of cell ``name`` reads, by name."""
    bench = _json(os.path.join(ROOT, "BENCHMARK.json"))
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise SystemExit(f"unknown workload {name!r}; known: {sorted(cells)}")
    cell = cells[name]
    traffic = _json(os.path.join(HERE, "traffic", cell["traffic"] + ".json"))
    check_mesh(traffic, cell["chips"])
    e2e = [m for m in bench["end_to_end"]
           if name in m.get("workloads", [name])]
    per_layer = [m for m in bench["per_layer"]
                 if name in m.get("workloads", [name])
                 and any(m["moves"] == e["name"] for e in e2e)]
    return {"name": name, "chips": cell["chips"],
            "config_name": cell["config"],
            "config": _json(os.path.join(HERE, "configs",
                                         cell["config"] + ".json")),
            "traffic": traffic,
            "limits": _json(os.path.join(HERE, "limits", name + ".json")),
            "end_to_end": e2e, "per_layer": per_layer,
            "names": _json(os.path.join(HERE, "names.json"))}


def check_mesh(traffic: dict, chips: int) -> None:
    """A traffic's ``mesh`` [workers, data] has to cover the cell's chips
    exactly, and its workers axis has to divide the fleet."""
    if "mesh" not in traffic:
        return
    mesh = traffic["mesh"]
    fleet = traffic["subnets"] * traffic["workers_per_subnet"]
    if (len(mesh) != 2 or min(mesh) < 1 or math.prod(mesh) != chips
            or fleet % mesh[0]):
        raise SystemExit(f"chipbench: mesh {mesh} (workers, data) does not "
                         f"fit the cell: it needs {chips} chip(s) in all and "
                         f"a workers axis that divides the {fleet} workers")


def reader(metric: str):
    path = os.path.join(HERE, "metrics", metric + ".py")
    spec = importlib.util.spec_from_file_location(
        "chipbench_metric_" + metric.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def enable_cache() -> None:
    """JAX's persistent compilation cache at a fixed path in the checkout,
    keeping every program however small or quick to compile."""
    import jax
    jax.config.update("jax_compilation_cache_dir", os.path.join(CACHE, "jax"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def tpu_devices(chips: int) -> list:
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu" or len(devs) < chips:
        sys.stderr.write(f"chipbench: needs {chips} TPU chip(s); JAX sees "
                         f"{len(devs)} {devs[0].platform} device(s)\n")
        raise SystemExit(3)
    return devs[:chips]


def peaks_for(kind: str) -> dict:
    table = _json(os.path.join(HERE, "peaks.json"))
    if kind not in table:
        sys.stderr.write(f"chipbench: no peaks for device kind {kind!r} in "
                         "peaks.json\n")
        raise SystemExit(4)
    return table[kind]


class CompileCounter:
    """Counts traces and compiles while armed (JAX's monitoring events)."""

    def __init__(self):
        import jax
        self.armed, self.count = False, 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, *_a, **_k) -> None:
        if self.armed and ("compile" in event or "trace" in event):
            self.count += 1


def run(cell: dict, seed: int, seconds: float, trace: bool, devices: list,
        peaks: dict, *, log=print) -> dict:
    """One run of a training cell; returns the result line as a dict."""
    import jax
    import numpy as np

    import cell_train
    import compare
    import flops
    import reference
    import traces

    counter = CompileCounter()
    tc = cell_train.TrainCell(cell["config_name"], cell["config"],
                              cell["traffic"], seed, devices)
    first = tc.follow(cell["traffic"]["check_slots"])
    tc.warm()
    setup_s = time.time() - START
    tdir = os.path.join(CACHE, "trace")
    if trace:
        shutil.rmtree(tdir, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1
        jax.profiler.start_trace(tdir, profiler_options=opts)
    counter.armed = True
    with jax.profiler.TraceAnnotation(traces.WINDOW):
        win = tc.window(seconds)
    counter.armed = False
    if trace:
        jax.profiler.stop_trace()
    log(f"chipbench: compiles inside the window: {counter.count}; window "
        f"{win['seconds']:.3f} s, {win['slots']} slots, {win['ahead']} "
        f"rounds queued ahead (a warm round took {tc.round_s:.3f} s)",
        file=sys.stderr)
    finite = bool(np.all(np.isfinite(np.asarray(
        jax.device_get(tc.metrics["loss"])))))
    mem = cell_train.memory_peak(devices)
    batches = tc.batches()
    tc.free()
    ref = reference.run(tc.spec, cell["traffic"], seed, tc.gate_seed, batches,
                        devices)
    correct, checks, readings = compare.check(first, ref, cell["limits"])
    log(f"chipbench: readings {json.dumps(readings)}; loss gap by slot "
        f"{compare.loss_by_slot(first, ref)}", file=sys.stderr)
    correct = correct and finite

    device = {"platform": devices[0].platform, "kind": devices[0].device_kind,
              "count": len(devices), "memory_peak_bytes": mem}
    out = {"correct": correct, "attempted": win["slots"],
           "failed": 0 if finite else win["slots"]}
    if not trace:
        out["metrics"] = {
            "train_tokens_per_s": {"value": win["tokens"] / win["seconds"],
                                   "unit": "tokens/s"},
            "setup_s": {"value": setup_s, "unit": "s"}}
    else:
        tr = traces.load(tdir, LABELS)
        ctx = types.SimpleNamespace(
            trace=tr, traces=traces, flops=flops, spec=tc.spec,
            traffic=cell["traffic"], window=win, chips=len(devices),
            peaks=peaks, names=cell["names"])
        metrics = {}
        for m in cell["per_layer"]:
            v = reader(m["name"])(ctx)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        out["metrics"] = metrics
        busy = traces.busy_ns(tr)
        device["busy_s"] = sum(busy.values()) / max(len(busy), 1) / 1e9
        device["window_s"] = tr.window_ns / 1e9
        out["breakdown"] = {"device_ops": traces.top_ops(tr),
                            "idle_gaps": traces.idle_gaps(tr)}
        shutil.rmtree(tdir, ignore_errors=True)
    out["device"] = device
    # a gap that is not a number (nan, inf) reads as far over any limit
    out["checks"] = {k: {"value": r["value"] if math.isfinite(r["value"])
                         else 1e300, "limit": r["limit"]}
                     for k, r in checks.items()}
    for k, r in checks.items():
        log(f"chipbench: check {k} = {r['value']!r} (limit {r['limit']!r}, "
            f"worst at {r['where']})", file=sys.stderr)
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if args.seed < 0:
        ap.error("--seed must be a non-negative whole number")
    cell = load_cell(args.workload)
    sys.path.insert(0, os.path.join(ROOT, "src"))
    if importlib.util.find_spec("repro") is None:
        sys.stderr.write("chipbench: the trainer (src/repro) is not in this "
                         "checkout\n")
        return 5
    enable_cache()
    devices = tpu_devices(cell["chips"])
    peaks = peaks_for(devices[0].device_kind)
    out = run(cell, args.seed, args.seconds, bool(args.trace), devices,
              peaks)
    sys.stderr.flush()
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
