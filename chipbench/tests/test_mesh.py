"""A cell on a four-chip mesh, added to a copy of the benchmark by data
files and `BENCHMARK.json` entries alone, run on four forced CPU devices
(a child process: the device count is fixed when JAX starts); and the
reference spread over four devices against the same on one."""
import json
import os
import shutil
import subprocess
import sys
import textwrap

from conftest import BENCH, PEAKS, ROOT, TINY, TINY_LIMITS

CELL = "tiny.train.mesh4"
SEED = 2**31 + 29


def _child(cwd, script: str) -> subprocess.CompletedProcess:
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    return subprocess.run([sys.executable, "-c", textwrap.dedent(script)],
                          cwd=cwd, env=env, capture_output=True, text=True,
                          timeout=1200)


def _copy_with_mesh_cell(tmp_path, mesh) -> str:
    """The benchmark copied to ``tmp_path`` (the trainer linked beside
    it), plus a tiny qwen2-0.5b-shaped configuration, the local cell's
    traffic on a ``mesh`` [workers, data], its limits, and their entries."""
    root = tmp_path / "checkout"
    shutil.copytree(BENCH, root / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    os.symlink(os.path.join(ROOT, "src"), root / "src")
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    with open(os.path.join(BENCH, "configs", "qwen2-0.5b.json")) as f:
        cfg = dict(json.load(f), **TINY)
    with open(os.path.join(BENCH, "traffic", "train.local.json")) as f:
        traffic = dict(json.load(f), seq_len=32, tokens_per_worker=4096,
                       mesh=mesh)
    files = {"configs/tiny.json": cfg, "traffic/train.mesh4.json": traffic,
             f"limits/{CELL}.json": TINY_LIMITS}
    for name, data in files.items():
        with open(root / "chipbench" / name, "w") as f:
            json.dump(data, f)
    bench["configs"].append({"name": "tiny", "source": "test",
                             "file": "chipbench/configs/tiny.json",
                             "reduced": [], "why": "test"})
    bench["workloads"].append({"name": CELL, "config": "tiny",
                               "traffic": "train.mesh4", "chips": 4,
                               "why": "test"})
    for m in bench["per_layer"]:
        if m["name"] in ("local_slot_ms", "event_slot_ms"):
            m["workloads"].append(CELL)
    bench["per_layer"].append(
        {"name": "collective_exposed_ms", "unit": "ms", "better": "lower",
         "source": "device_trace", "layer": "mesh",
         "moves": "train_tokens_per_s", "workloads": [CELL]})
    with open(root / "BENCHMARK.json", "w") as f:
        json.dump(bench, f)
    return str(root)


def test_mesh_cell_from_data_files(tmp_path):
    """Both kinds of run of the mesh cell: correct, the exchange read as
    the reference's, and, from the programs its trace holds, the local
    scans told apart from the event steps by their scopes."""
    root = _copy_with_mesh_cell(tmp_path, [4, 1])
    res = _child(root, f"""
        import json, sys
        sys.path[:0] = ["chipbench", "src"]
        import jax
        import run, spans, traces
        cell = run.load_cell({CELL!r})
        held = {{}}
        load = traces.load

        def keep(directory, labels):
            with open(spans.newest(directory), "rb") as f:
                held["trace"] = f.read()
            return load(directory, labels)

        traces.load = keep
        devices = jax.devices()[:4]
        outs = [run.run(cell, {SEED}, 1.0, trace, devices, {PEAKS!r},
                        log=lambda *a, **k: None) for trace in (False, True)]
        kinds = {{mod: spans.kind_of(instrs, cell["names"]["programs"])
                 for mod, (_, instrs)
                 in spans.hlo_modules(held["trace"]).items()}}
        print(json.dumps({{"outs": outs, "kinds": kinds}}))
        """)
    assert res.returncode == 0, res.stderr[-4000:]
    got = json.loads(res.stdout.strip().splitlines()[-1])
    for out, trace in zip(got["outs"], (False, True)):
        assert out["correct"] is True, out["checks"]
        assert out["checks"]["exchange"]["value"] == 0
        assert out["device"]["count"] == 4
        assert list(out)[-1] == "checks"
        if not trace:
            assert set(out["metrics"]) == {"train_tokens_per_s", "setup_s"}
        else:
            assert {"busy_s", "window_s"} <= set(out["device"])
    kinds = {}
    for mod, kind in got["kinds"].items():
        kinds.setdefault(kind, set()).add(mod)
    # the window ran local scans of 4, 2 and 1 slots and both events
    assert len(kinds.get("local_scan", ())) >= 3
    assert len(kinds.get("event_step", ())) >= 2
    assert not kinds.get("dense_step")


def test_mesh_that_does_not_fit_the_cell(tmp_path):
    """A mesh whose size is not the cell's chips stops the run before
    set-up, with no result."""
    root = _copy_with_mesh_cell(tmp_path, [2, 1])
    res = subprocess.run(
        [sys.executable, "chipbench/run.py", "--workload", CELL, "--seed",
         "1", "--seconds", "1", "--trace", "0"], cwd=root,
        env=dict(os.environ, JAX_PLATFORMS="cpu"), capture_output=True,
        text=True, timeout=300)
    assert res.returncode != 0
    assert res.stdout.strip() == ""
    assert "mesh [2, 1]" in res.stderr


def test_reference_spread_over_devices():
    """The reference with its four workers on four devices reads as with
    all four on one, bit for bit."""
    res = _child(BENCH, f"""
        import json, sys
        sys.path[:0] = [".", {os.path.join(ROOT, "src")!r}]
        import jax, numpy as np
        import cell_train, reference, run
        from weights import Spec
        cell = run.load_cell("qwen2-0.5b.train.mix")
        spec = Spec.from_json(dict(cell["config"], **{TINY!r}))
        train = dict(cell["traffic"], seq_len=32, tokens_per_worker=4096)
        batches = cell_train.first_batches(spec, train, {SEED}, 3)
        one, four = (reference.run(spec, train, {SEED}, 0, batches, devs)
                     for devs in (jax.devices()[:1], jax.devices()[:4]))
        same = (np.array_equal(one["loss"], four["loss"])
                and one["grad1"] == four["grad1"]
                and one["deltas"] == four["deltas"])
        print(json.dumps({{"same": same, "loss": one["loss"].tolist()}}))
        """)
    assert res.returncode == 0, res.stderr[-4000:]
    got = json.loads(res.stdout.strip().splitlines()[-1])
    assert got["same"] is True
