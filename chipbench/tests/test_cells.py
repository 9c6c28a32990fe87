"""Each cell end to end at a tiny size on the CPU (Pallas in interpret
mode): set-up, the followed slots, a one-second window, the reference and
the comparison, and the result line's keys."""
import json
import os
import subprocess
import sys

import jax
import pytest

import run

from conftest import BENCH, PEAKS, ROOT, tiny_cell

ONE_CHIP = ["qwen2-0.5b.train.local", "qwen2-0.5b.train.mix"]


def _check(out: dict, cell: dict, trace: bool) -> None:
    assert out["correct"] is True, out["checks"]
    assert out["attempted"] > 0 and out["failed"] == 0
    assert list(out)[-1] == "checks"
    assert set(out["checks"]) == set(cell["limits"])
    names = set(out["metrics"])
    if trace:
        # the CPU's trace holds no TPU plane: every reader finds nothing
        assert names <= {m["name"] for m in cell["per_layer"]}
        assert {"busy_s", "window_s"} <= set(out["device"])
    else:
        assert names == {"train_tokens_per_s", "setup_s"}
    assert out["device"]["count"] == cell["chips"]


@pytest.mark.parametrize("name,trace", [(ONE_CHIP[0], False),
                                        (ONE_CHIP[1], True)])
def test_one_chip_cell(name, trace):
    cell = tiny_cell(name)
    out = run.run(cell, 2**31 + 17, 1.0, trace, jax.devices()[:1], PEAKS,
                  log=lambda *a, **k: None)
    json.dumps(out)
    _check(out, cell, trace)


def test_exits_without_a_tpu():
    res = subprocess.run(
        [sys.executable, "chipbench/run.py", "--workload",
         ONE_CHIP[0], "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=ROOT, env=dict(os.environ, JAX_PLATFORMS="cpu"),
        capture_output=True, text=True, timeout=300)
    assert res.returncode != 0
    assert res.stdout.strip() == ""


def test_exits_without_the_program(tmp_path):
    """A directory with only BENCHMARK.json and chipbench/ holds no
    trainer: the run fails and prints no result."""
    subprocess.run(["cp", "-r", BENCH, str(tmp_path / "chipbench")],
                   check=True)
    subprocess.run(["cp", os.path.join(ROOT, "BENCHMARK.json"),
                    str(tmp_path)], check=True)
    res = subprocess.run(
        [sys.executable, "chipbench/run.py", "--workload", ONE_CHIP[0],
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=tmp_path, capture_output=True, text=True, timeout=300)
    assert res.returncode != 0
    assert res.stdout.strip() == ""
