"""What `correct` must reject, at a tiny size on the CPU.

The control: the reference put in the trainer's place, with every matmul
in a precision below the configuration's bf16.  At the cells' own size
the control is int8; at this size int8 reads within 1-3x of sound runs
(its per-tensor scale costs little on narrow tensors), so float8 stands
in for it here.  The faults a
training cell can have, planted in the trainer underneath a whole run:
a step that returns its state unchanged; half of each batch left out of
the loss; the exchange between workers left out."""
import jax
import pytest

import cell_train
import compare
import reference
import run
from weights import Spec

from conftest import PEAKS, tiny_cell


@pytest.mark.parametrize("name", ["qwen2-0.5b.train.local",
                                  "qwen2-0.5b.train.mix"])
def test_float8_control_fails(name):
    cell = tiny_cell(name)
    spec, train = Spec.from_json(cell["config"]), cell["traffic"]
    seed = 11
    batches = cell_train.first_batches(spec, train, seed,
                                       train["check_slots"][-1])
    dev = jax.devices()[0]
    ref = reference.run(spec, train, seed, seed, batches, [dev])
    ctl = reference.run(spec, train, seed, seed, batches, [dev],
                        lowp="float8_e4m3fn")
    ok, rows, _ = compare.check(ctl, ref, cell["limits"])
    assert not ok, rows


def _unchanged(real):
    def step(train_state, *a, **k):
        new, metrics = real(train_state, *a, **k)
        return train_state._replace(step=new.step), metrics
    return step


def _no_exchange(real):
    from repro.core import protocol

    def step(*a, **k):
        k.pop("phase", None)
        return real(*a, phase=protocol.PHASE_LOCAL, **k)
    return step


def _half_batch(real):
    def ce(logits, labels, mask=None):
        t = labels.shape[-1] // 2
        return real(logits[..., :t, :], labels[..., :t])
    return ce


@pytest.mark.parametrize("fault", ["unchanged", "half_batch", "no_exchange"])
@pytest.mark.parametrize("name", ["qwen2-0.5b.train.local",
                                  "qwen2-0.5b.train.mix"])
def test_planted_fault_fails(monkeypatch, name, fault):
    from repro.launch import harness
    from repro.train import train_step
    if fault == "half_batch":
        monkeypatch.setattr(train_step, "cross_entropy",
                            _half_batch(train_step.cross_entropy))
    else:
        wrap = _unchanged if fault == "unchanged" else _no_exchange
        monkeypatch.setattr(harness, "mll_harness_step",
                            wrap(harness.mll_harness_step))
    cell = tiny_cell(name)
    out = run.run(cell, 23, 1.0, False, jax.devices()[:1], PEAKS,
                  log=lambda *a, **k: None)
    assert out["correct"] is False, out["checks"]
