"""The trainer's own trace marks (`launch.spans`), on the smoke config:

* the step programs carry the device scopes ``mll.grads``, ``mll.update``
  and ``mll.mix.*`` in their op metadata, and the Pallas calls their names,
* `TrainHarness.run_span` emits its host spans in plan order, with the
  slots each covers as a stat beside a bare name, in a profiler trace,
* `TrainHarness.retraces` counts traces by entry point, phase and chunk
  length, and `run_plan`'s log names them once something retraced.
"""
import dataclasses
import glob
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.registry import get_smoke_config
from repro.core import protocol
from repro.core.mllsgd import MLLConfig, build_network, build_state
from repro.core.protocol import init_train_state
from repro.core.timeline import get_policy
from repro.data.pipeline import LMBatcher, make_token_stream
from repro.launch import spans
from repro.launch.harness import TrainHarness, run_plan
from repro.launch.train import replicate_params
from repro.models import model as model_mod

CFG = get_smoke_config("qwen2-0.5b")
TAU, Q = 8, 2
ROUND = TAU * Q
W = 4
HOST = {spans.RUN_SPAN, spans.DRAW_BATCH, spans.STACK_BATCHES,
        spans.LOCAL_SCAN, spans.event_step(1), spans.event_step(2),
        spans.DENSE_STEP, spans.SKIP_IDLE}


@dataclasses.dataclass
class Cell:
    mll: MLLConfig
    network: object
    st: object
    plan: object

    def harness(self, impl="xla"):
        return TrainHarness(CFG, self.mll, self.st,
                            gate_mode=self.plan.gate_mode, impl=impl)

    def state(self):
        params = model_mod.init_model(jax.random.PRNGKey(0), CFG)
        return init_train_state(replicate_params(params, W), cfg=self.mll)

    @staticmethod
    def feed():
        stream = make_token_stream(W, 4096, vocab_size=CFG.vocab_size, seed=0)
        return LMBatcher(stream, 16, 1), np.random.default_rng(0)


@pytest.fixture(scope="module")
def cell():
    """Deadline plan, tau 8, q 2, two sub-networks of two workers."""
    mll = MLLConfig(tau=TAU, q=Q, eta=0.005, hub_topology="ring",
                    worker_rates=(1.0, 0.5, 1.0, 0.5))
    network = build_network(
        dataclasses.replace(mll, granularity="worker_per_data"), 2, 2)
    st = build_state(mll, network)
    plan = get_policy("deadline").plan(network, mll.schedule, 4 * ROUND,
                                       np.random.default_rng(0),
                                       rate_model="bernoulli")
    return Cell(mll, network, st, plan)


def _locations(lowered) -> list[str]:
    """The name-stack locations of a lowered program's ops."""
    return re.findall(r'loc\("([^"]*)"', lowered.as_text(debug_info=True))


@pytest.mark.parametrize("entry", ["local_scan", "event_step.1",
                                   "event_step.2"])
def test_step_programs_carry_scopes_and_kernel_names(cell, entry):
    h = cell.harness(impl="flash")
    batcher, rng = cell.feed()
    batch = batcher.sample(rng)
    state = cell.state()
    if entry == "local_scan":
        lowered = h.local_scan.lower(
            state, jax.tree.map(lambda x: x[None], batch),
            jnp.ones((1, W), bool))
        mix = set()
    else:
        ph = int(entry.rsplit(".", 1)[1])
        lowered = h.event_step[ph].lower(state, batch, jnp.ones((W,), bool))
        mix = {spans.MIX_SUBNET if ph == protocol.PHASE_SUBNET
               else spans.MIX_HUB}
    locs = _locations(lowered)
    found = {m for loc in locs for m in re.findall(r"mll\.[a-z.]+", loc)}
    assert found == {spans.GRADS, spans.UPDATE} | mix
    # the backward runs inside the gradient scope too
    assert any(spans.GRADS in loc and "transpose" in loc for loc in locs)
    calls = [loc for loc in locs if re.search(r"\w/pallas_call", loc)]
    kernels = {re.search(r"(\w+)/pallas_call", loc).group(1) for loc in calls}
    assert kernels == {"flash_fwd", "flash_dq", "flash_dkv"}


def _host_events(directory) -> list:
    """(name, start, end, stats) of the trainer's host spans in the newest
    trace under ``directory``, parents before children."""
    path = sorted(glob.glob(os.path.join(directory, "**", "*.xplane.pb"),
                            recursive=True), key=os.path.getmtime)[-1]
    pd = jax.profiler.ProfileData.from_file(path)
    out = []
    for plane in pd.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.split("#")[0] in HOST:
                    out.append((ev.name, ev.start_ns,
                                ev.start_ns + ev.duration_ns,
                                {k: int(v) for k, v in ev.stats}))
    return sorted(out, key=lambda e: (e[1], -e[2]))


def _chunks(n: int) -> list[int]:
    out = []
    while n:
        out.append(1 << (n.bit_length() - 1))
        n -= out[-1]
    return out


def test_run_span_emits_spans_with_slot_counts(cell, tmp_path):
    """A warm hub round, traced: the spans come in plan order with bare
    names, the slots on them add up to the plan's, and the round
    retraces nothing."""
    h = cell.harness()
    batcher, rng = cell.feed()
    state, m = h.run_span(cell.state(), cell.plan, batcher, rng, 0, ROUND)
    jax.block_until_ready(state)
    traced = dict(h.retraces)
    jax.profiler.start_trace(str(tmp_path))
    try:
        state, m = h.run_span(state, cell.plan, batcher, rng, ROUND,
                              2 * ROUND)
        jax.block_until_ready(state)
    finally:
        jax.profiler.stop_trace()
    assert dict(h.retraces) == traced

    events = _host_events(str(tmp_path))
    names = [e[0] for e in events]
    # tau 8: a subnet event after each 7 local slots, the hub after 15
    local = []
    for k in _chunks(TAU - 1):
        local += [spans.DRAW_BATCH] * k + [spans.STACK_BATCHES,
                                           spans.LOCAL_SCAN]
    event = [spans.DRAW_BATCH, spans.STACK_BATCHES]
    assert names == ([spans.RUN_SPAN] + local + event + [spans.event_step(1)]
                     + local + event + [spans.event_step(2)])
    run = events[0]
    assert run[3] == {"lo": ROUND, "hi": 2 * ROUND}
    assert all(run[1] <= e[1] and e[2] <= run[2] for e in events[1:])

    ops = cell.plan.op_ids[ROUND:2 * ROUND]
    slots = {}
    for name, _, _, stats in events:
        slots[name] = slots.get(name, 0) + stats.get("slots", 0)
    assert slots[spans.LOCAL_SCAN] == int((ops == 0).sum()) == 14
    assert slots[spans.event_step(1)] == int((ops == 1).sum()) == 1
    assert slots[spans.event_step(2)] == int((ops == 2).sum()) == 1
    assert [e[3]["slots"] for e in events if e[0] == spans.LOCAL_SCAN] \
        == _chunks(TAU - 1) * 2
    assert all(e[3]["idle"] == 0 for e in events
               if e[0].startswith(spans.EVENT_STEP))


def test_retraces_count_entry_phase_and_chunk(cell):
    h = cell.harness()
    batcher, rng = cell.feed()
    state, _ = h.run_span(cell.state(), cell.plan, batcher, rng, 0, ROUND)
    assert dict(h.retraces) == {
        ("local_scan", protocol.PHASE_LOCAL, 4): 1,
        ("local_scan", protocol.PHASE_LOCAL, 2): 1,
        ("local_scan", protocol.PHASE_LOCAL, 1): 1,
        ("event_step", protocol.PHASE_SUBNET, 1): 1,
        ("event_step", protocol.PHASE_HUB, 1): 1}
    state, _ = h.run_span(state, cell.plan, batcher, rng, ROUND, 2 * ROUND)
    assert sum(h.retraces.values()) == 5
    # a chunk length the plan never asked for is a new program
    batches = {k: jnp.stack([b[k] for b in (batcher.sample(rng),) * 3])
               for k in ("tokens", "labels")}
    h.local_scan(state, batches, jnp.ones((3, W), bool))
    assert h.retraces["local_scan", protocol.PHASE_LOCAL, 3] == 1
    assert sum(h.retraces.values()) == 6


def test_run_plan_logs_retraces_after_the_first_boundary(cell):
    """Boundaries every 4 slots: the first runs one 4-slot chunk; the
    second traces the 2- and 1-slot chunks and the subnet event, so its
    log line names the traces."""
    batcher, rng = cell.feed()
    lines = []
    run_plan(CFG, cell.mll, cell.network, cell.st, cell.plan, batcher, rng,
             cell.state(), stop_slot=12, eval_every=4, log=lines.append)
    assert len(lines) == 3
    assert "retraces" not in lines[0]
    assert "('event_step', 1, 1): 1" in lines[1]
    assert "('local_scan', 0, 2): 1" in lines[1]
