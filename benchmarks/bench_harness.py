"""Production-harness throughput: slots/sec of the plan-driven launch path.

The launch path now runs through the timeline engine (`launch.harness`):
readiness-policy plans compiled into event-sparse jitted scans over the
per-worker transformer step.  This benchmark measures what a production
slot costs per policy on the smoke transformer config — STEADY-STATE: one
`TrainHarness` is compiled, a full warmup pass populates every jit
signature the plan can hit (all pow2 chunk lengths, every event kind), and
a second pass over a fresh carry is timed.  The plan's protocol accounting
(rounds, events, idle worker-slots) is emitted from the shared trace
schema — the same document the simulator and the launcher export.

Emits ``harness/...`` CSV lines and writes BENCH_harness.json at the repo
root (the nightly job uploads it; `common.load_bench_json` is the baseline
a future regression gate can diff against).  ``--mesh W,D`` re-runs the
same plans through the SPMD shard_map path: records gain a ``_meshWxD``
suffix plus ``tags`` (mesh shape, device count) so the nightly gate
compares like-for-like, and each policy's first mixing event is both
timed and costed from its compiled HLO (`launch.hlo_analysis`) — the
measured-vs-predicted pair the roofline report reads.

  PYTHONPATH=src python -m benchmarks.bench_harness [--smoke]
  XLA_FLAGS=--xla_force_host_platform_device_count=8 \
      PYTHONPATH=src python -m benchmarks.bench_harness --mesh 4,2
"""
from __future__ import annotations

import argparse
import dataclasses
import time

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks import common
from repro.configs.registry import get_smoke_config
from repro.core import timeline
from repro.core.mllsgd import MLLConfig, build_network, build_state
from repro.core.protocol import init_train_state
from repro.data.pipeline import LMBatcher, make_token_stream
from repro.launch import hlo_analysis
from repro.launch.harness import TrainHarness, shard_train_state
from repro.launch.mesh import make_mesh
from repro.launch.train import replicate_params
from repro.models import model as model_mod

POLICIES = ("deadline", "barrier", "gossip")
RATES = (1.0, 0.9, 1.0, 0.6)


def _mix_event_costs(harness, plan, batcher, state):
    """Time the plan's first mixing event and cost its compiled HLO.

    Returns ``(seconds, HloCosts)`` or None for a plan with no events.
    The entry's ``.build(*args)`` hands back the underlying jitted
    function (shard_map'd under a mesh), so the analyzed HLO is exactly
    what the timed call executes — including the psum/ppermute/all_gather
    collectives the SPMD lowerings emit."""
    op_mats = plan.op_mats or {}
    batch = batcher.sample(np.random.default_rng(1))
    for e in range(plan.slots):
        act = jnp.asarray(plan.active[e])
        if e in op_mats:
            entry = harness.dense_step
            args = (state, batch, act, jnp.asarray(op_mats[e]))
            break
        if plan.op_ids[e] != 0:
            entry = harness.event_step[int(plan.op_ids[e])]
            args = (state, batch, act)
            break
    else:
        return None
    fn = entry.build(*args)
    costs = hlo_analysis.analyze_hlo(fn.lower(*args).compile().as_text())
    # entry points donate the state: each call feeds the previous output
    out = fn(*args)
    jax.block_until_ready(out[0].params)           # compile + warm
    reps = 4
    t0 = time.time()
    for _ in range(reps):
        out = fn(out[0], *args[1:])
    jax.block_until_ready(out[0].params)
    return (time.time() - t0) / reps, costs


def bench_policy(cfg, policy: str, slots: int, *, seq_len: int,
                 batch: int, mesh=None, tag: str = "",
                 tags: dict | None = None) -> None:
    mll = MLLConfig(tau=4, q=2, eta=0.05, hub_topology="complete",
                    worker_rates=RATES)
    network = build_network(
        dataclasses.replace(mll, granularity="worker_per_data"), 2, 2)
    st = build_state(mll, network)
    plan = timeline.get_policy(policy).plan(
        network, mll.schedule, slots, np.random.default_rng(0))
    params = model_mod.init_model(jax.random.PRNGKey(0), cfg)
    stacked = replicate_params(params, network.num_workers)
    stream = make_token_stream(network.num_workers, 8192,
                               vocab_size=cfg.vocab_size, seed=0)
    batcher = LMBatcher(stream, seq_len, batch)
    harness = TrainHarness(cfg, mll, st, gate_mode=plan.gate_mode, mesh=mesh)

    def full_pass():
        # a fresh copy per pass: the harness donates the state it is given
        state = init_train_state(jax.tree.map(jnp.copy, stacked), cfg=mll)
        if mesh is not None:
            state = shard_train_state(state, mesh, network.num_workers)
        rng = np.random.default_rng(0)
        return harness.run_span(state, plan, batcher, rng, 0, plan.slots)

    jax.block_until_ready(full_pass()[0].params)   # compile every signature
    t0 = time.time()
    state, _ = full_pass()             # steady state, same jit caches
    jax.block_until_ready(state.params)
    dt = time.time() - t0

    doc = timeline.plan_trace(plan, policy=policy, source="bench_harness")
    common.emit(f"harness/slots_per_sec_{policy}{tag}", slots / dt, t0=t0,
                tags=tags)
    common.emit(f"harness/rounds_{policy}{tag}", int(doc["rounds_completed"]),
                tags=tags)
    common.emit(f"harness/events_{policy}{tag}", len(doc["events"]),
                tags=tags)
    common.emit(f"harness/idle_worker_slots_{policy}{tag}",
                int(np.sum(doc["idle_slots"])), tags=tags)
    mix = _mix_event_costs(harness, plan, batcher, state)
    if mix is not None:
        secs, costs = mix
        common.emit(f"harness/mix_ms_{policy}{tag}", secs * 1e3, tags=tags)
        common.emit(f"harness/mix_pred_gflops_{policy}{tag}",
                    costs.flops / 1e9, tags=tags)
        common.emit(f"harness/mix_pred_gbytes_{policy}{tag}",
                    costs.bytes / 1e9, tags=tags)
        common.emit(f"harness/mix_collective_gbytes_{policy}{tag}",
                    costs.collective_bytes / 1e9, tags=tags)


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="tiny slot budget (CI-sized)")
    ap.add_argument("--slots", type=int, default=None)
    ap.add_argument("--mesh", metavar="W,D", default=None,
                    help="run the SPMD shard_map path over a (workers, data) "
                         "mesh — needs XLA_FLAGS=--xla_force_host_platform_"
                         "device_count=N; records gain a _meshWxD suffix + "
                         "tags")
    args = ap.parse_args(argv)
    slots = args.slots or (16 if args.smoke else 64)
    seq_len, batch = (32, 2) if args.smoke else (64, 4)
    cfg = get_smoke_config("qwen2-0.5b")
    mesh, tag, tags = None, "", None
    if args.mesh:
        mw, md = (int(x) for x in args.mesh.split(","))
        mesh = make_mesh((mw, md), ("workers", "data"))
        tag = f"_mesh{mw}x{md}"
        tags = {"mesh": f"{mw}x{md}", "devices": jax.device_count()}

    common.begin_bench("harness")
    for policy in POLICIES:
        bench_policy(cfg, policy, slots, seq_len=seq_len, batch=batch,
                     mesh=mesh, tag=tag, tags=tags)
    common.end_bench("harness")
    # merge into the committed snapshot so vmap and mesh-tagged entries
    # ride in ONE trajectory file (a --mesh run must not clobber the vmap
    # baseline the nightly gate diffs, and vice versa)
    records = common.load_bench_json("harness") or {}
    records.update(common.bench_records("harness"))
    common.write_bench_json("harness", records)


if __name__ == "__main__":
    main()
