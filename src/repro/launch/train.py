"""Plan-driven MLL-SGD training launcher.

The launch path runs through the timeline engine: a readiness policy from
`core.timeline` (``--policy barrier|deadline|gossip`` or any
``@register_policy`` entry) compiles a `TimelinePlan` for the slot budget,
and `launch.harness` executes it over the production transformer step —
event-sparse jitted local scans between mixing events, the registered
mixing strategy (or per-event masked dense operators for gossip) at each
event.  The default ``policy="deadline"`` with the Bernoulli gate
reproduces the legacy lock-step tick loop bit for bit; the other policies
express what that loop never could: straggler barriers, overlapping subnet
rounds, neighbor-ready gossip — on real devices, not just the simulator.

Per-worker rates can be hand-fed (``--rates``, the paper's p_i) or MEASURED
(``--rate-model measured``): a warmup pass profiles per-device step times,
derives the rate staircase, and serializes the calibration next to the
plan.  Checkpoints carry the full protocol state (params + inner-opt +
mixing state + timeline/data cursors); ``--resume`` continues a killed run
to a bit-identical trajectory.

  PYTHONPATH=src python -m repro.launch.train --arch qwen3-1.7b --smoke \\
      --steps 64 --tau 8 --q 4 --eta 0.05 --topology ring \\
      --policy gossip --rates 1.0 0.5 1.0 0.25 \\
      --checkpoint-dir /tmp/ck [--resume] [--trace /tmp/trace.json]
"""
from __future__ import annotations

import argparse
import dataclasses
import os
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ArchConfig
from repro.configs.registry import ARCH_IDS, get_config, get_smoke_config
from repro.core.mllsgd import MLLConfig, build_network, build_state
from repro.core.protocol import (available_mixing, describe_mixing,
                                 init_train_state)
from repro.core.timeline import (RATE_MODELS, RateCalibration,
                                 available_policies, get_policy)
from repro.data.pipeline import LMBatcher, make_token_stream, rng_from_state
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.harness import (CALIBRATION_FILE, measure_worker_rates,
                                  plan_config, resolve_measured_network,
                                  run_plan)
from repro.launch.mesh import make_mesh
from repro.models import model as model_mod
from repro.optim import optimizers as optim_mod
from repro.train import checkpoint

PyTree = Any


@dataclasses.dataclass
class TrainLoopConfig:
    steps: int = 64                  # slot budget (ticks under "deadline")
    eval_every: int = 16
    seq_len: int = 128
    batch_per_worker: int = 4
    tokens_per_worker: int = 65536
    seed: int = 0
    checkpoint_dir: str | None = None
    checkpoint_every: int = 0
    policy: str = "deadline"         # any registered readiness policy
    rate_model: str = "bernoulli"    # bernoulli | deterministic | measured
    resume: bool = False             # continue from checkpoint_dir's state
    stop_slot: int | None = None     # execute only [start, stop_slot) of the
                                     # plan and checkpoint there (kill point)
    trace_path: str | None = None    # export the event trace (JSON)
    impl: str = "xla"                # mixer implementation: xla | flash |
                                     # pallas (native-training Pallas kernels)
    mesh: tuple[int, int] | None = None  # (workers, data): compile the plan
                                     # to shard_map over a device mesh with
                                     # real mixing collectives (--mesh W,D);
                                     # None = single-device vmap.  NOT part
                                     # of the resume guard: trajectories and
                                     # checkpoints are device-count-portable
    overlap: str = "none"            # "chunked": mix the packed buffer
                                     # chunk-by-chunk (overlaps hub exchange
                                     # with local compute; rtol-equivalent)
    overlap_chunks: int = 4          # lane chunks per mixing event


def replicate_params(params: PyTree, w: int) -> PyTree:
    return jax.tree.map(
        lambda x: jnp.broadcast_to(x[None], (w,) + x.shape), params)


def _calibrate(cfg: ArchConfig, loop: TrainLoopConfig, stacked: PyTree,
               batcher: LMBatcher, log) -> RateCalibration:
    """Measured-rate warmup pass.  The calibration is an artifact of the
    run directory: if one is already serialized there it is reloaded
    (re-measuring would change the plan — fatal for a resumed run, silently
    divergent for a re-run); the warmup batch comes from a PRIVATE rng so
    the training data cursor is untouched."""
    path = (os.path.join(loop.checkpoint_dir, CALIBRATION_FILE)
            if loop.checkpoint_dir else None)
    if path and os.path.exists(path):
        log(f"reusing serialized calibration {path}")
        return RateCalibration.load(path)
    if loop.resume:
        raise FileNotFoundError(
            "rate_model='measured' resume needs the original calibration "
            f"next to the checkpoint ({path})")
    warm = batcher.sample(np.random.default_rng(loop.seed + 0x5eed))
    calibration = measure_worker_rates(cfg, stacked, warm, impl=loop.impl)
    if path:
        os.makedirs(loop.checkpoint_dir, exist_ok=True)
        calibration.save(path)
    log(f"measured step times (s): "
        f"{['%.4f' % t for t in calibration.step_times]} -> rates "
        f"{['%.2f' % r for r in calibration.rates]}")
    return calibration


def run_training(cfg: ArchConfig, mll: MLLConfig, loop: TrainLoopConfig,
                 *, num_subnets: int = 2, workers_per_subnet: int = 2,
                 log=print) -> dict:
    """Thin wrapper over the plan-driven harness (`launch.harness.run_plan`).

    Builds the network, synthetic data and protocol state, compiles the
    readiness policy's `TimelinePlan` for ``loop.steps`` slots, and executes
    it.  With ``policy="deadline"`` + the Bernoulli rate model this
    reproduces the legacy per-tick loop bit for bit (regression-tested).
    Returns loss history + final averaged params (+ plan/trace/state, and
    the `TrainHarness` whose compiled steps ran the plan).
    """
    if loop.impl not in ("xla", "flash", "pallas"):
        raise ValueError(f"unknown impl {loop.impl!r} (xla | flash | pallas)")
    if loop.resume and not loop.checkpoint_dir:
        raise ValueError("--resume needs --checkpoint-dir")
    if loop.stop_slot is not None and not loop.checkpoint_dir:
        raise ValueError("--stop-slot checkpoints the kill point; it needs "
                         "--checkpoint-dir (otherwise the partial run's "
                         "state is discarded and --resume is impossible)")
    network = build_network(
        dataclasses.replace(mll, granularity="worker_per_data"),
        num_subnets, workers_per_subnet)
    st = build_state(mll, network)
    w = network.num_workers
    key = jax.random.PRNGKey(loop.seed)
    params = model_mod.init_model(key, cfg)
    stacked = replicate_params(params, w)
    n_params = sum(int(x.size) for x in jax.tree.leaves(params))
    log(f"arch={cfg.name} params={n_params/1e6:.1f}M workers={w} "
        f"(D={num_subnets} x N={workers_per_subnet}) tau={mll.tau} q={mll.q} "
        f"policy={loop.policy} rate_model={loop.rate_model}")

    stream = make_token_stream(w, loop.tokens_per_worker,
                               vocab_size=cfg.vocab_size, seed=loop.seed)
    batcher = LMBatcher(stream, loop.seq_len, loop.batch_per_worker)
    rng = np.random.default_rng(loop.seed)

    calibration = None
    if loop.rate_model == "measured":
        calibration = _calibrate(cfg, loop, stacked, batcher, log)
        network = resolve_measured_network(network, calibration)
        st = build_state(mll, network)

    pol = get_policy(loop.policy)
    # needs_dense policies (gossip) mix strict worker subsets via masked
    # dense operators at full precision — compressed wire formats have no
    # partial-participation form — so every registered strategy runs here:
    # its wire format applies to the full V/Z rounds only.
    plan = pol.plan(network, mll.schedule, loop.steps,
                    np.random.default_rng(loop.seed),
                    rate_model=loop.rate_model)
    log(f"plan: {plan.rounds_completed} rounds / {len(plan.events)} events "
        f"in {plan.slots} slots (used {plan.slots_used}, "
        f"idle worker-slots {int(plan.idle_slots.sum())})")

    mesh = None
    if loop.mesh is not None:
        mw, md = loop.mesh
        if mw < 1 or w % mw:
            raise ValueError(
                f"mesh {loop.mesh}: the workers axis ({mw}) must divide the "
                f"fleet W={w} (D={num_subnets} x N={workers_per_subnet}) — "
                "fix --mesh")
        mesh = make_mesh((mw, md), ("workers", "data"))
        log(f"mesh: workers={mw} data={md} over {mw * md} devices "
            f"({jax.devices()[0].platform})")

    # full protocol state: inner-optimizer + mixing state ride along, so
    # MLLConfig(inner_opt=..., mixing="int8_ef") runs end-to-end here
    train_state = init_train_state(stacked, cfg=mll)
    start_slot = 0
    last_worker_loss = None
    # everything that determines the trajectory: the plan-defining config
    # plus the run-loop fields that drive the shared data cursor (eval
    # draws and batch shapes consume the same rng stream)
    current = dict(plan_config(mll, network, plan, loop.policy,
                               loop.rate_model),
                   arch=cfg.name, impl=loop.impl, overlap=loop.overlap,
                   overlap_chunks=loop.overlap_chunks,
                   eval_every=loop.eval_every, seq_len=loop.seq_len,
                   batch_per_worker=loop.batch_per_worker,
                   tokens_per_worker=loop.tokens_per_worker,
                   loop_seed=loop.seed)
    if loop.resume:
        train_state, start_slot, extra = checkpoint.restore_state(
            loop.checkpoint_dir, train_state)
        saved = extra.get("plan_config")
        if saved is not None and "impl" not in saved:
            # checkpoints written before the kernel-training PR carry no
            # impl field; they were xla-impl runs by construction
            saved = dict(saved, impl="xla")
        if saved is not None and "overlap" not in saved:
            # pre-overlap checkpoints ran the unchunked event path
            saved = dict(saved, overlap="none", overlap_chunks=4)
        if saved is not None and saved != current:
            diff = {k: (saved.get(k), current[k]) for k in current
                    if saved.get(k) != current[k]}
            raise ValueError(
                "resume config mismatch — the checkpoint was written under "
                "a different plan; resuming would splice two plans into one "
                f"trajectory.  Differing (saved, current): {diff}")
        rng = rng_from_state(extra["rng_state"])
        last_worker_loss = extra.get("last_worker_loss")
        log(f"resumed from slot {start_slot} "
            f"(policy={extra.get('policy')}, saved rng restored)")

    run = run_plan(cfg, mll, network, st, plan, batcher, rng, train_state,
                   start_slot=start_slot, stop_slot=loop.stop_slot,
                   eval_every=loop.eval_every,
                   checkpoint_dir=loop.checkpoint_dir,
                   checkpoint_every=loop.checkpoint_every,
                   calibration=calibration, trace_path=loop.trace_path,
                   policy=loop.policy, rate_model=loop.rate_model,
                   last_worker_loss=last_worker_loss, run_config=current,
                   impl=loop.impl, mesh=mesh, overlap=loop.overlap,
                   overlap_chunks=loop.overlap_chunks, log=log)
    return {"history": run.history, "avg_params": run.avg_params,
            "network": run.network, "plan": run.plan,
            "train_state": run.train_state, "calibration": run.calibration,
            "trace_path": run.trace_path, "harness": run.harness}


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen3-1.7b", choices=ARCH_IDS)
    ap.add_argument("--smoke", action="store_true",
                    help="use the reduced smoke config (CPU-friendly)")
    ap.add_argument("--steps", type=int, default=64,
                    help="slot budget (ticks under policy='deadline')")
    ap.add_argument("--tau", type=int, default=8)
    ap.add_argument("--q", type=int, default=4)
    ap.add_argument("--eta", type=float, default=0.05)
    ap.add_argument("--topology", default="complete")
    ap.add_argument("--mixing", default="dense", metavar="NAME",
                    help="registered mixing strategy; 'list' prints the "
                         "registry with wire-format descriptions and exits")
    ap.add_argument("--inner-opt", default="sgd",
                    choices=tuple(sorted(optim_mod.OPTIMIZERS)))
    ap.add_argument("--subnets", type=int, default=2)
    ap.add_argument("--workers-per-subnet", type=int, default=2)
    ap.add_argument("--seq-len", type=int, default=128)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--rates", type=float, nargs="*", default=None,
                    help="per-worker p_i (heterogeneous operating rates)")
    ap.add_argument("--policy", default="deadline",
                    choices=available_policies(),
                    help="readiness policy compiling the timeline plan")
    ap.add_argument("--rate-model", default="bernoulli", choices=RATE_MODELS,
                    help="'measured' profiles per-device step times in a "
                         "warmup pass instead of using hand-fed p_i")
    ap.add_argument("--impl", default="xla",
                    choices=("xla", "flash", "pallas"),
                    help="mixer implementation for train/eval steps: 'flash'"
                         "/'pallas' run the native-training Pallas kernels "
                         "(fwd + custom-vjp bwd), 'xla' the pure-XLA path")
    ap.add_argument("--mesh", default=None, metavar="W,D",
                    help="compile the plan to shard_map over a (workers, "
                         "data) device mesh with real mixing collectives, "
                         "e.g. --mesh 4,2 on 8 devices (CPU: set XLA_FLAGS="
                         "--xla_force_host_platform_device_count=8); "
                         "checkpoints stay portable across mesh shapes")
    ap.add_argument("--overlap", default="none", choices=("none", "chunked"),
                    help="'chunked' mixes the packed buffer chunk-by-chunk "
                         "so hub exchange overlaps local compute (requires "
                         "inner_opt=sgd and a dense-operator mixing; "
                         "rtol-equivalent reduction-order change)")
    ap.add_argument("--overlap-chunks", type=int, default=4,
                    help="lane chunks per mixing event under --overlap "
                         "chunked")
    ap.add_argument("--eval-every", type=int, default=16)
    ap.add_argument("--checkpoint-dir", default=None)
    ap.add_argument("--resume", action="store_true",
                    help="continue from the full-protocol checkpoint in "
                         "--checkpoint-dir (bit-identical trajectory)")
    ap.add_argument("--stop-slot", type=int, default=None,
                    help="execute only up to this slot of the plan and "
                         "checkpoint there (simulated kill / partial run)")
    ap.add_argument("--trace", default=None,
                    help="export the event trace (simulator schema) here")
    args = ap.parse_args(argv)
    enable_compile_cache()

    if args.mixing == "list":
        print(describe_mixing())
        return
    if args.mixing not in available_mixing():
        ap.error(f"unknown mixing {args.mixing!r}; registered: "
                 f"{', '.join(available_mixing())} (or 'list' to describe)")

    cfg = get_smoke_config(args.arch) if args.smoke else get_config(args.arch)
    mesh = None
    if args.mesh:
        try:
            mesh = tuple(int(x) for x in args.mesh.split(","))
            if len(mesh) != 2:
                raise ValueError
        except ValueError:
            ap.error(f"--mesh must be 'W,D' (two ints), got {args.mesh!r}")
    rates = tuple(args.rates) if args.rates else 1.0
    mll = MLLConfig(tau=args.tau, q=args.q, eta=args.eta,
                    hub_topology=args.topology, mixing=args.mixing,
                    inner_opt=args.inner_opt, worker_rates=rates)
    loop = TrainLoopConfig(steps=args.steps, eval_every=args.eval_every,
                           seq_len=args.seq_len,
                           batch_per_worker=args.batch,
                           checkpoint_dir=args.checkpoint_dir,
                           checkpoint_every=max(args.steps // 2, 1)
                           if args.checkpoint_dir else 0,
                           policy=args.policy, rate_model=args.rate_model,
                           resume=args.resume, stop_slot=args.stop_slot,
                           trace_path=args.trace, impl=args.impl,
                           mesh=mesh, overlap=args.overlap,
                           overlap_chunks=args.overlap_chunks)
    out = run_training(cfg, mll, loop, num_subnets=args.subnets,
                       workers_per_subnet=args.workers_per_subnet)
    losses = out["history"]["avg_loss"]
    if losses:
        print(f"final u_k loss: {losses[-1]:.4f} "
              f"(first recorded {losses[0]:.4f})")


if __name__ == "__main__":
    main()
