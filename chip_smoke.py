"""Chip smoke: run the MLL-SGD trainer and server once on a TPU.

    python chip_smoke.py             # one chip: train, check, serve
    python chip_smoke.py --chips 4   # four chips: shard_map mesh vs vmap

Default run, in one process on one chip, with qwen2-0.5b at its published
width (random weights from seed `SEED`):

  (a) print the device JAX sees; anything but a TPU exits non-zero here;
  (b) train a 2 subnets x 2 workers fleet through `run_training`, the
      function ``python -m repro.launch.train`` calls: Pallas flash
      attention, deadline policy, tau=2 q=2, ring hubs, two_stage mixing,
      8 slots at batch 1 x seq 128, checkpoint at the end.  The u_k losses
      must be finite and fall, and the compiled local step must hold a
      `tpu_custom_call` (the kernels ran compiled, not interpreted);
  (c) one gradient step on u_k with the flash kernels and with plain XLA:
      loss and gradients agree within a bf16 tolerance, and again in f32
      at full matmul precision within a tight one, leaf by leaf;
  (d) serve u_k from the checkpoint with the flash-decode engine: every
      request finishes, every cache block returns, and flash-decode on the
      live cache agrees with the gather + softmax reference.

``--chips 4`` runs only the mesh phase: the plan of (b) with XLA attention
on a (4, 1) (workers, data) mesh, one worker per chip, against the same
plan through the single-device vmap path on chip 0.

Per-phase wall time and peak device memory are printed as information, not
as metrics.  The last line of standard output is one JSON object naming
the device; it is printed only when every phase passed.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import shutil
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

ARCH = "qwen2-0.5b"
SUBNETS, WORKERS_PER_SUBNET = 2, 2
SLOTS, EVAL_EVERY = 8, 4
SEQ, SEED = 128, 0
# The tied embedding table is initialised with std 1, so at width 896 the
# initial logits have std ~30 and the loss starts near 170.  Trained at the
# default eta 0.05, u_k lands where bf16 gradients mean little: at that u_k
# (CPU, full width cut to 8 layers, vocab 8192) flash and XLA agree to
# 6.1e-6 relative L2 in f32 at full matmul precision, yet in bf16 XLA is
# 0.24 and flash 0.45 away from that f32 gradient, and 0.38 from each
# other.  The kernels compute the same function; bf16 cannot resolve it
# there.  A tenth of that step keeps u_k where bf16 can: at 0.005 both
# bf16 gradients are 1.1e-2 from the f32 one and 4.0e-3 from each other
# (same CPU check), and the loss still falls.
ETA = 0.005
# Flash vs XLA, one gradient step in the training dtype (bf16).  bf16
# rounds each result to 8 significant bits (2^-8 relative); flash keeps
# f32 where XLA rounds intermediates to bf16, and ~100 such roundings in
# sequence through 24 layers forward and backward add up as a random walk
# to about 10 * 2^-8 = 4e-2; the bound allows 2.5 times that.
LOSS_RTOL = 1e-2          # |loss_flash - loss_xla| / |loss_xla|
GRAD_RTOL = 1e-1          # ||g_flash - g_xla||_2 / ||g_xla||_2, all leaves
# The same step in f32 at full matmul precision on both sides: only the
# order of summation differs, so agreement is far tighter.  The all-leaves
# norm is dominated by the embedding and FFN weights, so every leaf is
# also held to a bound of its own.  A 10% error planted in one KV head's
# dk (CPU, full size at init) moves the f32 worst leaf from 2.7e-6 to
# 7.5e-2 and the f32 all-leaves error to 1.0e-2, while the bf16 all-leaves
# error only goes from 1.4e-2 to 1.8e-2, inside GRAD_RTOL.
F32_LOSS_RTOL = 1e-4
F32_GRAD_RTOL = 1e-3
F32_LEAF_RTOL = 1e-3      # worst single leaf, f32
DECODE_RTOL = 1e-2        # max |kernel - ref| / max |ref| (bf16 output)
# Mesh vs vmap.  Each worker's gradient compiles at width 1 on the mesh
# and width W under vmap, and on a v5e the two round differently (one
# active hub event from the init state: 2.7e-2 at per-worker batch 1,
# 3.0e-2 at batch 2); the differences then grow over the plan's slots.
# The loss and active-event bounds are therefore loose.  The tight check
# is mixing alone, from a state whose workers differ by ~10% (`_spread`):
# bit-identical on a v5e and on the CPU, while the same event without its
# hub roll is 8.8e-2 away.
MESH_LOSS_RTOL = 0.1      # u_k losses over the whole plan
MESH_MIX_RTOL = 1e-3      # per-leaf relative L2 error, one idle hub event
MESH_EVENT_RTOL = 1e-2    # the same, one hub event from the plan's end


def require(ok: bool, msg: str) -> None:
    if not ok:
        raise RuntimeError(msg)


def mll_config():
    from repro.core.mllsgd import MLLConfig
    return MLLConfig(tau=2, q=2, eta=ETA, hub_topology="ring",
                     mixing="two_stage")


def loop_config(seq: int, **kw):
    from repro.launch.train import TrainLoopConfig
    return TrainLoopConfig(steps=SLOTS, eval_every=EVAL_EVERY, seq_len=seq,
                           batch_per_worker=1, seed=SEED, policy="deadline",
                           **kw)


def _abstract(tree):
    return jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype),
                        tree)


# ------------------------------------------------------------------ phases
def train_phase(cfg, ckpt: str, seq: int = SEQ, log=print) -> dict:
    """(b): train through `run_training` with the flash kernels; the u_k
    losses must be finite and the last below the first."""
    from repro.launch.train import run_training
    out = run_training(cfg, mll_config(),
                       loop_config(seq, impl="flash", checkpoint_dir=ckpt),
                       num_subnets=SUBNETS,
                       workers_per_subnet=WORKERS_PER_SUBNET, log=log)
    losses = out["history"]["avg_loss"]
    log(f"u_k losses: {losses}")
    require(len(losses) >= 2 and all(np.isfinite(losses)),
            f"u_k losses not finite: {losses}")
    require(losses[-1] < losses[0], f"u_k loss did not fall: {losses}")
    return out


def local_step_hlo(out: dict, seq: int = SEQ) -> str:
    """Compiled text of the local scan that (b) ran between mixing events:
    lowered from the harness `run_training` drove, at the plan's first
    local segment and the run's state and batch shapes."""
    plan = out["plan"]
    events = np.flatnonzero(plan.op_ids != 0)    # event slots end segments
    run = int(events[0]) if events.size else int(plan.slots)
    require(run > 0, "the plan has no local slot before its first event")
    k = 1 << (run.bit_length() - 1)              # the harness's pow2 chunk
    w = plan.active.shape[1]
    tokens = jax.ShapeDtypeStruct((k, w, 1, seq), jnp.int32)
    active = jax.ShapeDtypeStruct((k, w), plan.active.dtype)
    return out["harness"].local_scan.lower(
        _abstract(out["train_state"]), {"tokens": tokens, "labels": tokens},
        active).compile().as_text()


def grad_batch(cfg, seq: int = SEQ) -> dict:
    from repro.data.pipeline import LMBatcher, make_token_stream
    stream = make_token_stream(1, 4 * seq, vocab_size=cfg.vocab_size,
                               seed=SEED + 1)
    batch = LMBatcher(stream, seq, 1).sample(np.random.default_rng(SEED))
    return {k: v[0] for k, v in batch.items()}                  # (1, seq)


def flash_vs_xla(cfg, params, batch, precision=None) -> dict:
    """Loss and gradients of one batch with the flash kernels and with
    plain XLA; ``precision`` is the default matmul precision of both."""
    from repro.train.train_step import loss_fn

    def value_and_grad(impl):
        with jax.default_matmul_precision(precision):
            fn = jax.jit(jax.value_and_grad(
                lambda p, b: loss_fn(p, b, cfg, impl=impl)[0]))
            loss, grads = fn(params, batch)
        return float(loss), jax.tree_util.tree_leaves_with_path(jax.tree.map(
            lambda g: np.asarray(g, np.float32), grads))

    lf, gf = value_and_grad("flash")
    lx, gx = value_and_grad("xla")
    leaves = [(jax.tree_util.keystr(k), a, b) for (k, a), (_, b)
              in zip(gf, gx)]
    diff2 = sum(float(np.sum((a - b) ** 2)) for _, a, b in leaves)
    ref2 = sum(float(np.sum(b ** 2)) for _, _, b in leaves)
    per_leaf = {k: float(np.linalg.norm(a - b)
                         / max(np.linalg.norm(b), 1e-30))
                for k, a, b in leaves}
    worst = max(per_leaf, key=per_leaf.get)
    return {"loss_flash": lf, "loss_xla": lx,
            "loss_rel_err": abs(lf - lx) / abs(lx),
            "grad_rel_l2_err": float(np.sqrt(diff2 / ref2)),
            "worst_leaf": worst, "worst_leaf_rel_l2_err": per_leaf[worst]}


def grad_phase(cfg, params, seq: int = SEQ, log=print) -> dict:
    """(c): loss and gradients at the same params and batch, flash vs xla,
    in bf16 and again in f32 at full matmul precision."""
    batch = grad_batch(cfg, seq)
    cfg32 = dataclasses.replace(cfg, param_dtype="float32",
                                compute_dtype="float32")
    res = {}
    for name, loss_tol, grad_tol, leaf_tol in (
            ("bf16", LOSS_RTOL, GRAD_RTOL, None),
            ("f32", F32_LOSS_RTOL, F32_GRAD_RTOL, F32_LEAF_RTOL)):
        if name == "bf16":
            err = flash_vs_xla(cfg, params, batch)
        else:
            err = flash_vs_xla(cfg32, jax.tree.map(
                lambda x: x.astype(jnp.float32), params), batch, "highest")
        res[name] = err
        log(f"flash vs xla, {name}: {json.dumps(err)}")
        require(np.isfinite(err["loss_flash"])
                and np.isfinite(err["loss_xla"]), f"{name}: non-finite loss")
        require(err["loss_rel_err"] <= loss_tol,
                f"{name}: loss differs beyond {loss_tol}: {err}")
        require(err["grad_rel_l2_err"] <= grad_tol,
                f"{name}: gradients differ beyond {grad_tol}: {err}")
        require(leaf_tol is None or err["worst_leaf_rel_l2_err"] <= leaf_tol,
                f"{name}: a gradient leaf differs beyond {leaf_tol}: {err}")
    return res


def serve_phase(cfg, ckpt: str, log=print) -> dict:
    """(d): the flash-decode engine serves u_k from the checkpoint; then
    flash-decode on the live cache against the reference."""
    from repro.kernels import ops as kops
    from repro.kernels import ref
    from repro.serve.engine import EngineConfig, Request, ServeEngine
    n_req, plen, max_new = 4, 32, 16
    ecfg = EngineConfig(max_batch=n_req, block_size=16, num_blocks=32,
                        max_len=64, seed=SEED, impl="flash")
    engine = ServeEngine.from_checkpoint(ckpt, cfg, ecfg)
    rng = np.random.default_rng(SEED)
    reqs = [Request(rid=i, prompt=rng.integers(0, cfg.vocab_size, plen)
                    .astype(np.int32), max_new=max_new)
            for i in range(n_req)]
    res = engine.run(reqs)
    gen = sorted(r["generated"] for r in res["records"])
    log(f"served {len(res['records'])} requests in {res['slots']} slots, "
        f"generated {gen}")
    require(gen == [max_new] * n_req, f"requests unfinished: {gen}")
    require(all(0 <= t < cfg.vocab_size
                for toks in res["outputs"].values() for t in toks),
            "token outside the vocabulary")
    require(engine.alloc.available == ecfg.num_blocks,
            f"{ecfg.num_blocks - engine.alloc.available} blocks not freed")

    # layer 0's live pools, read through the lanes' last block tables
    pools = jax.tree.map(lambda x: x[0], engine.state["pos0"])
    tables = jnp.asarray(engine.tables)
    lengths = jnp.asarray([plen + max_new - 1, 40, 33, 20], jnp.int32)
    q = jax.random.normal(jax.random.PRNGKey(SEED),
                          (n_req, cfg.n_heads, cfg.resolved_head_dim)
                          ).astype(pools["k_pool"].dtype)
    got = np.asarray(kops.flash_decode(q, pools["k_pool"], pools["v_pool"],
                                       tables, lengths), np.float32)
    with jax.default_matmul_precision("highest"):
        want = np.asarray(ref.flash_decode_ref(
            q.astype(jnp.float32), pools["k_pool"].astype(jnp.float32),
            pools["v_pool"].astype(jnp.float32), tables, lengths))
    err = float(np.max(np.abs(got - want)) / np.max(np.abs(want)))
    log(f"flash_decode vs ref on the live cache: max rel err {err:.3e}")
    require(err <= DECODE_RTOL, f"flash_decode differs beyond {DECODE_RTOL}")
    return {"decode_rel_err": err}


def _rel_l2(got, want) -> float:
    """Largest per-leaf ||got - want||_2 / ||want||_2 over two pytrees."""
    worst = 0.0
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
        worst = max(worst, float(np.linalg.norm(a - b)
                                 / max(np.linalg.norm(b), 1e-30)))
    return worst


def _bit_identical(got, want) -> bool:
    return all(np.array_equal(a, b) for a, b in
               zip(jax.tree.leaves(got), jax.tree.leaves(want)))


def _spread(state, w: int):
    """``state`` (on the host) with worker i's params scaled by 1 + i/10, so
    that sub-networks and hubs hold models ~10% apart."""
    s = 1.0 + 0.1 * np.arange(w, dtype=np.float32)
    return state._replace(params=jax.tree.map(
        lambda x: (np.asarray(x, np.float32)
                   * s.reshape((w,) + (1,) * (x.ndim - 1))).astype(x.dtype),
        state.params))


def mesh_phase(cfg, seq: int = SEQ, log=print) -> dict:
    """--chips 4: the plan of (b), XLA attention, on a (4, 1) mesh (one
    worker per chip) against the single-device vmap path on chip 0, each
    through the harness `run_training` drove.

    Three comparisons.  The whole plan: params bit for bit (reported) and
    the u_k losses within `MESH_LOSS_RTOL`.  Mixing alone, one hub event
    with every worker idle, from a spread state (`_spread`): params within
    `MESH_MIX_RTOL`; the vmap event without its hub roll (the subnet
    event) is reported beside it as the reading of a wrong collective.
    One hub event with every worker active from the plan's final state:
    params within `MESH_EVENT_RTOL`."""
    from repro.core import protocol
    from repro.data.pipeline import LMBatcher, make_token_stream
    from repro.launch.hlo_analysis import analyze_hlo
    from repro.launch.train import run_training
    w = SUBNETS * WORKERS_PER_SUBNET
    require(len(jax.devices()) >= w, f"need {w} devices")
    mll = mll_config()
    kw = dict(num_subnets=SUBNETS, workers_per_subnet=WORKERS_PER_SUBNET,
              log=log)
    out = run_training(cfg, mll, loop_config(seq, impl="xla", mesh=(w, 1)),
                       **kw)
    state, harness = out["train_state"], out["harness"]
    for leaf in jax.tree.leaves(state.params):
        shards = leaf.addressable_shards
        require(len({s.device for s in shards}) == w
                and all(s.data.shape[0] == 1 for s in shards),
                "worker state is not one worker per chip")
    log("mesh state: one worker per chip on devices "
        f"{sorted(d.id for d in state.params['embed']['table'].devices())}")

    # the compiled mixing events hold the real collectives
    stream = make_token_stream(w, 4 * seq, vocab_size=cfg.vocab_size,
                               seed=SEED)
    batch = LMBatcher(stream, seq, 1).sample(np.random.default_rng(SEED))
    dtype = out["plan"].active.dtype
    active, idle = jnp.ones((w,), dtype), jnp.zeros((w,), dtype)
    hub, sub = protocol.PHASE_HUB, protocol.PHASE_SUBNET
    counts = {}
    for name, ph in (("subnet", sub), ("hub", hub)):
        fn = harness.event_step[ph].build(state, batch, active)
        counts[name] = dict(analyze_hlo(fn.lower(state, batch, active)
                                        .compile().as_text())
                            .collective_counts)
    log(f"collectives per event: {counts}")
    require(counts["subnet"].get("all-reduce", 0) > 0,
            f"subnet event holds no all-reduce: {counts}")
    require(counts["hub"].get("collective-permute", 0) > 0,
            f"hub event holds no collective-permute: {counts}")

    host_state = jax.device_get(state)
    spread = _spread(host_state, w)
    mesh_losses = out["history"]["avg_loss"]
    mesh_event = jax.device_get(
        harness.event_step[hub](state, batch, active)[0].params)
    mesh_mix = jax.device_get(
        harness.event_step_idle[hub](spread, batch, idle)[0].params)
    del out, state, harness

    ref_out = run_training(cfg, mll, loop_config(seq, impl="xla"), **kw)
    ref_params = jax.device_get(ref_out["train_state"].params)
    ref_losses = ref_out["history"]["avg_loss"]
    vmap = ref_out["harness"]
    del ref_out

    def vmap_event(ph, st, act, step="event_step"):
        return jax.device_get(getattr(vmap, step)[ph](
            jax.device_put(st), batch, act)[0].params)

    res = {"params_bit_identical": _bit_identical(host_state.params,
                                                  ref_params),
           "params_max_abs_diff": max(
               float(np.max(np.abs(np.asarray(a, np.float32)
                                   - np.asarray(b, np.float32))))
               for a, b in zip(jax.tree.leaves(host_state.params),
                               jax.tree.leaves(ref_params))),
           "u_k_losses_mesh": mesh_losses, "u_k_losses_vmap": ref_losses}
    del ref_params
    want = vmap_event(hub, spread, idle, "event_step_idle")
    res["mix_bit_identical"] = _bit_identical(mesh_mix, want)
    res["mix_rel_l2_err"] = _rel_l2(mesh_mix, want)
    res["mix_without_hub_roll_rel_l2_err"] = _rel_l2(
        mesh_mix, vmap_event(sub, spread, idle, "event_step_idle"))
    want = vmap_event(hub, host_state, active)
    res["hub_event_bit_identical"] = _bit_identical(mesh_event, want)
    res["hub_event_rel_l2_err"] = _rel_l2(mesh_event, want)
    log(f"mesh vs vmap: {json.dumps(res)}")
    require(all(np.isfinite(mesh_losses)), "non-finite mesh losses")
    require(np.allclose(mesh_losses, ref_losses, rtol=MESH_LOSS_RTOL,
                        atol=0.0),
            f"u_k losses differ beyond rtol {MESH_LOSS_RTOL}")
    require(res["mix_rel_l2_err"] <= MESH_MIX_RTOL,
            f"mixing alone differs beyond {MESH_MIX_RTOL}")
    require(res["hub_event_rel_l2_err"] <= MESH_EVENT_RTOL,
            f"hub event differs beyond {MESH_EVENT_RTOL}")
    return res


# -------------------------------------------------------------------- main
def _peak_bytes() -> str:
    stats = [d.memory_stats() or {} for d in jax.devices()]
    peaks = [s.get("peak_bytes_in_use") for s in stats]
    if any(p is None for p in peaks):
        return "not reported"
    return ", ".join(f"{p / 2**30:.2f} GiB" for p in peaks)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="4: run only the mesh-vs-vmap phase on four chips")
    args = ap.parse_args(argv)

    dev = jax.devices()[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices())}
    print(f"device: {json.dumps(device)}", flush=True)
    if dev.platform != "tpu":
        print("no TPU found: this smoke runs only on the chip",
              file=sys.stderr)
        return 2

    from repro.configs.registry import get_config
    from repro.launch.compile_cache import enable_compile_cache
    print(f"compile cache: {enable_compile_cache()}", flush=True)
    cfg = get_config(ARCH)

    def timed(name, fn, *a):
        t0 = time.perf_counter()
        res = fn(*a)
        print(f"[{name}] {time.perf_counter() - t0:.1f} s, peak device "
              f"memory {_peak_bytes()}", flush=True)
        return res

    if args.chips == 4:
        require(device["count"] >= 4, f"--chips 4 found {device['count']}")
        timed("mesh", mesh_phase, cfg)
    else:
        ckpt = os.path.join(ROOT, ".chip_smoke", "ckpt")
        shutil.rmtree(ckpt, ignore_errors=True)
        try:
            out = timed("train", train_phase, cfg, ckpt)
            hlo = timed("kernel-check", local_step_hlo, out)
            require("tpu_custom_call" in hlo,
                    "compiled local step holds no tpu_custom_call")
            print("compiled local step holds tpu_custom_call", flush=True)
            params = out["avg_params"]
            del out
            timed("flash-vs-xla", grad_phase, cfg, params)
            del params
            timed("serve", serve_phase, cfg, ckpt)
        finally:
            shutil.rmtree(os.path.dirname(ckpt), ignore_errors=True)
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
