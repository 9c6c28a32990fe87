import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
os.environ["JAX_PLATFORMS"] = "cpu"
# ^ MUST precede every other import (jax locks device count on first init);
# the 512 placeholder devices are host devices, so an attached chip is
# never claimed.
"""Multi-pod dry-run: lower + compile every (architecture x input-shape x
mesh) combination with ShapeDtypeStruct stand-ins (no device allocation).

For each combo this produces:
  * proof-of-coherence : ``.lower().compile()`` must succeed (sharding
    mismatches, unsupported collectives, compile-time OOM are bugs),
  * ``compiled.memory_analysis()``  — per-device footprint,
  * trip-count-corrected HLO costs  — FLOPs / HBM bytes / collective bytes
    (see hlo_analysis.py; raw ``cost_analysis()`` is recorded too but
    under-counts lax.scan bodies),
  * roofline terms for the §Roofline table.

Training combos additionally lower each MLL-SGD phase separately
(``--phase local|subnet|hub``) so the averaging collectives can be amortized
exactly over the (tau, q) schedule.

CLI:
  python -m repro.launch.dryrun --arch qwen3-1.7b --shape train_4k \\
      [--multipod] [--phase hub] [--mixing two_stage] [--out results.json]
  python -m repro.launch.dryrun --all [--multipod]
"""
import argparse
import dataclasses
import json
import sys
import time
import traceback
from functools import partial
from typing import Any

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs.base import ArchConfig
from repro.configs.registry import ARCH_IDS, get_config
from repro.core.mllsgd import MLLConfig, build_network, build_state
from repro.core.protocol import available_mixing
from repro.launch import hlo_analysis as hlo
from repro.launch.input_specs import (SHAPES, ShapeSpec, adapt_config,
                                      decode_input_specs, prefill_input_specs,
                                      train_input_specs)
from repro.launch.mesh import make_production_mesh
from repro.launch.sharding import ShardingPlan, make_plan
from repro.models import model as model_mod
from repro.models.pjit_utils import logical_sharding
from repro.serve.serve_step import serve_step
from repro.train.train_step import loss_fn, mll_transformer_step

PyTree = Any
SDS = jax.ShapeDtypeStruct
PHASES = {"local": 0, "subnet": 1, "hub": 2, "dynamic": None}


# ------------------------------------------------------------ spec builders
def params_shape(cfg: ArchConfig) -> PyTree:
    return jax.eval_shape(lambda: model_mod.init_model(jax.random.PRNGKey(0), cfg))


def stack_worker_axis(shapes: PyTree, w: int) -> PyTree:
    return jax.tree.map(lambda s: SDS((w,) + s.shape, s.dtype), shapes)


def _batch_axis(plan: ShardingPlan, size: int):
    """Mesh axes for a global batch dim of the given size (serving path)."""
    axes = [a for a in ("pod", "data") if a in plan.axis_sizes]
    prod = 1
    keep = []
    for a in axes:
        if size % (prod * plan.axis_sizes[a]) == 0:
            keep.append(a)
            prod *= plan.axis_sizes[a]
    return tuple(keep) or None


def train_batch_specs(batch: dict, plan: ShardingPlan) -> dict:
    """Sharding for per-worker training batches (leading worker axis)."""
    waxes = plan.worker_axes or None
    inner_batch = ("data" if plan.granularity == "worker_per_pod" else None)

    def one(name, leaf):
        rest = [None] * (leaf.ndim - 1)
        # dim 1 is the per-worker batch dim except for "positions" (streams)
        bdim = 2 if name == "positions" else 1
        if inner_batch and leaf.shape[bdim] % plan.data_size == 0:
            rest[bdim - 1] = inner_batch
        return P(waxes, *rest)

    return {k: NamedSharding(plan.mesh, one(k, v)) for k, v in batch.items()}


def serve_batch_specs(batch: dict, plan: ShardingPlan) -> dict:
    def one(name, leaf):
        bax = _batch_axis(plan, leaf.shape[0])
        bdim = 1 if name == "positions" else 0
        spec = [None] * leaf.ndim
        spec[bdim] = bax if leaf.shape[bdim] > 1 else None
        return P(*spec)

    return {k: NamedSharding(plan.mesh, one(k, v)) for k, v in batch.items()}


def decode_state_specs(state_shapes: PyTree, plan: ShardingPlan) -> PyTree:
    """KV-cache / recurrent-state sharding: batch -> data(/pod), then the
    head or channel dim -> model when divisible (kv-head first, head_dim as
    fallback — the contraction over a sharded head_dim lowers to a psum)."""
    ms = plan.model_size

    def div(n):
        return n % ms == 0

    def one(path, leaf):
        name = str(getattr(path[-1], "key", path[-1]))
        shp = leaf.shape                       # (L, B, ...) stacked blocks
        bax = _batch_axis(plan, shp[1]) if shp[1] > 1 else None
        spec = [None, bax] + [None] * (leaf.ndim - 2)
        if name in ("k", "v") and leaf.ndim == 5:      # (L,B,S,hkv,hd)
            if div(shp[3]):
                spec[3] = "model"
            elif div(shp[4]):
                spec[4] = "model"
        elif name == "h" and leaf.ndim == 4:           # mamba (L,B,di,n)
            if div(shp[2]):
                spec[2] = "model"
        elif name == "conv" and leaf.ndim == 4:        # (L,B,K-1,di)
            if div(shp[3]):
                spec[3] = "model"
        elif name == "c" and leaf.ndim == 5:           # mlstm (L,B,h,hd,hd)
            if div(shp[2]):
                spec[2] = "model"
            elif div(shp[3]):
                spec[3] = "model"
        elif name == "n" and leaf.ndim == 4:           # mlstm (L,B,h,hd)
            if div(shp[2]):
                spec[2] = "model"
            elif div(shp[3]):
                spec[3] = "model"
        elif leaf.ndim == 3 and name in ("h", "c", "n", "m"):   # slstm (L,B,dp)
            if div(shp[2]):
                spec[2] = "model"
        return NamedSharding(plan.mesh, P(*spec))

    return jax.tree_util.tree_map_with_path(one, state_shapes)


# -------------------------------------------------------------- lower+compile
def _summarize(compiled, mesh, *, multi_pod: bool) -> dict:
    chips = mesh.devices.size
    pod_stride = 256 if multi_pod else 0
    costs = hlo.analyze_hlo(compiled.as_text(), pod_stride=pod_stride)
    rl = hlo.roofline_terms(costs, chips)
    mem = compiled.memory_analysis()
    mem_d = {}
    for attr in ("argument_size_in_bytes", "output_size_in_bytes",
                 "temp_size_in_bytes", "alias_size_in_bytes",
                 "generated_code_size_in_bytes"):
        v = getattr(mem, attr, None)
        if v is not None:
            mem_d[attr] = int(v)
    raw_ca = {}
    try:
        ca = compiled.cost_analysis()
        raw_ca = {k: float(v) for k, v in ca.items()
                  if isinstance(v, (int, float)) and k in
                  ("flops", "bytes accessed", "utilization operand 0 {}")}
    except Exception:
        pass
    return {
        "chips": chips,
        "memory_analysis": mem_d,
        "hlo_costs": costs.as_dict(),
        "roofline": rl.as_dict(),
        "raw_cost_analysis": raw_ca,
    }


def build_train_step(cfg: ArchConfig, plan: ShardingPlan, *,
                     tau: int, q: int, mixing: str, mix_dtype: str | None,
                     phase: int | None, remat: str, impl: str,
                     microbatch: int = 1, accum_dtype: str = "float32"):
    mll = MLLConfig(tau=tau, q=q, granularity=plan.granularity,
                    hub_topology="complete", mixing=mixing,
                    mix_dtype=mix_dtype, accum_dtype=accum_dtype)
    network = build_network(mll, plan.n_pods, plan.data_size,
                            plan.model_size)
    st = build_state(mll, network)
    spmd = plan.worker_axes if plan.worker_axes else None

    def step_fn(stacked_params, batch, step):
        return mll_transformer_step(
            stacked_params, batch, step, cfg, mll, st,
            spmd_axis_name=spmd, impl=impl, remat=remat,
            microbatch=microbatch, static_phase=phase)

    return step_fn


def prefill_fn_for(cfg: ArchConfig, *, impl: str, remat: str):
    def prefill(params, batch):
        logits, _ = model_mod.forward_train(params, batch, cfg,
                                            impl=impl, remat=remat)
        return logits[:, -1]        # next-token logits after the prompt
    return prefill


def run_one(arch_id: str, shape_name: str, *, multi_pod: bool = False,
            phase: str = "dynamic", mixing: str = "dense",
            mix_dtype: str | None = None, remat: str = "full",
            tau: int = 8, q: int = 4, impl: str = "auto",
            granularity: str | None = None,
            moe_groups: int | None = None,
            rules_override: dict | None = None,
            microbatch: int = 1,
            accum_dtype: str = "float32",
            decode_coshard: bool = True,
            save_hlo: str | None = None) -> dict:
    t0 = time.time()
    shape = SHAPES[shape_name]
    cfg = adapt_config(get_config(arch_id), shape)
    if moe_groups is not None:
        cfg = dataclasses.replace(cfg, moe_groups=moe_groups)
    if not decode_coshard:
        cfg = dataclasses.replace(cfg, decode_coshard=False)
    mesh = make_production_mesh(multi_pod=multi_pod)
    plan = make_plan(mesh, cfg, granularity=granularity)
    meta = {
        "arch": arch_id, "shape": shape_name,
        "mesh": "pod2x16x16" if multi_pod else "16x16",
        "kind": shape.kind, "phase": phase, "mixing": mixing,
        "mix_dtype": mix_dtype, "remat": remat, "tau": tau, "q": q,
        "granularity": plan.granularity, "num_workers": plan.num_workers,
        "params_total": cfg.param_count(),
        "params_active": cfg.active_param_count(),
    }
    serving = shape.kind != "train"
    rules = plan.logical_rules(serving=serving)
    if rules_override:
        rules.update(rules_override)
        meta["rules_override"] = {k: str(v) for k, v in rules_override.items()}
    if moe_groups is not None:
        meta["moe_groups"] = moe_groups
    meta["microbatch"] = microbatch

    with mesh, logical_sharding(mesh, rules):
        if shape.kind == "train":
            w = plan.num_workers
            pshapes = stack_worker_axis(params_shape(cfg), w)
            pspecs = plan.named(plan.param_specs(pshapes, with_worker_axis=True))
            batch = train_input_specs(cfg, shape, w)
            bspecs = train_batch_specs(batch, plan)
            step_fn = build_train_step(
                cfg, plan, tau=tau, q=q, mixing=mixing, mix_dtype=mix_dtype,
                phase=PHASES[phase], remat=remat, impl=impl,
                microbatch=microbatch, accum_dtype=accum_dtype)
            jitted = jax.jit(step_fn,
                             in_shardings=(pspecs, bspecs, NamedSharding(mesh, P())),
                             out_shardings=(pspecs, None))
            lowered = jitted.lower(pshapes, batch, SDS((), jnp.int32))
            tokens = shape.global_batch * shape.seq_len
        elif shape.kind == "prefill":
            pshapes = params_shape(cfg)
            pspecs = plan.named(plan.param_specs(pshapes, with_worker_axis=False))
            batch = prefill_input_specs(cfg, shape)
            bspecs = serve_batch_specs(batch, plan)
            fn = prefill_fn_for(cfg, impl=impl, remat=remat)
            jitted = jax.jit(fn, in_shardings=(pspecs, bspecs))
            lowered = jitted.lower(pshapes, batch)
            tokens = shape.global_batch * shape.seq_len
        else:  # decode
            pshapes = params_shape(cfg)
            pspecs = plan.named(plan.param_specs(pshapes, with_worker_axis=False))
            sshapes = jax.eval_shape(
                lambda: model_mod.init_decode_state(cfg, shape.global_batch,
                                                    shape.seq_len))
            sspecs = decode_state_specs(sshapes, plan)
            spec_d = decode_input_specs(cfg, shape)
            bspecs = serve_batch_specs(spec_d["batch"], plan)

            def fn(params, state, batch, cur):
                return serve_step(params, state, batch, cur, cfg)

            jitted = jax.jit(fn, in_shardings=(pspecs, sspecs, bspecs,
                                               NamedSharding(mesh, P())))
            lowered = jitted.lower(pshapes, sshapes, spec_d["batch"],
                                   spec_d["cur"])
            tokens = shape.global_batch            # one token per sequence
        t_lower = time.time()
        compiled = lowered.compile()
        t_compile = time.time()

    out = dict(meta)
    out.update(_summarize(compiled, mesh, multi_pod=multi_pod))
    # decode steps run in bf16/f32 mixes dominated by memory: MODEL_FLOPS for
    # decode is 2*N_active per token (fwd only); train is 6*N_active.
    flops_per_tok = (6.0 if shape.kind == "train" else 2.0) * cfg.active_param_count()
    out["model_flops"] = flops_per_tok * tokens
    global_flops = out["roofline"]["flops"]       # per-chip HLO flops x chips
    out["useful_fraction"] = (out["model_flops"] / global_flops
                              if global_flops else 0.0)
    out["lower_s"] = round(t_lower - t0, 2)
    out["compile_s"] = round(t_compile - t_lower, 2)
    if save_hlo:
        with open(save_hlo, "w") as f:
            f.write(compiled.as_text())
    return out


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=ARCH_IDS)
    ap.add_argument("--shape", choices=tuple(SHAPES))
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--multipod", action="store_true")
    ap.add_argument("--phase", default="dynamic", choices=tuple(PHASES))
    ap.add_argument("--mixing", default="dense", choices=available_mixing())
    ap.add_argument("--mix-dtype", default=None)
    ap.add_argument("--remat", default="full", choices=("none", "full", "dots"))
    ap.add_argument("--impl", default="auto")
    ap.add_argument("--tau", type=int, default=8)
    ap.add_argument("--q", type=int, default=4)
    ap.add_argument("--granularity", default=None,
                    choices=(None, "worker_per_data", "worker_per_pod",
                             "worker_per_chip"))
    ap.add_argument("--moe-groups", type=int, default=None)
    ap.add_argument("--out", default=None)
    ap.add_argument("--save-hlo", default=None)
    args = ap.parse_args(argv)

    combos = ([(a, s) for a in ARCH_IDS for s in SHAPES]
              if args.all else [(args.arch, args.shape)])
    results = []
    for arch, shp in combos:
        try:
            r = run_one(arch, shp, multi_pod=args.multipod, phase=args.phase,
                        mixing=args.mixing, mix_dtype=args.mix_dtype,
                        remat=args.remat, tau=args.tau, q=args.q,
                        impl=args.impl, granularity=args.granularity,
                        moe_groups=args.moe_groups, save_hlo=args.save_hlo)
            rl = r["roofline"]
            print(f"OK  {arch:24s} {shp:12s} {r['mesh']:10s} phase={args.phase:8s}"
                  f" compute={rl['compute_s']:.3e}s memory={rl['memory_s']:.3e}s"
                  f" coll={rl['collective_s']:.3e}s dom={rl['dominant']}"
                  f" compile={r['compile_s']}s", flush=True)
            results.append(r)
        except Exception as e:
            traceback.print_exc()
            print(f"FAIL {arch} {shp}: {e}", flush=True)
            results.append({"arch": arch, "shape": shp, "error": str(e)})
            if not args.all:
                sys.exit(1)
    if args.out:
        with open(args.out, "w") as f:
            json.dump(results, f, indent=1)
    return results


if __name__ == "__main__":
    main()
