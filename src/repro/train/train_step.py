"""Loss + per-worker gradient step for the transformer substrate.

The full MLL-SGD production tick is:

  1. each worker computes grads on its own minibatch (vmap over the worker
     axis; `spmd_axis_name` threads the mesh axes through internal sharding
     constraints),
  2. the Bernoulli-gated inner-optimizer update (paper Eq. 2-3; plain SGD
     by default, any `repro.optim.optimizers` optimizer via
     ``MLLConfig(inner_opt=...)``),
  3. the scheduled averaging round through the mixing-strategy registry
     (`core.protocol`).

`mll_transformer_step` is the stateless fast path (sgd + stateless mixing);
`mll_transformer_state_step` carries a full `MLLTrainState` so stateful
inner optimizers (momentum/adamw) and stateful mixing (int8_ef error
feedback) run end-to-end on the production mesh.  `mll_harness_step` is the
PLAN-DRIVEN slot: the same tick with the gate/mixing decided host-side by a
`core.timeline` readiness policy (the production harness in
`launch.harness` compiles `TimelinePlan`s into scans over it).

No gradient collective crosses the worker axis during local steps — that is
the paper's communication saving, visible directly in the dry-run HLO.
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig
from repro.core import protocol
from repro.core.mllsgd import MLLConfig, MLLState, apply_schedule, gate_sample, gated_sgd_update
from repro.core.protocol import MLLTrainState, protocol_step
from repro.core.timeline import apply_event_operator, chunked_apply_operator
from repro.launch import spans
from repro.models import model as model_mod
from repro.models.pjit_utils import constraint

PyTree = Any


def cross_entropy(logits: jnp.ndarray, labels: jnp.ndarray,
                  mask: jnp.ndarray | None = None) -> jnp.ndarray:
    """Mean CE over (optionally masked) positions; logits may be sharded on
    vocab — the logsumexp/gather contract over vocab lowers to a psum."""
    logits = logits.astype(jnp.float32)
    lse = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    nll = lse - gold
    if mask is not None:
        return (nll * mask).sum() / jnp.maximum(mask.sum(), 1.0)
    return nll.mean()


def loss_fn(params: PyTree, batch: dict, cfg: ArchConfig, *,
            impl: str = "xla", remat: str = "none") -> tuple[jnp.ndarray, dict]:
    logits, aux = model_mod.forward_train(params, batch, cfg, impl=impl, remat=remat)
    labels = batch["labels"]
    if cfg.input_mode == "tokens+patches":
        # patches are prepended: only text positions carry labels
        p = cfg.num_patches
        logits = logits[:, p:]
    mask = batch.get("loss_mask")
    ce = cross_entropy(logits, labels, mask)
    return ce + aux, {"ce": ce, "aux": aux}


def per_worker_grads(params: PyTree, batch: dict, cfg: ArchConfig, *,
                     spmd_axis_name=None, impl: str = "xla",
                     remat: str = "none", microbatch: int = 1,
                     accum_dtype: str = "float32") -> tuple[PyTree, dict]:
    """vmap value_and_grad over the leading worker axis of params and batch.

    ``microbatch`` > 1 splits each worker's batch into that many
    gradient-accumulation chunks via lax.scan — live activations shrink by
    the same factor (the lever that fits the big FSDP archs into HBM; the
    FSDP weight gathers repeat per chunk, a memory-for-collective trade
    recorded in EXPERIMENTS.md §Perf)."""
    vg = jax.value_and_grad(partial(loss_fn, cfg=cfg, impl=impl, remat=remat),
                            has_aux=True)

    if microbatch > 1:
        def one_worker(wparams, wbatch):
            b = wbatch["labels"].shape[0]
            if b % microbatch:
                raise ValueError(f"batch {b} not divisible by microbatch "
                                 f"{microbatch}")

            def resh(name, x):
                # "positions" carries a leading streams dim: batch is axis 1
                if name == "positions":
                    y = x.reshape(x.shape[:1] + (microbatch, b // microbatch)
                                  + x.shape[2:])
                    return jnp.moveaxis(y, 1, 0)
                return x.reshape((microbatch, b // microbatch) + x.shape[1:])

            chunks = {k: resh(k, v) for k, v in wbatch.items()}

            def body(acc, chunk):
                (l, m), g = vg(wparams, chunk)
                acc_g, acc_l, acc_ce, acc_aux = acc
                acc_g = jax.tree.map(lambda a, x: a + x.astype(a.dtype),
                                     acc_g, g)
                return (acc_g, acc_l + l, acc_ce + m["ce"],
                        acc_aux + m["aux"]), None

            zero_g = jax.tree.map(
                lambda p: jnp.zeros(p.shape, jnp.dtype(accum_dtype)), wparams)
            zero = (zero_g, jnp.zeros((), jnp.float32),
                    jnp.zeros((), jnp.float32), jnp.zeros((), jnp.float32))
            (g, l, ce, aux), _ = jax.lax.scan(body, zero, chunks)
            inv = 1.0 / microbatch
            g = jax.tree.map(lambda x: x * inv, g)
            return (l * inv, {"ce": ce * inv, "aux": aux * inv}), g
    else:
        one_worker = vg

    vmapped = jax.vmap(one_worker, spmd_axis_name=spmd_axis_name)
    (loss, metrics), grads = vmapped(params, batch)
    return grads, {"loss": loss, **metrics}


def mll_transformer_step(stacked_params: PyTree, batch: dict,
                         step: jnp.ndarray, cfg: ArchConfig,
                         mll: MLLConfig, st: MLLState, *,
                         spmd_axis_name=None, impl: str = "xla",
                         remat: str = "none", microbatch: int = 1,
                         static_phase: int | None = None) -> tuple[PyTree, dict]:
    """One production MLL-SGD tick over the whole worker fleet (stateless
    fast path: plain gated SGD + the registered mixing strategy run with
    fresh per-round state)."""
    grads, metrics = per_worker_grads(stacked_params, batch, cfg,
                                      spmd_axis_name=spmd_axis_name,
                                      impl=impl, remat=remat,
                                      microbatch=microbatch,
                                      accum_dtype=mll.accum_dtype)
    theta = gate_sample(mll.seed, step, st.rates)
    stacked = gated_sgd_update(stacked_params, grads, theta, mll.eta)
    stacked = apply_schedule(stacked, step, mll, st, static_phase=static_phase)
    return stacked, metrics


def mll_transformer_state_step(train_state: MLLTrainState, batch: dict,
                               cfg: ArchConfig, mll: MLLConfig,
                               st: MLLState, *, spmd_axis_name=None,
                               impl: str = "xla", remat: str = "none",
                               microbatch: int = 1,
                               static_phase: int | None = None,
                               ) -> tuple[MLLTrainState, dict]:
    """One production protocol tick carrying full `MLLTrainState`: the
    configured inner optimizer's per-worker state and the mixing strategy's
    state (e.g. int8_ef residuals) thread through the step.  The tick index
    lives in ``train_state.step``."""
    grads, metrics = per_worker_grads(train_state.params, batch, cfg,
                                      spmd_axis_name=spmd_axis_name,
                                      impl=impl, remat=remat,
                                      microbatch=microbatch,
                                      accum_dtype=mll.accum_dtype)
    new_state = protocol_step(train_state, grads, mll, st,
                              static_phase=static_phase)
    return new_state, metrics


def event_form(mll: MLLConfig, opt_state: PyTree, *, phase: int,
               op: jnp.ndarray | None = None,
               spmd: protocol.SpmdAxis | None = None,
               overlap: str = "none") -> str:
    """How `mll_harness_step` writes a slot's gated update and mixing event.

    ``"rows"``: worker by worker (`protocol.gated_update_rows` into the
    strategy's `mix_rows`), so XLA fuses the update and the mixing into
    one pass over each leaf that reads the params and gradients once and
    writes the mixed params once.  Taken for a subnet or hub event on one
    device (no ``spmd`` axis) with no composed ``op`` and no ``chunked``
    overlap, when the strategy has a row form (`two_stage`) and the inner
    optimizer's state holds no arrays (``sgd``).

    ``"composed"``: the inner update over the stacked tree, then the
    strategy's event: every other slot (shard_map, dense operators,
    ``chunked``, the other strategies, stateful optimizers).  Both forms
    give the same bits."""
    if (phase != protocol.PHASE_LOCAL and op is None and spmd is None
            and overlap == "none"
            and not jax.tree.leaves(opt_state["inner"])
            and protocol.resolve_mixing(mll).has_rows()):
        return "rows"
    return "composed"


def mll_harness_step(train_state: MLLTrainState, batch: dict,
                     active: jnp.ndarray, cfg: ArchConfig, mll: MLLConfig,
                     st: MLLState, *, gate_mode: str = "bernoulli",
                     phase: int = protocol.PHASE_LOCAL,
                     op: jnp.ndarray | None = None,
                     compute_grads: bool = True,
                     spmd_axis_name=None, impl: str = "xla",
                     remat: str = "none", microbatch: int = 1,
                     spmd: protocol.SpmdAxis | None = None,
                     overlap: str = "none", overlap_chunks: int = 4,
                     ) -> tuple[MLLTrainState, dict]:
    """One PLAN-DRIVEN production slot: the tick of `mll_transformer_state_step`
    with the schedule's ``lax.switch`` replaced by a statically known event.

    A `TimelinePlan` (readiness policy) decides host-side what each slot
    does; this step executes it:

      * ``active`` is the plan's per-worker progress mask for the slot.
        Under ``gate_mode="bernoulli"`` it multiplies the counter-based
        Bernoulli(p_i) draw of Eq. (3) — with an all-ones mask the gate is
        bit-for-bit `mll_transformer_state_step`'s; under ``"forced"`` the
        mask IS the gate (progress was already drawn host-side by the
        policy, e.g. barrier NegBin trials or the measured-rate staircase).
      * ``phase`` pins the mixing event at trace time (local slots skip the
        identity contraction entirely); policies that mix a strict subset
        of workers pass a composed dense (W, W) operator as ``op`` instead.

    The local-only specialisation (``phase=PHASE_LOCAL``, ``op=None``) is
    the scan body of the harness's event-sparse local segments.

    Under shard_map (``spmd`` set: the mesh axis sharding the worker dim)
    the step sees only its shard's ``(W/size, ...)`` slice of state, batch
    and ``active``; mixing lowers to the strategy's collective lowering
    (psum / ppermute / all_gather) and the Bernoulli gate is drawn at FULL
    width then sliced — the counter-based draw is shape-dependent, so this
    keeps gates bit-identical to the vmap path on every shard layout.

    The step's parts run under named scopes (`launch.spans`): ``mll.grads``
    (forward, loss, backward), ``mll.update`` (gate draw and inner update)
    and ``mll.mix.subnet`` / ``mll.mix.hub`` / ``mll.mix.dense`` (the
    mixing event), so the ops of the compiled program carry them in their
    ``op_name`` metadata.  Scopes change metadata alone, not the program.

    An event slot on one device runs in the form `event_form` picks: the
    ``"rows"`` form updates and mixes each leaf worker row by worker row,
    so the update (under ``mll.update``) and the mixing (under
    ``mll.mix.*``) compile to one pass over the leaf; the ``"composed"``
    form updates the stacked tree, then mixes it.  The state is the same
    bit for bit either way.

    ``compute_grads=False`` is the ALL-IDLE event slot (forced plans: the
    straggler tail of a barrier round ends in mixing with every worker's
    gate at zero): the backward pass and the θ=0 inner update — a state
    no-op by construction — are skipped; only the per-worker loss (the
    metrics contract) and the mixing event run.

    ``overlap="chunked"`` replaces the mixing contraction (only — the
    inner-optimizer update stays per leaf, stateful optimizers included)
    with `timeline.chunked_apply_operator`: the dense (W, W) operator over
    the packed buffer one lane chunk at a time, so chunk i's exchange
    overlaps chunk i+1's compute.  Structured strategies execute their
    mathematically-equal dense operator (st.v_op / st.z_op) — together
    with the packed-vs-per-leaf einsum this is the documented
    reduction-order change: rtol-equivalent to ``overlap="none"``, not
    bitwise.  Vmap path only (`TrainHarness` refuses chunked + mesh).
    """
    if gate_mode not in ("bernoulli", "forced"):
        raise ValueError(f"unknown gate_mode {gate_mode!r}")
    if overlap not in ("none", "chunked"):
        raise ValueError(f"unknown overlap {overlap!r}; "
                         "expected none|chunked")
    step = train_state.step.astype(jnp.int32) + 1
    rows = event_form(mll, train_state.opt_state, phase=phase, op=op,
                      spmd=spmd, overlap=overlap) == "rows"
    if compute_grads:
        with jax.named_scope(spans.GRADS):
            grads, metrics = per_worker_grads(train_state.params, batch, cfg,
                                              spmd_axis_name=spmd_axis_name,
                                              impl=impl, remat=remat,
                                              microbatch=microbatch,
                                              accum_dtype=mll.accum_dtype)
        with jax.named_scope(spans.UPDATE):
            active = active.astype(st.rates.dtype)
            if gate_mode == "bernoulli":
                theta = gate_sample(mll.seed, step, st.rates)
                if spmd is not None and spmd.size > 1:
                    theta = jax.lax.dynamic_slice_in_dim(
                        theta, spmd.offset(), spmd.per_shard, 0)
                theta = theta * active
            else:
                theta = active
            optimizer = protocol.resolve_inner_optimizer(mll)
            update = (protocol.gated_update_rows if rows
                      else protocol.gated_inner_update)
            params, opt_state = update(optimizer, train_state.params,
                                       train_state.opt_state, grads, theta)
    else:
        with jax.named_scope(spans.GRADS):      # the forward alone
            loss, m = jax.vmap(partial(loss_fn, cfg=cfg, impl=impl,
                                       remat=remat))(train_state.params,
                                                     batch)
        metrics = {"loss": loss, **m}
        params, opt_state = train_state.params, train_state.opt_state
        if rows:
            params = [jax.tree.map(lambda x: x[i], params)
                      for i in range(st.rates.shape[0])]
    mix_state = train_state.mix_state
    sharded = spmd is not None and spmd.size > 1
    chunked = overlap == "chunked"
    if op is not None:
        with jax.named_scope(spans.MIX_DENSE):
            if chunked:
                params = chunked_apply_operator(params, op, overlap_chunks)
            else:
                params = apply_event_operator(params, op, spmd=spmd)
    elif phase != protocol.PHASE_LOCAL:
        subnet = phase == protocol.PHASE_SUBNET
        with jax.named_scope(spans.MIX_SUBNET if subnet else spans.MIX_HUB):
            if chunked:
                op_mat = st.v_op if subnet else st.z_op
                params = chunked_apply_operator(params, op_mat,
                                                overlap_chunks)
            elif rows:
                params = protocol.resolve_mixing(mll).mix_rows(
                    params, st, hub=not subnet)
            else:
                # mix_state is always populated up front
                # (init_train_state) — a structure change mid-run would
                # retrace every compiled segment
                strategy = protocol.resolve_mixing(mll)
                if subnet and sharded:
                    params, mix_state = strategy.subnet_spmd_with_state(
                        params, st, mix_state, spmd)
                elif subnet:
                    params, mix_state = strategy.subnet_with_state(
                        params, st, mix_state)
                elif sharded:
                    params, mix_state = strategy.hub_spmd_with_state(
                        params, st, mix_state, spmd)
                else:
                    params, mix_state = strategy.hub_with_state(
                        params, st, mix_state)
    return MLLTrainState(params, opt_state, mix_state, step), metrics
