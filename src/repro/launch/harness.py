"""Plan-driven production trainer: the launch path through the timeline engine.

`core.timeline` gave the SIMULATOR readiness policies, wall-clock slot
accounting and event-sparse execution; this module gives the PRODUCTION
trainer the same contract.  A `TimelinePlan` compiled by **any** registered
readiness policy (``barrier`` / ``deadline`` / ``gossip`` / user-registered)
is the single execution schedule:

  * **local segments** (slots between mixing events) run only the gated
    per-worker grads + inner-optimizer update — a jitted `lax.scan` over
    stacked per-slot batches of `mll_harness_step`, decomposed into
    power-of-two chunks so recompiles stay O(log max_run) regardless of how
    the policy scatters its events,
  * **mixing events** apply the registered strategy with the phase pinned
    at trace time (dense / two_stage / ppermute / int8 / ... through the
    protocol registry), or a composed per-event dense (W, W) operator for
    partial-participation policies (gossip),
  * **all-idle runs** of forced plans (the straggler tail of barrier
    rounds, measured-rate staircases) fast-forward: the data cursor still
    consumes each slot's draw, but no gradients are computed.

With ``policy="deadline"`` and the Bernoulli gate this reproduces the
legacy lock-step ``run_training`` tick loop bit for bit (regression-tested
in tests/test_harness.py) — the launcher is now a thin wrapper over this
harness, and "simulator" vs "production" are two consumers of one engine.

Beyond the executor, the harness owns the production run lifecycle:

  * ``rate_model="measured"`` — a warmup timing pass profiles each worker's
    seconds-per-step (`measure_worker_rates`), the derived
    `timeline.RateCalibration` replaces hand-fed p_i and is serialized next
    to the plan/checkpoints,
  * **full-protocol resumable checkpoints** — the entire `MLLTrainState`
    plus the timeline cursor and the `LMBatcher` data cursor go through
    `train.checkpoint.save_state`; a killed run restored with
    ``resume=True`` replays the uninterrupted trajectory bit for bit,
  * **event-trace export** in the simulator's schema
    (`timeline.plan_trace`), consumable by `benchmarks/` and the nightly
    gate.
"""
from __future__ import annotations

import collections
import dataclasses
import time
from functools import partial
from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs.base import ArchConfig
from repro.core import protocol, timeline
from repro.core.mllsgd import MLLConfig, MLLState
from repro.core.simulator import weighted_average
from repro.data.pipeline import LMBatcher, rng_state
from repro.launch import spans
from repro.train import checkpoint
from repro.train.train_step import event_form, loss_fn, mll_harness_step

PyTree = Any

CALIBRATION_FILE = "calibration.json"


# ------------------------------------------------------- rate calibration
def measure_worker_rates(cfg: ArchConfig, params_stacked: PyTree,
                         batch: dict, *, reps: int = 3,
                         skew: tuple[float, ...] | None = None,
                         impl: str = "xla") -> timeline.RateCalibration:
    """Warmup timing pass: profile each worker's seconds per local gradient
    step and derive relative rates (fastest worker = 1.0).

    Workers are timed one at a time on their own slice of the stacked
    params/batch — one compile (shapes are identical across workers), then
    ``reps`` timed calls each, keeping the median.  ``skew`` multiplies the
    measured times per worker (testing hook: on a single host all workers
    share silicon, so heterogeneity must be injected to be visible).
    """
    w = jax.tree.leaves(params_stacked)[0].shape[0]
    if skew is not None and len(skew) != w:
        raise ValueError(f"need {w} skew factors, got {len(skew)}")
    grad_one = jax.jit(jax.grad(lambda p, b: loss_fn(p, b, cfg,
                                                     impl=impl)[0]))

    def worker_slice(tree, i):
        return jax.tree.map(lambda x: x[i], tree)

    times = []
    for i in range(w):
        p_i, b_i = worker_slice(params_stacked, i), worker_slice(batch, i)
        jax.block_until_ready(grad_one(p_i, b_i))          # compile + warm
        samples = []
        for _ in range(reps):
            t0 = time.perf_counter()
            jax.block_until_ready(grad_one(p_i, b_i))
            samples.append(time.perf_counter() - t0)
        times.append(float(np.median(samples)))
    if skew is not None:
        times = [t * float(s) for t, s in zip(times, skew)]
    return timeline.RateCalibration(step_times=tuple(times))


def resolve_measured_network(network, calibration: timeline.RateCalibration):
    """The network re-rated with measured per-worker rates."""
    return timeline.network_with_rates(network, calibration.rates)


# ----------------------------------------------------------------- harness
def _stack_batches(batches: list[dict]) -> dict:
    return {k: jnp.stack([b[k] for b in batches]) for k in batches[0]}


def _draw(batcher: LMBatcher, rng: np.random.Generator) -> dict:
    with spans.span(spans.DRAW_BATCH):
        return batcher.sample(rng)


def _worker_spec(x, w: int, axis: int = 0) -> P:
    """PartitionSpec for one leaf: shard the worker dim (size ``w`` at
    ``axis``) over the mesh's `workers` axis, replicate everything else.
    Per-slot stacked batches carry the worker dim at axis 1 (the scan axis
    leads), so the position is an argument, not sniffed from the shape."""
    shape = jnp.shape(x)
    if len(shape) > axis and shape[axis] == w:
        return P(*([None] * axis + ["workers"]))
    return P()


def shard_train_state(state: PyTree, mesh, num_workers: int) -> PyTree:
    """device_put a train state onto the mesh: worker-leading leaves shard
    on the `workers` axis, scalars/full-width tables replicate."""
    def put(x):
        return jax.device_put(
            x, NamedSharding(mesh, _worker_spec(x, num_workers)))
    return jax.tree.map(put, state)


class TrainHarness:
    """Compiled plan executor for the production (transformer) trainer.

    Three jitted entry points, mirroring `timeline.EventExecutor` on the
    `MLLTrainState` carry:

      * ``local_scan(state, batches, active)`` — lax.scan of the local-only
        slot body over stacked (k, W, B, S) batches; returns the state and
        the LAST slot's metrics,
      * ``event_step[phase](state, batch, active)`` — one slot ending in a
        subnet/hub round, phase pinned at trace time,
      * ``dense_step(state, batch, active, op)`` — one slot ending in a
        composed dense (W, W) operator event (partial-participation
        policies).

    ``gate_mode`` is fixed per plan: ``"bernoulli"`` multiplies the plan's
    active mask into the counter-based gate draw (deadline = the legacy
    lock-step trainer bit for bit), ``"forced"`` uses the mask as the gate.

    Every entry point DONATES its incoming state: the new state is written
    over the old one's buffers, so a fleet of W full-width replicas needs
    one copy of its state on the device, not two.  Callers rebind
    (``state, m = h.local_scan(state, ...)``) and never reuse a state they
    passed in.

    With ``mesh=`` (a mesh carrying a `workers` axis, e.g.
    ``make_mesh((4, 2), ("workers", "data"))``) every entry point compiles
    to `shard_map` over that mesh instead of single-device vmap: each
    worker shard runs its local slots on its own device slice and mixing
    events lower to the strategy's REAL collectives (intra-subnet psum,
    circulant ppermute rolls, all_gather + local einsum for dense) — the
    paper's communication structure on actual device boundaries, with
    trajectories bit-identical to the vmap path (tests/test_spmd_subproc).
    The `data` axis replicates the protocol computation (sharding the
    batch would change f32 reduction order); it exists so the same mesh
    shape can carry batch-parallel eval/serving work.

    Bit-identity contract: the full state trajectory (params, opt state,
    mix state) and every u_k / avg-loss eval match the vmap path bit for
    bit.  The one exception is the per-worker f32 *loss diagnostic*: the
    scalar ``nll.mean()`` reduction vectorizes differently at vmap width
    W than at shard width W/num_shards, so it can wobble in the final
    ulp (gradients of a mean are order-independent, which is why the
    state itself never drifts).  Tests pin it with allclose(rtol=1e-5).
    The contract is tested on the CPU at per-worker batch 2.  At batch 1
    XLA:CPU rounds the shard-width and vmap-width gradient programs
    differently, so single bf16 roundings differ and trajectories drift
    apart over slots (`chip_smoke.py --chips 4` measures both).
    """

    def __init__(self, cfg: ArchConfig, mll: MLLConfig, st: MLLState, *,
                 gate_mode: str, impl: str = "xla", mesh=None,
                 overlap: str = "none", overlap_chunks: int = 4):
        if gate_mode not in ("bernoulli", "forced"):
            raise ValueError(f"unknown gate_mode {gate_mode!r}")
        if impl not in ("xla", "flash", "pallas", "chunked", "auto"):
            # an unrecognized impl would silently train through the XLA
            # attention path — the exact fallback this harness rules out
            raise ValueError(f"unknown impl {impl!r}")
        if overlap not in ("none", "chunked"):
            raise ValueError(f"unknown overlap {overlap!r}; "
                             "expected none|chunked")
        if overlap == "chunked":
            if mesh is not None:
                raise ValueError(
                    "overlap='chunked' chunks the packed buffer on ONE "
                    "device; under a mesh the collective lowerings already "
                    "overlap by shard — use overlap='none' with --mesh")
            if (mll.mixing not in ("dense", "two_stage", "ppermute")
                    or mll.mix_dtype is not None):
                raise ValueError(
                    "overlap='chunked' mixes via a dense (W, W) operator "
                    "over the packed f32 buffer; it requires mix_dtype="
                    "None and mixing in ('dense', 'two_stage', 'ppermute')")
            if overlap_chunks < 1:
                raise ValueError(f"overlap_chunks must be >= 1, "
                                 f"got {overlap_chunks}")
        self.cfg, self.mll, self.st, self.gate_mode = cfg, mll, st, gate_mode
        self.impl = impl
        self.overlap, self.overlap_chunks = overlap, overlap_chunks
        self.mesh, self.spmd = mesh, None
        self.num_workers = int(st.rates.shape[0])
        if mesh is not None:
            sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
            if "workers" not in sizes:
                raise ValueError(
                    f"mesh axes {sizes} carry no 'workers' axis — the SPMD "
                    "harness shards the worker fleet on it (--mesh W,D)")
            if self.num_workers % sizes["workers"]:
                raise ValueError(
                    f"mesh workers axis ({sizes['workers']}) must divide "
                    f"the fleet W={self.num_workers} — fix the mesh shape")
            self.spmd = protocol.SpmdAxis("workers", int(sizes["workers"]),
                                          self.num_workers)
            # fail at construction, not inside the first event's trace
            protocol.resolve_mixing(mll).validate_spmd(st, self.spmd)
        step = partial(mll_harness_step, cfg=cfg, mll=mll, st=st,
                       gate_mode=gate_mode, impl=impl, spmd=self.spmd,
                       overlap=overlap, overlap_chunks=overlap_chunks)
        # spmd-free twin used ONLY for `jax.eval_shape` (out_specs): the
        # collective lowerings call `axis_index`, which is unbound outside
        # shard_map — the global output shapes are identical either way
        ref = partial(mll_harness_step, cfg=cfg, mll=mll, st=st,
                      gate_mode=gate_mode, impl=impl)
        # (entry point, phase, chunk length) -> times traced.  Counted in
        # the Python body of each entry point, which runs only while JAX
        # traces it, so a call that hits the compiled program counts
        # nothing.  The dense entries have no phase (None).
        self.retraces: collections.Counter = collections.Counter()
        # (phase, form) -> times an event entry was traced in that form
        # (`train_step.event_form`: "rows" or "composed"; dense entries
        # have no phase)
        self.event_forms: collections.Counter = collections.Counter()
        traced = self._traced

        def last_metrics(state_metrics):
            state, ms = state_metrics
            return state, jax.tree.map(lambda m: m[-1], ms)

        def make_local_scan(stepfn, count: bool):
            def impl(state, batches, active):
                if count:
                    self.retraces["local_scan", protocol.PHASE_LOCAL,
                                  int(active.shape[0])] += 1

                def body(s, xs):
                    b, act = xs
                    return stepfn(s, b, act)
                return jax.lax.scan(body, state, (batches, active))
            return lambda s, b, a: last_metrics(impl(s, b, a))

        # The wrappers keep the XLA module names a profiler trace shows
        # (a lambda: jit__lambda, a partial: jit__unknown).
        # Second argument per entry: worker-axis position inside each
        # positional arg for the shard_map specs (None = replicate the
        # whole arg — the composed (W, W) event operator is contracted in
        # full by every shard).  Stacked scan batches carry workers at 1.
        self.local_scan = self._wrap(
            make_local_scan(step, True), (0, 1, 1),
            make_local_scan(ref, False))
        self.event_step = {
            ph: self._wrap(partial(traced, ("event_step", ph, 1), step,
                                   phase=ph), (0, 0, 0),
                           partial(ref, phase=ph))
            for ph in (protocol.PHASE_SUBNET, protocol.PHASE_HUB)}
        self.dense_step = self._wrap(
            lambda s, b, a, op: traced(("dense_step", None, 1), step,
                                       s, b, a, op=op),
            (0, 0, 0, None),
            lambda s, b, a, op: ref(s, b, a, op=op))
        # all-idle event slots (forced plans: a barrier round whose cost
        # exceeds tau ends in mixing with every gate at zero) skip the
        # backward pass and the θ=0 no-op update — loss metrics + mix only
        self.event_step_idle = {
            ph: self._wrap(partial(traced, ("event_step_idle", ph, 1), step,
                                   phase=ph, compute_grads=False),
                           (0, 0, 0),
                           partial(ref, phase=ph, compute_grads=False))
            for ph in (protocol.PHASE_SUBNET, protocol.PHASE_HUB)}
        self.dense_step_idle = self._wrap(
            lambda s, b, a, op: traced(("dense_step_idle", None, 1), step,
                                       s, b, a, op=op, compute_grads=False),
            (0, 0, 0, None),
            lambda s, b, a, op: ref(s, b, a, op=op, compute_grads=False))

    def _traced(self, key: tuple, fn, train_state, batch, active, **kwargs):
        """``fn(train_state, batch, active, **kwargs)``, counting a trace
        of entry ``key`` and the form of its event (the argument names are
        the program's)."""
        self.retraces[key] += 1
        form = event_form(self.mll, train_state.opt_state,
                          phase=kwargs.get("phase", protocol.PHASE_LOCAL),
                          op=kwargs.get("op"), spmd=self.spmd,
                          overlap=self.overlap)
        self.event_forms[key[1], form] += 1
        return fn(train_state, batch, active, **kwargs)

    def _wrap(self, fn, rules, shape_fn=None):
        """jit one entry point; under a mesh, `shard_map` it first.

        ``rules[i]`` is the worker-axis position inside positional arg i
        (None = replicate the whole arg).  in_specs come from the actual
        call's shapes, out_specs from `jax.eval_shape` of ``shape_fn``
        (the spmd-free twin — `fn` itself calls collectives that can't
        trace outside shard_map) with the lead-axis rule — both cached
        per arg structure/shapes, so each pow2 scan chunk compiles once,
        exactly like the plain jit path.  ``check_vma`` is off: the
        lowerings index full-width tables with `axis_index`, which the
        replication checker can't see through.

        The returned callable carries ``.build(*args)`` returning the
        underlying jitted function for those shapes — tests lower it to
        compiled HLO to assert mixing became psum/ppermute collectives."""
        if self.mesh is None:
            jitted = jax.jit(fn, donate_argnums=0)
            jitted.build = lambda *args: jitted
            return jitted
        mesh, w = self.mesh, self.num_workers
        cache: dict = {}

        def build(*args):
            key = (jax.tree.structure(args),
                   tuple((jnp.shape(x), jnp.result_type(x))
                         for x in jax.tree.leaves(args)))
            if key not in cache:
                in_specs = tuple(
                    jax.tree.map(lambda x: P(), arg) if ax is None else
                    jax.tree.map(partial(_worker_spec, w=w, axis=ax), arg)
                    for arg, ax in zip(args, rules))
                out_specs = jax.tree.map(
                    partial(_worker_spec, w=w, axis=0),
                    jax.eval_shape(shape_fn or fn, *args))
                cache[key] = jax.jit(jax.shard_map(
                    fn, mesh=mesh, in_specs=in_specs, out_specs=out_specs,
                    check_vma=False), donate_argnums=0)
            return cache[key]

        def call(*args):
            return build(*args)(*args)

        call.build = build
        return call

    # ------------------------------------------------------------ driver
    def run_span(self, state: protocol.MLLTrainState,
                 plan: timeline.TimelinePlan, batcher: LMBatcher,
                 rng: np.random.Generator, lo: int, hi: int,
                 last_metrics: dict | None = None,
                 ) -> tuple[protocol.MLLTrainState, dict | None]:
        """Execute plan slots [lo, hi) event-sparsely.

        One batch is drawn per slot (the data-cursor contract resumable
        checkpoints rely on); all-idle runs of forced plans advance the
        cursor and the slot counter without computing gradients.

        The host's work is marked with `spans` (names in `launch.spans`):
        the call itself (``run_span``), each batch drawn, each stack and
        copy of a launch's inputs, each launch of a compiled step (a
        dispatch span covers the call alone, so its length is the host's
        time to launch) and each fast-forward.  The dispatch and
        fast-forward spans carry the slots they cover (``slots``)."""
        with spans.span(spans.RUN_SPAN, lo=lo, hi=hi):
            return self._run_span(state, plan, batcher, rng, lo, hi,
                                  last_metrics)

    def _run_span(self, state, plan, batcher, rng, lo, hi, last_metrics):
        op_mats = plan.op_mats or {}
        forced = plan.gate_mode == "forced"
        s = lo
        while s < hi:
            e = s
            while e < hi and plan.op_ids[e] == 0 and e not in op_mats:
                e += 1
            off = s
            while off < e:                      # local-only slots [s, e)
                if forced and not plan.active[off].any():
                    j = off                      # all-idle run: fast-forward
                    while j < e and not plan.active[j].any():
                        j += 1
                    with spans.span(spans.SKIP_IDLE, slots=j - off):
                        batcher.skip(rng, j - off)
                        state = state._replace(step=state.step + (j - off))
                    off = j
                    continue
                j = off
                if forced:
                    while j < e and plan.active[j].any():
                        j += 1
                else:
                    j = e
                run = j - off
                while run:
                    k = 1 << (run.bit_length() - 1)   # pow2: O(log) compiles
                    drawn = [_draw(batcher, rng) for _ in range(k)]
                    with spans.span(spans.STACK_BATCHES, slots=k):
                        batches = _stack_batches(drawn)
                        act = jnp.asarray(plan.active[off:off + k])
                    with spans.span(spans.LOCAL_SCAN, slots=k):
                        state, last_metrics = self.local_scan(
                            state, batches, act)
                    off += k
                    run -= k
            if e < hi:                          # the event slot itself
                batch = _draw(batcher, rng)
                idle = forced and not plan.active[e].any()
                with spans.span(spans.STACK_BATCHES, slots=1):
                    act = jnp.asarray(plan.active[e])
                    op = jnp.asarray(op_mats[e]) if e in op_mats else None
                if op is not None:
                    fn = self.dense_step_idle if idle else self.dense_step
                    with spans.span(spans.DENSE_STEP, slots=1,
                                    idle=int(idle)):
                        state, last_metrics = fn(state, batch, act, op)
                else:
                    ph = int(plan.op_ids[e])
                    table = (self.event_step_idle if idle
                             else self.event_step)
                    with spans.span(spans.event_step(ph), slots=1,
                                    idle=int(idle)):
                        state, last_metrics = table[ph](state, batch, act)
            s = e + 1
        return state, last_metrics


# ----------------------------------------------------------- run lifecycle
def plan_config(mll: MLLConfig, network, plan: timeline.TimelinePlan,
                policy: str, rate_model: str) -> dict:
    """Everything that determines the compiled plan (and hence the
    trajectory).  Recorded in every full-protocol checkpoint; a resume
    whose rebuilt config differs would silently splice two different
    plans into one 'successful' run — `restore_state` callers must
    compare (see `launch.train.run_training`)."""
    return {"policy": policy, "rate_model": rate_model,
            "slots": int(plan.slots), "tau": int(mll.tau), "q": int(mll.q),
            "eta": float(mll.eta), "hub_topology": mll.hub_topology,
            "mixing": mll.mixing, "mix_dtype": mll.mix_dtype,
            "inner_opt": mll.inner_opt,
            "inner_opt_args": [list(kv) for kv in mll.inner_opt_args],
            "seed": int(mll.seed),
            "workers_per_subnet": [int(n) for n in
                                   network.workers_per_subnet],
            "worker_rates": [float(r) for r in network.worker_rates]}


@dataclasses.dataclass
class HarnessRun:
    """What a plan-driven run returns (the launcher's result contract)."""
    history: dict
    avg_params: PyTree
    train_state: protocol.MLLTrainState
    plan: timeline.TimelinePlan
    network: Any
    calibration: timeline.RateCalibration | None = None
    trace_path: str | None = None
    harness: TrainHarness | None = None   # the compiled steps that ran


def _boundaries(plan: timeline.TimelinePlan, start: int, stop: int,
                eval_every: int, checkpoint_every: int) -> list[int]:
    """Host-surface points: eval slots, checkpoint slots, the stop/end."""
    pts = {stop}
    if eval_every:
        pts.update(range(eval_every, stop + 1, eval_every))
    if checkpoint_every:
        pts.update(range(checkpoint_every, stop + 1, checkpoint_every))
    return sorted(p for p in pts if p > start)


def run_plan(cfg: ArchConfig, mll: MLLConfig, network, st: MLLState,
             plan: timeline.TimelinePlan, batcher: LMBatcher,
             rng: np.random.Generator, train_state: protocol.MLLTrainState,
             *, start_slot: int = 0, stop_slot: int | None = None,
             eval_every: int = 16,
             checkpoint_dir: str | None = None, checkpoint_every: int = 0,
             calibration: timeline.RateCalibration | None = None,
             trace_path: str | None = None, policy: str = "deadline",
             rate_model: str = "bernoulli",
             last_worker_loss: list | None = None,
             run_config: dict | None = None, impl: str = "xla",
             mesh=None, overlap: str = "none", overlap_chunks: int = 4,
             log: Callable = print) -> HarnessRun:
    """Drive a compiled `TrainHarness` over the whole plan.

    ``train_state`` is consumed: the harness donates it to its first
    step (see `TrainHarness`).

    ``mesh`` switches the harness to shard_map execution (see
    `TrainHarness`): the incoming state is laid out on the mesh up front,
    and at every host boundary the params are gathered back so u_k, eval
    and checkpoints are computed on one device exactly as the vmap path
    computes them — checkpoints stay portable across device counts.

    The slot loop surfaces to the host only at eval/checkpoint boundaries;
    u_k = X a is computed ONCE per boundary and shared by eval, periodic
    checkpoints and the final checkpoint.  Checkpoints carry the full
    protocol state + cursors (`checkpoint.save_state`), so a killed run
    resumed from ``start_slot`` replays the remaining slots bit for bit.

    ``stop_slot`` executes only slots [start_slot, stop_slot) OF THE SAME
    PLAN and checkpoints there (policies' plans are budget-dependent —
    barrier drops rounds that don't fit — so a shorter-budget run is NOT a
    prefix of a longer one; a partial run of the full plan is).
    """
    harness = TrainHarness(cfg, mll, st, gate_mode=plan.gate_mode, impl=impl,
                           mesh=mesh, overlap=overlap,
                           overlap_chunks=overlap_chunks)
    if mesh is not None:
        train_state = shard_train_state(train_state, mesh,
                                        harness.num_workers)
    gather = jax.device_get if mesh is not None else (lambda t: t)
    a = jnp.asarray(network.a, jnp.float32)
    eval_fn = jax.jit(partial(loss_fn, cfg=cfg, impl=impl))
    history = {"step": [], "loss": [], "avg_loss": []}
    # the most recent per-worker training loss; restored on resume so an
    # eval boundary inside an all-idle straggler tail records the same
    # (stale) metric the uninterrupted run would
    last_metrics = (None if last_worker_loss is None
                    else {"loss": np.asarray(last_worker_loss, np.float32)})
    t0 = time.time()
    done = start_slot
    final_u = None
    stop = plan.slots if stop_slot is None else min(stop_slot, plan.slots)
    traced = None       # the harness's traces after the first boundary
    forms = {}          # the event forms last logged
    for b in _boundaries(plan, start_slot, stop, eval_every,
                         checkpoint_every):
        train_state, last_metrics = harness.run_span(
            train_state, plan, batcher, rng, done, b, last_metrics)
        done = b
        if traced is None:
            traced = dict(harness.retraces)
        u = None
        if (eval_every and done % eval_every == 0) or done == plan.slots:
            u = weighted_average(gather(train_state.params), a)
            eb = batcher.sample(rng)
            one = {kk: v[0] for kk, v in eb.items()}
            avg_loss, _ = eval_fn(u, one)
            # gather BEFORE reducing: .mean() on a worker-sharded (W,)
            # array would lower to a cross-device reduction whose
            # accumulation order drifts from the single-device mean.  The
            # reduction itself stays a jnp mean so the vmap path keeps
            # emitting the exact bits the legacy trainer reference does
            wl = (float(jnp.mean(jnp.asarray(
                      np.asarray(gather(last_metrics["loss"])))))
                  if last_metrics is not None else float("nan"))
            history["step"].append(done)
            history["loss"].append(wl)
            history["avg_loss"].append(float(avg_loss))
            retraced = (f"  retraces {dict(harness.retraces)}"
                        if harness.retraces != traced else "")
            if harness.event_forms != forms:
                forms = dict(harness.event_forms)
                retraced += f"  event forms {forms}"
            log(f"slot {done:5d}  worker-loss {wl:.4f}  u_k-loss "
                f"{float(avg_loss):.4f}  ({time.time()-t0:.1f}s){retraced}")
        want_ckpt = (checkpoint_dir and checkpoint_every
                     and done % checkpoint_every == 0) or \
                    (checkpoint_dir and done == stop)
        if want_ckpt:
            if u is None:
                u = weighted_average(gather(train_state.params), a)
            checkpoint.save(checkpoint_dir, u, step=done)
            wl = (None if last_metrics is None else
                  [float(x) for x in np.asarray(last_metrics["loss"])])
            checkpoint.save_state(
                checkpoint_dir, train_state, slot=done,
                rng_state=rng_state(rng),
                extra={"policy": policy, "rate_model": rate_model,
                       "last_worker_loss": wl,
                       # informational only — deliberately OUTSIDE the
                       # resume guard's plan_config, so checkpoints stay
                       # portable across mesh shapes / device counts
                       "mesh": dict(zip(mesh.axis_names,
                                        (int(s) for s in
                                         mesh.devices.shape)))
                       if mesh is not None else None,
                       "plan_config": run_config if run_config is not None
                       else plan_config(mll, network, plan, policy,
                                        rate_model)})
        if done == plan.slots:
            final_u = u
    # u_k is computed ONCE per boundary and shared by eval + checkpoints;
    # the final boundary's u is the run's result (recompute only on the
    # resume-past-the-end no-op path)
    u = final_u if final_u is not None \
        else weighted_average(gather(train_state.params), a)
    out_trace = None
    if trace_path:
        meta = {"policy": policy, "rate_model": rate_model,
                "arch": cfg.name, "source": "launch.harness"}
        if calibration is not None:
            meta["calibration"] = calibration.to_json()
        out_trace = timeline.export_trace(trace_path, plan, **meta)
    return HarnessRun(history=history, avg_params=u, train_state=train_state,
                      plan=plan, network=network, calibration=calibration,
                      trace_path=out_trace, harness=harness)
