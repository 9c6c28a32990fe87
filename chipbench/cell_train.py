"""Training cells: the MLL-SGD trainer's plan executor at a configuration's
published widths.

Set-up builds ONE `TrainHarness`, as `launch.harness.run_plan` builds it,
with its train state made on the device from the seed.  It drives that
harness through the plan's first hub round by `TrainHarness.run_span`
calls that end at the traffic's ``check_slots`` (the slots the reference
follows; together they run every program the window runs), then on to a
hub-round boundary a whole round later.  The window then runs whole hub
rounds through `TrainHarness.run_span` until ``seconds`` have passed, and
ends at `block_until_ready` on the state.

Without a ``mesh`` in the traffic every worker is vmapped on the first
chip.  With ``"mesh": [workers, data]`` the harness runs its `shard_map`
path over a mesh of exactly the cell's chips, axes ("workers", "data"),
and the state is made already laid out on it: each worker's model on its
shard of the ``workers`` axis, never all of them on one chip.
"""
from __future__ import annotations

import collections
import dataclasses
import math
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding

import tokens as tokens_mod
from weights import LAYER_KEYS, Spec, from_program, make_weights, to_program

PLAN_SLOTS = 65536      # more slots than any window runs
AHEAD_S = 6.0           # seconds of rounds the window keeps queued


def arch_config(name: str, spec: Spec):
    """The trainer's ArchConfig for a configuration file."""
    from repro.configs.base import ArchConfig
    return ArchConfig(
        name=name, family="dense", source="chipbench", num_layers=spec.layers,
        d_model=spec.d, n_heads=spec.heads, n_kv_heads=spec.kv_heads,
        head_dim=spec.head_dim, d_ff=spec.ffn, vocab_size=spec.vocab,
        pattern=("attn",), rope="standard", rope_theta=spec.rope_theta,
        qk_norm=spec.qk_norm, qkv_bias=spec.attention_bias,
        activation="swiglu", norm="rmsnorm", norm_eps=spec.eps,
        tie_embeddings=spec.tied, param_dtype=spec.dtype,
        compute_dtype=spec.dtype)


class TrainCell:
    """One training cell: harness, state, data feed, and plan."""

    def __init__(self, name: str, cfg: dict, traffic: dict, seed: int,
                 devices: list):
        from repro.core.mllsgd import MLLConfig, build_network, build_state
        from repro.core.protocol import init_train_state
        from repro.core.timeline import get_policy
        from repro.data.pipeline import LMBatcher
        from repro.launch.harness import TrainHarness

        self.spec, self.traffic, self.seed = Spec.from_json(cfg), traffic, seed
        t = traffic
        # the Bernoulli gate's seed is the protocol's configuration, not an
        # input: it is compiled into the step programs as a constant, so a
        # seed drawn per run would recompile every program in every run
        self.gate_seed = t["gate_seed"]
        self.workers = t["subnets"] * t["workers_per_subnet"]
        self.arch = arch_config(name, self.spec)
        mll = MLLConfig(tau=t["tau"], q=t["q"], eta=t["eta"],
                        hub_topology=t["topology"], mixing=t["mixing"],
                        inner_opt=t["inner_opt"],
                        worker_rates=tuple(t["rates"]), seed=self.gate_seed)
        network = build_network(
            dataclasses.replace(mll, granularity="worker_per_data"),
            t["subnets"], t["workers_per_subnet"])
        st = build_state(mll, network)
        self.plan = get_policy(t["policy"]).plan(
            network, mll.schedule, PLAN_SLOTS, np.random.default_rng(seed),
            rate_model="bernoulli")
        self.round = t["tau"] * t["q"]
        self.mesh = cell_mesh(t, devices)
        self.harness = TrainHarness(self.arch, mll, st,
                                    gate_mode=self.plan.gate_mode,
                                    impl=t["impl"], mesh=self.mesh)
        w = self.workers

        def init(w0):
            params = jax.tree.map(
                lambda x: jnp.broadcast_to(x[None], (w,) + x.shape),
                to_program(w0))
            return init_train_state(params, cfg=mll)

        w0 = make_weights(self.spec, seed)
        shapes = jax.eval_shape(init, w0)
        self._check_layout(shapes.params)
        if self.mesh is None:
            self.state = jax.jit(init)(w0)
        else:
            from repro.launch.harness import _worker_spec
            self.state = jax.jit(init, out_shardings=jax.tree.map(
                lambda x: NamedSharding(self.mesh, _worker_spec(x, w)),
                shapes))(w0)
        del w0
        stream = tokens_mod.bigram_stream(
            w, t["tokens_per_worker"], self.spec.vocab, seed, **t["bigram"])
        self.batcher = LMBatcher(stream, t["seq_len"], t["batch_per_worker"])
        self.rng = np.random.default_rng(seed)
        self.metrics = None
        self.slot = 0
        self.round_s = None

    def _check_layout(self, params) -> None:
        """Fail loudly if the trainer's parameter tree no longer matches
        the layout `weights.to_program` writes."""
        from repro.models import model as model_mod
        want = jax.eval_shape(
            lambda k: jax.tree.map(
                lambda x: jnp.broadcast_to(x[None], (self.workers,) + x.shape),
                model_mod.init_model(k, self.arch)), jax.random.PRNGKey(0))
        got = jax.tree.map(lambda x: (x.shape, x.dtype), params)
        exp = jax.tree.map(lambda x: (x.shape, x.dtype), want)
        if got != exp:
            raise RuntimeError("the trainer's parameter layout changed; "
                               "chipbench/weights.py no longer maps onto it")

    @staticmethod
    @jax.jit
    def _delta(params, w0):
        """Per worker and layer, ||initial - current|| of every weight."""
        out = {}
        for name, x in from_program(params).items():
            d = x.astype(jnp.float32) - w0[name].astype(jnp.float32)[None]
            keep = 2 if name in LAYER_KEYS else 1
            out[name] = jnp.sqrt(jnp.sum(d * d, tuple(range(keep, d.ndim))))
        return out

    def delta_norms(self) -> dict:
        w0 = make_weights(self.spec, self.seed)
        out = jax.device_get(self._delta(self.state.params, w0))
        del w0
        flat = {}
        for name, v in out.items():
            for i in range(self.workers):
                if v.ndim == 1:
                    flat[f"w{i}/{name}"] = float(v[i])
                else:
                    flat.update({f"w{i}/{name}.{l}": float(x)
                                 for l, x in enumerate(v[i])})
        return flat

    # ------------------------------------------------------------ driving
    def advance(self, hi: int) -> None:
        self.state, self.metrics = self.harness.run_span(
            self.state, self.plan, self.batcher, self.rng, self.slot, hi,
            self.metrics)
        self.slot = hi

    def follow(self, slots: list[int]) -> dict:
        """What the reference follows: one `run_span` call from the last
        slot reached to each of ``slots`` (the first is 1), so the window's
        own programs run, a local scan carrying its state over its chunk
        and each event program among them.  Returns the per-worker loss
        of the last slot of each call, and the change of the weights after
        each call."""
        if slots[0] != 1:
            raise ValueError("the followed slots start at slot 1")
        losses, deltas = [], []
        for k in slots:
            self.advance(k)
            losses.append(np.asarray(jax.device_get(self.metrics["loss"]),
                                     np.float64))
            deltas.append(self.delta_norms())
        return {"loss": np.stack(losses), "deltas": deltas,
                "delta1": deltas[0], "delta_last": deltas[-1]}

    def batches(self) -> list[dict]:
        return first_batches(self.spec, self.traffic, self.seed,
                             self.traffic["check_slots"][-1])

    def warm(self) -> None:
        """Run on to the first hub-round boundary a whole round past the
        followed slots: every program and shape the window uses has then
        run at least once.  One more round is timed: the window's estimate
        of a round's length."""
        r = self.round
        self.advance(-(-(self.slot + r) // r) * r)
        jax.block_until_ready(self.state)
        t0 = time.perf_counter()
        self.advance(self.slot + r)
        jax.block_until_ready(self.state)
        self.round_s = time.perf_counter() - t0

    def window(self, seconds: float) -> dict:
        """Whole hub rounds until ``seconds`` have passed.  About AHEAD_S
        seconds of rounds are kept queued on the device, so that a stall
        of the host does not leave it idle: the host waits for round r - n
        only after it has queued round r.  When ``seconds`` have passed
        nothing more is queued; the window waits for every round sent and
        then reads the clock, so all of them count, over all that time."""
        ahead = max(1, math.ceil(AHEAD_S / self.round_s))
        lo = self.slot
        t0 = time.perf_counter()
        pending = collections.deque()
        while True:
            if self.slot + self.round > self.plan.slots:
                raise RuntimeError("the plan is shorter than the window")
            self.advance(self.slot + self.round)
            pending.append(self.metrics)
            if len(pending) > ahead:
                jax.block_until_ready(pending.popleft())
            if time.perf_counter() - t0 >= seconds:
                break
        jax.block_until_ready(self.state)
        t1 = time.perf_counter()
        ops = self.plan.op_ids[lo:self.slot]
        t = self.traffic
        slots = self.slot - lo
        return {"seconds": t1 - t0, "slots": slots, "ahead": ahead,
                "event_slots": int((ops != 0).sum()),
                "local_slots": int((ops == 0).sum()),
                "tokens": slots * self.workers * t["batch_per_worker"]
                * t["seq_len"]}

    def free(self) -> None:
        """Drop the trainer's state before the reference runs."""
        self.state = self.metrics = self.harness = None


def cell_mesh(traffic: dict, devices: list):
    """The ("workers", "data") mesh over exactly ``devices`` that the
    traffic's ``mesh`` asks for, or None for vmap on the first chip."""
    if "mesh" not in traffic:
        return None
    from jax.experimental import mesh_utils
    from jax.sharding import AxisType, Mesh
    shape = tuple(traffic["mesh"])
    return Mesh(mesh_utils.create_device_mesh(shape, devices),
                ("workers", "data"), axis_types=(AxisType.Auto,) * 2)


def first_batches(spec: Spec, traffic: dict, seed: int, n: int) -> list:
    """The first n batches of a cell's feed, drawn again from the seed by
    the benchmark's own sampler: the reference's input."""
    t = traffic
    stream = tokens_mod.bigram_stream(
        t["subnets"] * t["workers_per_subnet"], t["tokens_per_worker"],
        spec.vocab, seed, **t["bigram"])
    rng = np.random.default_rng(seed)
    return [tokens_mod.sample(stream, rng, t["seq_len"],
                              t["batch_per_worker"]) for _ in range(n)]


def memory_peak(devices: list) -> int:
    peaks = [d.memory_stats().get("peak_bytes_in_use", 0)
             for d in devices if d.memory_stats()]
    return int(max(peaks)) if peaks else 0

