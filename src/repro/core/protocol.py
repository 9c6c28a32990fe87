"""Protocol engine: the paper's algorithm family as (mixing x inner-opt x schedule).

The paper's observation (Section 5) is that Distributed SGD, Local SGD,
HL-SGD and MLL-SGD are ONE algorithm parameterized by an averaging operator
schedule.  This module makes that literal in code: every execution path
(simulator, production mesh trainer, hub-level outer optimizer) drives the
same three pluggable pieces:

  1. a **MixingStrategy** from the registry below — how the subnet (V) and
     hub (Z) averaging rounds are realised (dense einsum, grouped two-stage,
     circulant ppermute rolls) and what the hub wire carries (the
     compression ladder: bf16, int8, int8/int4 + error feedback, top-k
     sparsification, low-rank PowerSGD factors — each with a `wire_bytes`
     accounting hook the benchmarks plot against loss),
  2. an **inner optimizer** (`repro.optim.optimizers.Optimizer`) applied
     per worker under the Bernoulli(p_i) gate of Eq. (3) — a gated worker
     skips the step entirely: params AND optimizer state stay frozen,
  3. the (tau, q) **schedule** choosing local / subnet / hub per tick.

Registering a new strategy is ~15 lines:

    from repro.core.protocol import MixingStrategy, register

    @register("my_mix")
    class MyMixing(MixingStrategy):
        def subnet(self, stacked, st):  # V round
            ...
        def hub(self, stacked, st):     # Z round
            ...

after which ``MLLConfig(mixing="my_mix")`` runs it through every path.
Stateful strategies (e.g. error feedback) additionally override
``init_state`` and ``hub_with_state``; the engine threads the state through
``lax.switch`` alongside the params.

With ``sgd`` + any stateless strategy, ``protocol_step`` reproduces the
legacy ``mll_train_step`` trajectory bit-for-bit (property-tested in
tests/test_protocol.py).
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import packing
from repro.optim import optimizers as optim_mod

PyTree = Any

PHASE_LOCAL, PHASE_SUBNET, PHASE_HUB = 0, 1, 2


@dataclasses.dataclass(frozen=True)
class MLLState:
    """Static (traced-constant) operator bundle used inside train steps.

    ``workers_per_subnet`` is 0 when sub-networks have unequal sizes; only
    the dense (matrix) strategies support that case — grouped strategies
    raise at trace time.
    """
    v_op: jnp.ndarray           # (W, W)
    z_op: jnp.ndarray           # (W, W)
    v_weights: jnp.ndarray      # (W,) within-subnet weights
    h: jnp.ndarray              # (D, D)
    rates: jnp.ndarray          # (W,)
    num_subnets: int
    workers_per_subnet: int


def state_from_network(network, dtype=jnp.float32) -> MLLState:
    """Operator bundle for any MultiLevelNetwork (unequal subnets allowed)."""
    nd = set(network.workers_per_subnet)
    return MLLState(
        v_op=jnp.asarray(network.v_matrix(), dtype=dtype),
        z_op=jnp.asarray(network.z_matrix(), dtype=dtype),
        v_weights=jnp.asarray(network.v, dtype=dtype),
        h=jnp.asarray(network.hub_net.h, dtype=dtype),
        rates=jnp.asarray(network.worker_rates, dtype=dtype),
        num_subnets=network.num_subnets,
        workers_per_subnet=int(next(iter(nd))) if len(nd) == 1 else 0,
    )


# ----------------------------------------------------------------- primitives
def phase_of(step: jnp.ndarray, tau: int, q: int) -> jnp.ndarray:
    """Phase of 1-based step: 0 local / 1 subnet / 2 hub (Eq. 6)."""
    hub = (step % (q * tau)) == 0
    sub = (step % tau) == 0
    return jnp.where(hub, PHASE_HUB, jnp.where(sub, PHASE_SUBNET, PHASE_LOCAL))


def gate_sample(seed: int, step: jnp.ndarray, rates: jnp.ndarray) -> jnp.ndarray:
    """theta_k ~ Bernoulli(p_i), identical on every device (counter-based)."""
    key = jax.random.fold_in(jax.random.PRNGKey(seed), step)
    u = jax.random.uniform(key, rates.shape, dtype=rates.dtype)
    return (u < rates).astype(rates.dtype)


def gated_sgd_update(stacked: PyTree, grads: PyTree, theta: jnp.ndarray,
                     eta: float) -> PyTree:
    """x_i <- x_i - eta * theta_i * g_i  per worker (Eq. 2/3)."""
    def upd(x, g):
        gate = theta.astype(x.dtype).reshape(theta.shape + (1,) * (x.ndim - 1))
        return x - jnp.asarray(eta, x.dtype) * gate * g.astype(x.dtype)
    return jax.tree.map(upd, stacked, grads)


def _einsum_operator(t: jnp.ndarray, stacked: PyTree,
                     mix_dtype: str | None) -> PyTree:
    # flat fast path: one (W, W) x (W, C) einsum over the packed buffer
    # (`repro.core.packing`) instead of a dispatch per leaf.  Engaged only
    # where dispatch count is the bottleneck (TPU / explicit override) and
    # when it is semantics-preserving: every leaf f32 and f32 mixing.
    if packing.flat_paths_enabled() and mix_dtype in (None, "float32") \
            and packing.all_f32(stacked):
        return packing.apply_operator_packed(stacked, t)

    def mix(x):
        xm = x.astype(mix_dtype) if mix_dtype else x
        y = jnp.einsum("ij,i...->j...", t.astype(xm.dtype), xm)
        return y.astype(x.dtype)
    return jax.tree.map(mix, stacked)


# ------------------------------------------------------------ SPMD lowering
@dataclasses.dataclass(frozen=True)
class SpmdAxis:
    """Static description of the sharded worker axis inside `shard_map`.

    The SPMD harness (`launch.harness.TrainHarness(mesh=...)`) runs plan
    slots with the stacked (W, ...) state SHARDED over a mesh axis instead
    of vmapped on one device; strategies then lower their averaging rounds
    to real collectives over ``name`` via the ``*_spmd`` methods below.

    The ``data`` mesh axis (when present) REPLICATES compute: sharding the
    within-worker batch would psum partial loss sums and change the f32
    reduction order, breaking the bit-identity contract with the
    single-host vmap path.  It reserves the mesh slot for future
    within-worker parallelism (FSDP dim-0 sharding, batch splits).
    """
    name: str          # mesh axis name the worker dim is sharded over
    size: int          # number of shards on that axis
    num_workers: int   # global W

    def __post_init__(self):
        if self.size < 1 or self.num_workers % self.size:
            raise ValueError(
                f"workers mesh axis of size {self.size} must divide "
                f"W={self.num_workers}")

    @property
    def per_shard(self) -> int:
        return self.num_workers // self.size

    def offset(self) -> jnp.ndarray:
        """Traced global index of this shard's first worker row."""
        return jax.lax.axis_index(self.name) * self.per_shard


def spmd_capable_mixing() -> tuple[str, ...]:
    """Registered strategies with a collective (SPMD) lowering."""
    return tuple(sorted(n for n, c in MIXING_REGISTRY.items()
                        if c.spmd_capable))


def grouped_spmd_layout(st: MLLState, spmd: SpmdAxis) -> int:
    """Shards per sub-network for the grouped collective lowerings.

    Returns 0 when the whole worker axis lives on one shard (the round is
    shard-local vmap math), otherwise the number of shards each
    sub-network spans.  The psum/ppermute lowerings need subnet-ALIGNED
    shards — every shard entirely inside one sub-network — so the subnet
    mean is one grouped all-reduce and the hub stage one permute per roll.
    """
    d, nd = _grouped_dims(st)
    ps = spmd.per_shard
    if spmd.size == 1:
        return 0
    if nd % ps:
        raise ValueError(
            f"grouped SPMD mixing needs subnet-aligned shards: {ps} workers "
            f"per shard must divide Nd={nd} (W={spmd.num_workers} over "
            f"{spmd.size} shards, D={d} sub-networks); use mixing='dense' "
            "or a workers axis that divides the subnet size")
    return nd // ps


def _subnet_groups(d: int, sps: int) -> list[list[int]]:
    """psum replica groups: sub-network g owns shards [g*sps, (g+1)*sps)."""
    return [[g * sps + s for s in range(sps)] for g in range(d)]


def _einsum_operator_spmd(t: jnp.ndarray, local: PyTree,
                          mix_dtype: str | None, spmd: SpmdAxis) -> PyTree:
    """SPMD lowering of `_einsum_operator`: all-gather the contracted
    worker axis, contract into this shard's output rows only.

    Bit-identical to the full (W, W) einsum: each output row's contraction
    runs over the same gathered operand with the same length — only the
    set of output rows shrinks.  One all-gather per leaf (or ONE for the
    packed buffer where the flat paths are enabled)."""
    if packing.flat_paths_enabled() and mix_dtype in (None, "float32") \
            and packing.all_f32(local):
        spec = packing.pack_spec(local)           # per-shard (W/size, sum C)
        buf = packing.pack(local, spec)
        full = jax.lax.all_gather(buf, spmd.name, axis=0, tiled=True)
        tl = jax.lax.dynamic_slice_in_dim(
            t.astype(jnp.float32), spmd.offset(), spmd.per_shard, 1)
        return packing.unpack(jnp.einsum("ij,ic->jc", tl, full), spec)

    def mix(x):
        xm = x.astype(mix_dtype) if mix_dtype else x
        full = jax.lax.all_gather(xm, spmd.name, axis=0, tiled=True)
        tl = jax.lax.dynamic_slice_in_dim(
            t.astype(xm.dtype), spmd.offset(), spmd.per_shard, 1)
        y = jnp.einsum("ij,i...->j...", tl, full)
        return y.astype(x.dtype)
    return jax.tree.map(mix, local)


def _grouped_dims(st: MLLState) -> tuple[int, int]:
    if st.workers_per_subnet <= 0:
        raise ValueError(
            "grouped mixing (two_stage/ppermute/int8/int8_ef) requires "
            "equal-size sub-networks; use mixing='dense' for unequal subnets")
    return st.num_subnets, st.workers_per_subnet


def subnet_average_dense(stacked: PyTree, st: MLLState,
                         mix_dtype: str | None = None) -> PyTree:
    return _einsum_operator(st.v_op, stacked, mix_dtype)


def hub_average_dense(stacked: PyTree, st: MLLState,
                      mix_dtype: str | None = None) -> PyTree:
    return _einsum_operator(st.z_op, stacked, mix_dtype)


def _product_mean(v: jnp.ndarray, xg: jnp.ndarray) -> jnp.ndarray:
    """Within-subnet weighted mean of (D, Nd, ...) as rounded per-worker
    PRODUCTS + an explicit reduce over Nd — term-for-term the arithmetic
    the SPMD psum lowering performs (an einsum's fused multiply-accumulate
    has no cross-device analogue, so the two would differ in ULPs)."""
    return (v.reshape(v.shape + (1,) * (xg.ndim - 2)) * xg).sum(axis=1)


def _roll_mix(h: jnp.ndarray, z: jnp.ndarray) -> jnp.ndarray:
    """y_e = sum_o H[(e+o) mod D, e] * z_{(e+o) mod D}, accumulated in
    ascending roll order o: one elementwise product + add per roll, matching
    the SPMD ppermute lowering add-for-add (general H — the circulant
    `hub_average_ppermute` loop is the same shape with scalar weights)."""
    d = z.shape[0]
    e = np.arange(d)
    y = None
    for o in range(d):
        w = h[(e + o) % d, e].reshape((d,) + (1,) * (z.ndim - 1))
        term = w * (jnp.roll(z, -o, axis=0) if o else z)
        y = term if y is None else y + term
    return y


def subnet_average_two_stage(stacked: PyTree, st: MLLState,
                             mix_dtype: str | None = None) -> PyTree:
    """Grouped weighted mean: reshape W->(D, Nd), reduce Nd, broadcast back.

    GSPMD lowers the Nd reduction to an all-reduce whose replica groups stay
    inside each pod (ICI), instead of a dense W x W global contraction; the
    explicit `_product_mean` form keeps it bit-compatible with the
    shard_map psum lowering (`subnet_average_two_stage_spmd`).
    """
    d, nd = _grouped_dims(st)
    v = st.v_weights.reshape(d, nd)

    def mix(x):
        xm = x.astype(mix_dtype) if mix_dtype else x
        xg = xm.reshape((d, nd) + x.shape[1:])
        mean = _product_mean(v.astype(xm.dtype), xg)
        y = jnp.broadcast_to(mean[:, None], xg.shape).reshape(x.shape)
        return y.astype(x.dtype)
    return jax.tree.map(mix, stacked)


def hub_average_two_stage(stacked: PyTree, st: MLLState,
                          mix_dtype: str | None = None) -> PyTree:
    """Subnet average, then H-mix the D hub models over the pod axis (as
    weighted rolls — see `_roll_mix` for why not a D x D einsum)."""
    d, nd = _grouped_dims(st)
    v = st.v_weights.reshape(d, nd)

    def mix(x):
        xm = x.astype(mix_dtype) if mix_dtype else x
        xg = xm.reshape((d, nd) + x.shape[1:])
        z = _product_mean(v.astype(xm.dtype), xg)            # hub models
        y = _roll_mix(st.h.astype(xm.dtype), z)              # H mixing
        out = jnp.broadcast_to(y[:, None], xg.shape).reshape(x.shape)
        return out.astype(x.dtype)
    return jax.tree.map(mix, stacked)


def _chain_sum(terms: list, dtype) -> jnp.ndarray:
    """terms[0] + terms[1] + ... added in ascending order in ``dtype``.

    The first add is written ``terms[1] + terms[0]``.  IEEE addition
    commutes, so without contraction the bits are the same; XLA:CPU
    contracts an f32 product into the add that consumes it, the left
    operand's first, and so contracts each LATER product into the running
    sum, as it does in `_product_mean`'s reduce and `_roll_mix`'s adds."""
    ts = [t.astype(dtype) for t in terms]
    acc = ts[0] if len(ts) == 1 else ts[1] + ts[0]
    for t in ts[2:]:
        acc = acc + t
    return acc


def _round_to(x: jnp.ndarray, dtype) -> jnp.ndarray:
    """``x.astype(dtype)`` with the rounding made an op of its own.

    XLA:TPU computes a fusion's 16-bit float ops in f32 and drops the casts
    between them, so a rounding that the composed form makes by writing a
    bf16 array (the updated params, the subnet mean) would vanish inside
    the one fused loop of the row form.  An explicit ``reduce_precision``
    keeps it; on values already rounded it changes nothing."""
    dtype = jnp.dtype(dtype)
    fi = jnp.finfo(dtype)
    if fi.bits < 32:
        x = jax.lax.reduce_precision(x.astype(jnp.float32),
                                     exponent_bits=fi.nexp,
                                     mantissa_bits=fi.nmant)
    return x.astype(dtype)


def two_stage_rows(rows: list, st: MLLState, *, hub: bool) -> jnp.ndarray:
    """`subnet_average_two_stage` (``hub=False``) or `hub_average_two_stage`
    of ONE leaf given as its W worker rows, returned stacked.

    The same products and adds, in the same order, as the composed form:
    the subnet mean sums in the accumulator `jnp.sum` uses (float32 for
    16-bit floats) and rounds once, the hub mix adds in ascending roll
    order; the input rows and the hub models are rounded where the
    composed form writes them (`_round_to`).  But every output row is an
    elementwise expression of static worker rows: no reduce over the
    worker axis, no roll, no broadcast back.  XLA can then fuse whatever
    produced the rows (the gated update) into the one loop that writes
    the mixed leaf."""
    d, nd = _grouped_dims(st)
    dt = rows[0].dtype
    acc = jnp.float32 if dt in (jnp.bfloat16, jnp.float16) else dt
    v = st.v_weights.astype(dt)
    x = [_round_to(r, dt) for r in rows]
    z = [_round_to(_chain_sum([v[i] * x[i]
                               for i in range(k * nd, (k + 1) * nd)], acc),
                   dt) for k in range(d)]                    # hub models
    if hub:                     # `_roll_mix` with H indexed statically
        h = st.h.astype(dt)
        z = [_chain_sum([h[(e + o) % d, e] * z[(e + o) % d]
                         for o in range(d)], dt) for e in range(d)]
    return jnp.stack([z[i // nd] for i in range(d * nd)])


def _grouped_spmd_z(x, st: MLLState, spmd: SpmdAxis, sps: int,
                    mix_dtype: str | None):
    """This shard's sub-network mean (no worker axis): local weighted
    partial products reduced over the shard's rows, then an intra-subnet
    grouped psum.  Bit-identical to `_product_mean` when each shard holds
    one worker (the add orders coincide); otherwise equal to reduction
    order."""
    d, _ = _grouped_dims(st)
    ps = spmd.per_shard
    xm = x.astype(mix_dtype) if mix_dtype else x
    vl = jax.lax.dynamic_slice_in_dim(
        st.v_weights.astype(xm.dtype), spmd.offset(), ps, 0)
    part = (vl.reshape((ps,) + (1,) * (x.ndim - 1)) * xm).sum(axis=0)
    if sps > 1:
        part = jax.lax.psum(part, spmd.name,
                            axis_index_groups=_subnet_groups(d, sps))
    return xm, part


def subnet_average_two_stage_spmd(local: PyTree, st: MLLState,
                                  spmd: SpmdAxis,
                                  mix_dtype: str | None = None) -> PyTree:
    """`subnet_average_two_stage` under shard_map: the block-diag subnet
    mean becomes an intra-subnet grouped psum (replica groups =
    `_subnet_groups`), broadcast back over this shard's worker rows."""
    sps = grouped_spmd_layout(st, spmd)
    if sps == 0:                    # whole worker axis on this shard
        return subnet_average_two_stage(local, st, mix_dtype)

    def mix(x):
        xm, z = _grouped_spmd_z(x, st, spmd, sps, mix_dtype)
        return jnp.broadcast_to(z[None], xm.shape).astype(x.dtype)
    return jax.tree.map(mix, local)


def _hub_spmd_rolls(local: PyTree, st: MLLState, spmd: SpmdAxis,
                    mix_dtype: str | None, terms) -> PyTree:
    """Shared hub-stage SPMD skeleton: subnet mean via grouped psum, then
    ``terms(z, roll)`` summed over the rolls the strategy emits — each roll
    one `ppermute` of the hub model along the subnet-sharded axis."""
    d, _ = _grouped_dims(st)
    sps = grouped_spmd_layout(st, spmd)
    assert sps > 0, "callers handle the single-shard case"

    def roll(z, o):
        if not o:
            return z
        perm = [((s + o * sps) % spmd.size, s) for s in range(spmd.size)]
        return jax.lax.ppermute(z, spmd.name, perm=perm)

    def mix(x):
        xm, z = _grouped_spmd_z(x, st, spmd, sps, mix_dtype)
        y = None
        for term in terms(xm.dtype, z, roll):
            y = term if y is None else y + term
        return jnp.broadcast_to(y[None], xm.shape).astype(x.dtype)
    return jax.tree.map(mix, local)


def hub_average_two_stage_spmd(local: PyTree, st: MLLState, spmd: SpmdAxis,
                               mix_dtype: str | None = None) -> PyTree:
    """`hub_average_two_stage` under shard_map: circulant-indexed rolls of
    the hub model via `ppermute`, each weighted by the RECEIVER's H column
    entry (general H) — add-for-add the `_roll_mix` accumulation."""
    d, _ = _grouped_dims(st)
    sps = grouped_spmd_layout(st, spmd)
    if sps == 0:
        return hub_average_two_stage(local, st, mix_dtype)
    e = np.arange(d)

    def terms(dtype, z, roll):
        h = st.h.astype(dtype)
        sub = jax.lax.axis_index(spmd.name) // sps     # this shard's subnet
        for o in range(d):
            yield jnp.take(h[(e + o) % d, e], sub) * roll(z, o)
    return _hub_spmd_rolls(local, st, spmd, mix_dtype, terms)


def hub_average_ppermute_spmd(local: PyTree, st: MLLState, spmd: SpmdAxis,
                              mix_dtype: str | None = None) -> PyTree:
    """`hub_average_ppermute` under shard_map: one `ppermute` per NONZERO
    circulant coefficient (wire traffic scales with hub-graph degree), the
    zero-coefficient rolls skipped exactly as in the vmap loop."""
    sps = grouped_spmd_layout(st, spmd)
    if sps == 0:
        return hub_average_ppermute(local, st, mix_dtype)
    coeffs = _circulant_coeffs(st)

    def terms(dtype, z, roll):
        for o, c in enumerate(coeffs):
            if abs(float(c)) < 1e-12:
                continue                     # non-neighbour: no traffic
            yield jnp.asarray(c, dtype) * roll(z, o)
    return _hub_spmd_rolls(local, st, spmd, mix_dtype, terms)


def _sym_quantize(x: jnp.ndarray, axes: tuple[int, ...],
                  levels: int) -> tuple:
    """Symmetric per-hub integer quantization: scale = max|x| / ``levels``
    over all dims except the leading hub dim, values clipped to
    [-levels, levels].  ``levels=127`` is the int8 wire, ``levels=7`` the
    int4 wire (stored int8 in simulation — jax carries no packed int4
    buffers — but only 4 bits of information survive, which is what the
    `wire_bytes` accounting charges)."""
    amax = jnp.max(jnp.abs(x.astype(jnp.float32)), axis=axes, keepdims=True)
    scale = jnp.maximum(amax, 1e-12) / float(levels)
    q = jnp.clip(jnp.round(x.astype(jnp.float32) / scale), -levels, levels
                 ).astype(jnp.int8)
    return q, scale


def _int8_quantize(x: jnp.ndarray, axes: tuple[int, ...]) -> tuple:
    """Symmetric per-hub int8 quantization: scale = max|x| / 127 over all
    dims except the leading hub dim."""
    return _sym_quantize(x, axes, 127)


def _circulant_coeffs(st: MLLState) -> np.ndarray:
    """H as circulant coefficients c_o with y_e = sum_o c_o z_{(e+o) mod D}.
    Valid when the hub graph + weights make H circulant (ring or complete
    with uniform hub weights) — checked here at trace time."""
    h = np.asarray(st.h)
    d = h.shape[0]
    c = h[:, 0]                                   # c_o = H[o, 0]
    want = np.empty_like(h)
    for e in range(d):
        for o in range(d):
            want[(e + o) % d, e] = c[o]
    if not np.allclose(want, h, atol=1e-9):
        raise ValueError("mixing='ppermute' needs a circulant H (ring or "
                         "complete hub graph with uniform hub weights)")
    return c


def hub_average_ppermute(stacked: PyTree, st: MLLState,
                         mix_dtype: str | None = None) -> PyTree:
    """Beyond-paper: circulant-H hub mixing as a sum of rolls along the
    (pod-sharded) hub axis.  Each nonzero coefficient lowers to a
    collective-permute of one hub model instead of the all-gather the dense
    D x D contraction needs — DCN bytes scale with the graph DEGREE, not D."""
    d, nd = _grouped_dims(st)
    v = st.v_weights.reshape(d, nd)
    coeffs = _circulant_coeffs(st)

    def mix(x):
        xm = x.astype(mix_dtype) if mix_dtype else x
        xg = xm.reshape((d, nd) + x.shape[1:])
        z = _product_mean(v.astype(xm.dtype), xg)
        y = None
        for o, c in enumerate(coeffs):
            if abs(float(c)) < 1e-12:
                continue                     # non-neighbour: no traffic
            zo = jnp.roll(z, -o, axis=0) if o else z
            term = jnp.asarray(c, zo.dtype) * zo
            y = term if y is None else y + term
        out = jnp.broadcast_to(y[:, None], xg.shape).reshape(x.shape)
        return out.astype(x.dtype)
    return jax.tree.map(mix, stacked)


def hub_average_int8(stacked: PyTree, st: MLLState) -> PyTree:
    """Beyond-paper: int8-quantized hub mixing over circulant H.

    The subnet average stays full precision (ICI is cheap); neighbour hub
    models cross the pod boundary as int8 + one f32 scale per hub model.
    Structured as coefficient-weighted ROLLS (like ppermute mixing) rather
    than an einsum: a contraction over the pod-sharded hub dim would make
    GSPMD all-reduce f32 partial sums — the rolls guarantee the wire
    carries the int8 buffers (collective-permute of int8), halving DCN
    bytes vs bf16.  Quantization error is symmetric per-tensor
    (<= scale/2 per element); the ``int8_ef`` strategy removes the residual
    bias with error feedback."""
    d, nd = _grouped_dims(st)
    v = st.v_weights.reshape(d, nd)
    coeffs = _circulant_coeffs(st)

    def mix(x):
        xg = x.astype(jnp.float32).reshape((d, nd) + x.shape[1:])
        z = jnp.einsum("dn,dn...->d...", v, xg)            # hub models (f32)
        q, scale = _int8_quantize(z, tuple(range(1, z.ndim)))
        y = None
        for o, c in enumerate(coeffs):
            if abs(float(c)) < 1e-12:
                continue
            if o:
                qo = jnp.roll(q, -o, axis=0)               # int8 on the wire
                so = jnp.roll(scale, -o, axis=0)
                term = float(c) * (qo.astype(jnp.float32) * so)
            else:
                term = float(c) * z                        # own model exact
            y = term if y is None else y + term
        out = jnp.broadcast_to(y[:, None], (d, nd) + x.shape[1:])
        return out.reshape(x.shape).astype(x.dtype)
    return jax.tree.map(mix, stacked)


def init_error_feedback(stacked_params: PyTree) -> PyTree:
    """Residual state for error-feedback int8 mixing (one buffer per worker,
    same layout/sharding as the params)."""
    return jax.tree.map(lambda x: jnp.zeros(x.shape, jnp.float32),
                        stacked_params)


def _split_pairs(pairs: PyTree) -> tuple[PyTree, PyTree]:
    """Split a tree of (a, b) leaf tuples into two trees."""
    first = jax.tree.map(lambda t: t[0], pairs,
                         is_leaf=lambda t: isinstance(t, tuple))
    second = jax.tree.map(lambda t: t[1], pairs,
                          is_leaf=lambda t: isinstance(t, tuple))
    return first, second


def hub_average_intq_ef(stacked: PyTree, ef: PyTree, st: MLLState, *,
                        levels: int = 127) -> tuple[PyTree, PyTree]:
    """Integer-quantized hub mixing WITH error feedback: the quantization
    residual of each hub round is added back before the next round's
    quantization, so the long-run averaging is unbiased (Karimireddy et al.
    2019 style).  ``levels=127`` is the int8 wire, ``levels=7`` the int4
    wire (int4 values + one f32 scale per hub model per leaf).

    Returns (mixed params, new residual state).  Wire format identical to
    `hub_average_int8` modulo the level count (integer rolls); only local
    state is added."""
    d, nd = _grouped_dims(st)
    v = st.v_weights.reshape(d, nd)
    coeffs = _circulant_coeffs(st)

    def mix(x, e):
        xg = x.astype(jnp.float32).reshape((d, nd) + x.shape[1:])
        eg = e.reshape((d, nd) + x.shape[1:])
        z = jnp.einsum("dn,dn...->d...", v, xg + eg)      # compensated avg
        q, scale = _sym_quantize(z, tuple(range(1, z.ndim)), levels)
        deq_own = q.astype(jnp.float32) * scale
        resid = z - deq_own                                # what the wire lost
        y = None
        for o, c in enumerate(coeffs):
            if abs(float(c)) < 1e-12:
                continue
            if o:
                qo = jnp.roll(q, -o, axis=0)               # ints on the wire
                so = jnp.roll(scale, -o, axis=0)
                term = float(c) * (qo.astype(jnp.float32) * so)
            else:
                term = float(c) * deq_own
            y = term if y is None else y + term
        out = jnp.broadcast_to(y[:, None], (d, nd) + x.shape[1:])
        # every worker carries the FULL hub residual: the next round's
        # v-weighted average (weights sum to 1 within a subnet) then returns
        # exactly `resid`, so compensation is complete — dividing by nd here
        # would feed back only 1/nd of the error per round
        new_e = jnp.broadcast_to(resid[:, None], (d, nd) + x.shape[1:])
        return (out.reshape(x.shape).astype(x.dtype),
                new_e.reshape(x.shape).astype(jnp.float32))

    return _split_pairs(jax.tree.map(mix, stacked, ef))


def hub_average_int8_ef(stacked: PyTree, ef: PyTree, st: MLLState,
                        ) -> tuple[PyTree, PyTree]:
    """`hub_average_intq_ef` at the int8 wire (levels=127)."""
    return hub_average_intq_ef(stacked, ef, st, levels=127)


def hub_average_bf16(stacked: PyTree, st: MLLState) -> PyTree:
    """bf16-wire hub mixing: the subnet average stays full precision (ICI
    is cheap), neighbour hub models cross the pod boundary as bf16 —
    halving DCN bytes vs f32 with no extra state.

    Structured as receiver-weighted ROLLS of the bf16 wire buffer (general
    H, like `hub_average_two_stage`); the o=0 term keeps the hub's OWN
    model in f32 (it never touches the wire), rolled terms dequantize
    bf16 -> f32 before the weighted accumulation.  Term-for-term the
    arithmetic of `hub_average_bf16_spmd`, whose `ppermute` carries the
    bf16 buffers."""
    d, nd = _grouped_dims(st)
    v = st.v_weights.reshape(d, nd)
    e = np.arange(d)

    def mix(x):
        xg = x.astype(jnp.float32).reshape((d, nd) + x.shape[1:])
        z = _product_mean(v, xg)
        wire = z.astype(jnp.bfloat16)                      # the wire buffer
        h = st.h.astype(jnp.float32)
        y = None
        for o in range(d):
            w = h[(e + o) % d, e].reshape((d,) + (1,) * (z.ndim - 1))
            zo = z if o == 0 else jnp.roll(wire, -o, axis=0
                                           ).astype(jnp.float32)
            term = w * zo
            y = term if y is None else y + term
        out = jnp.broadcast_to(y[:, None], xg.shape).reshape(x.shape)
        return out.astype(x.dtype)
    return jax.tree.map(mix, stacked)


def hub_average_bf16_spmd(local: PyTree, st: MLLState,
                          spmd: SpmdAxis) -> PyTree:
    """`hub_average_bf16` under shard_map: the `ppermute` rolls carry the
    BF16 wire buffers (the collective moves 2 bytes/element), dequantized
    to f32 on arrival — add-for-add the vmap accumulation (which groups in
    f32 regardless of the param dtype, hence mix_dtype="float32" here)."""
    sps = grouped_spmd_layout(st, spmd)
    if sps == 0:
        return hub_average_bf16(local, st)
    d, _ = _grouped_dims(st)
    e = np.arange(d)

    def terms(dtype, z, roll):
        wire = z.astype(jnp.bfloat16)
        h = st.h.astype(jnp.float32)
        sub = jax.lax.axis_index(spmd.name) // sps     # this shard's subnet
        for o in range(d):
            c = jnp.take(h[(e + o) % d, e], sub)
            yield c * (z if o == 0
                       else roll(wire, o).astype(jnp.float32))
    return _hub_spmd_rolls(local, st, spmd, "float32", terms)


def _topk_count(cols: int, ratio: float) -> int:
    """Entries kept per hub model for a leaf with ``cols`` elements."""
    return max(1, min(cols, int(-(-cols * ratio // 1))))


def _topk_sparsify(z: jnp.ndarray, k: int) -> jnp.ndarray:
    """Dense copy of (D, ...) hub models keeping only each model's k
    largest-|.| entries (the wire carries k (value, index) pairs)."""
    d = z.shape[0]
    flat = z.reshape(d, -1)
    _, idx = jax.lax.top_k(jnp.abs(flat), k)
    picked = jnp.take_along_axis(flat, idx, axis=1)
    rows = jnp.arange(d)[:, None]
    return jnp.zeros_like(flat).at[rows, idx].set(picked).reshape(z.shape)


def hub_average_topk_ef(stacked: PyTree, ef: PyTree, st: MLLState, *,
                        ratio: float, momentum: float,
                        ) -> tuple[PyTree, PyTree]:
    """Top-k sparsified hub mixing with momentum error feedback: each hub
    model crosses the wire as its k = ceil(ratio * size) largest-magnitude
    entries per leaf ((value, index) pairs); the dropped mass decays into
    the residual buffer with factor ``momentum`` and is compensated into
    the next round's input.  General H (the dequantized sparse models mix
    through `_roll_mix`)."""
    d, nd = _grouped_dims(st)
    v = st.v_weights.reshape(d, nd)

    def mix(x, e):
        xg = x.astype(jnp.float32).reshape((d, nd) + x.shape[1:])
        eg = e.reshape((d, nd) + x.shape[1:])
        u = jnp.einsum("dn,dn...->d...", v, xg + eg)      # compensated avg
        cols = 1
        for dim in x.shape[1:]:
            cols *= dim
        s = _topk_sparsify(u, _topk_count(cols, ratio))
        resid = u - s                                      # dropped mass
        y = _roll_mix(st.h.astype(jnp.float32), s)
        out = jnp.broadcast_to(y[:, None], (d, nd) + x.shape[1:])
        new_e = jnp.broadcast_to((momentum * resid)[:, None],
                                 (d, nd) + x.shape[1:])
        return (out.reshape(x.shape).astype(x.dtype),
                new_e.reshape(x.shape).astype(jnp.float32))

    return _split_pairs(jax.tree.map(mix, stacked, ef))


def _powersgd_approx(m: jnp.ndarray, q: jnp.ndarray) -> tuple:
    """One warm-started PowerSGD iteration per hub model.

    ``m`` (D, n, c) matrices, ``q`` (D, c, r) warm-started right factors.
    P = M Q orthonormalized (batched reduced QR), Q' = M^T P, and the
    rank-r reconstruction is P Q'^T = P P^T M — the projection of M's
    columns onto span(P), exact whenever rank(M) <= r (Vogels et al. 2019).
    Returns (approx (D, n, c), Q' (D, c, r))."""
    p = jnp.einsum("dnc,dcr->dnr", m, q)
    p, _ = jnp.linalg.qr(p)                           # orthonormal columns
    q_new = jnp.einsum("dnc,dnr->dcr", m, p)
    return jnp.einsum("dnr,dcr->dnc", p, q_new), q_new


def init_powersgd_state(stacked_params: PyTree, rank: int) -> dict:
    """PowerSGD mixing state: EF residuals + warm-started right factors.

    Matrix leaves (per-worker ndim >= 2, flattened to (n, c)) get a
    per-worker (c, r_eff) Gaussian Q with r_eff = min(rank, n, c),
    deterministic per leaf position; vector/scalar leaves cross the wire
    uncompressed and carry an empty (W, 0) placeholder so the state tree
    keeps one leaf per param leaf (lax.switch needs a fixed structure)."""
    ef = init_error_feedback(stacked_params)
    leaves, treedef = jax.tree.flatten(stacked_params)
    qs = []
    for i, x in enumerate(leaves):
        w = x.shape[0]
        if x.ndim >= 3:
            n = x.shape[1]
            c = 1
            for dim in x.shape[2:]:
                c *= dim
            r = min(rank, n, c)
            qi = jax.random.normal(jax.random.PRNGKey(i), (c, r), jnp.float32)
            qs.append(jnp.broadcast_to(qi[None], (w, c, r)))
        else:
            qs.append(jnp.zeros((w, 0), jnp.float32))
    return {"ef": ef, "q": jax.tree.unflatten(treedef, qs)}


def hub_average_powersgd(stacked: PyTree, ef: PyTree, q: PyTree,
                         st: MLLState) -> tuple[PyTree, PyTree, PyTree]:
    """Low-rank hub mixing with warm-started PowerSGD factors and error
    feedback (Vogels et al. 2019 adapted to model mixing): each hub's
    compensated model crosses the wire as rank-r factors P (n x r) and
    Q (c x r) per matrix leaf; the low-rank residual feeds back next round
    and Q' warm-starts the next power iteration.  Vector/scalar leaves are
    sent exact (their EF residual stays zero).  General H via `_roll_mix`.

    Returns (mixed params, new EF residuals, new Q factors)."""
    d, nd = _grouped_dims(st)
    v = st.v_weights.reshape(d, nd)

    def mix(x, e, qv):
        xg = x.astype(jnp.float32).reshape((d, nd) + x.shape[1:])
        eg = e.reshape((d, nd) + x.shape[1:])
        u = jnp.einsum("dn,dn...->d...", v, xg + eg)      # compensated avg
        if x.ndim >= 3 and qv.size:
            n = x.shape[1]
            c = qv.shape[1]
            m = u.reshape(d, n, c)
            qh = qv.reshape((d, nd) + qv.shape[1:])[:, 0]  # (d, c, r)
            approx, q_new = _powersgd_approx(m, qh)
            s = approx.reshape(u.shape)
            resid = u - s                                  # low-rank error
            new_q = jnp.broadcast_to(
                q_new[:, None], (d, nd) + q_new.shape[1:]).reshape(qv.shape)
        else:
            s, resid, new_q = u, jnp.zeros_like(u), qv     # exact wire
        y = _roll_mix(st.h.astype(jnp.float32), s)
        out = jnp.broadcast_to(y[:, None], (d, nd) + x.shape[1:])
        new_e = jnp.broadcast_to(resid[:, None], (d, nd) + x.shape[1:])
        return (out.reshape(x.shape).astype(x.dtype),
                new_e.reshape(x.shape).astype(jnp.float32),
                new_q.astype(jnp.float32))

    trip = jax.tree.map(mix, stacked, ef, q)
    is_leaf = lambda t: isinstance(t, tuple)   # noqa: E731
    return (jax.tree.map(lambda t: t[0], trip, is_leaf=is_leaf),
            jax.tree.map(lambda t: t[1], trip, is_leaf=is_leaf),
            jax.tree.map(lambda t: t[2], trip, is_leaf=is_leaf))


def _hub_edges(st: MLLState) -> int:
    """Directed hub-graph edges that carry wire traffic: nonzero
    off-diagonal entries of H (a hub's own model never leaves the pod)."""
    h = np.abs(np.asarray(st.h)) > 1e-12
    return int(h.sum() - np.diag(h).sum())


# ------------------------------------------------------------------- registry
class MixingStrategy:
    """How subnet (V) and hub (Z) averaging rounds are realised.

    Stateless strategies implement ``subnet(stacked, st)`` and
    ``hub(stacked, st)``.  Stateful strategies (error feedback, ...) also
    override ``init_state`` and the ``*_with_state`` variants — the engine
    always calls the ``*_with_state`` forms so state threads uniformly
    through ``lax.switch``.
    """
    name: str = "?"
    # strategies with a collective lowering (the ``*_spmd`` methods) set
    # this True; the SPMD harness refuses meshes for the rest up front
    spmd_capable: bool = False
    # one-line wire-format description (``--mixing list`` / mixing_zoo)
    wire_format: str = "f32 hub models (4 B/elem; mix_dtype overrides)"

    def __init__(self, mix_dtype: str | None = None):
        self.mix_dtype = mix_dtype

    # ---- wire accounting (benchmarks plot bytes-on-wire per strategy)
    def hub_payload_bytes(self, st: MLLState, spec) -> int:
        """Bytes ONE hub model costs on the wire under this strategy's
        format, for a stacked tree laid out by ``spec`` (a
        `packing.PackSpec`).  Default: every element at mix dtype."""
        dt = jnp.dtype(self.mix_dtype) if self.mix_dtype else jnp.dtype(
            jnp.float32)
        return int(dt.itemsize) * spec.total_cols

    def wire_bytes(self, st: MLLState, spec) -> int:
        """Hub-boundary (DCN) bytes for ONE hub averaging round: one
        `hub_payload_bytes` payload per directed hub edge (`_hub_edges`).
        Subnet rounds ride intra-pod ICI and are deliberately not counted —
        the ladder compresses the scarce hub hop, matching the paper's
        premise that hub exchange dominates."""
        return _hub_edges(st) * self.hub_payload_bytes(st, spec)

    # ---- stateless interface
    def subnet(self, stacked: PyTree, st: MLLState) -> PyTree:
        raise NotImplementedError

    def hub(self, stacked: PyTree, st: MLLState) -> PyTree:
        raise NotImplementedError

    # ---- state threading (override for stateful strategies)
    def init_state(self, stacked_params: PyTree) -> PyTree:
        return ()

    def subnet_with_state(self, stacked: PyTree, st: MLLState,
                          state: PyTree) -> tuple[PyTree, PyTree]:
        return self.subnet(stacked, st), state

    def hub_with_state(self, stacked: PyTree, st: MLLState,
                       state: PyTree) -> tuple[PyTree, PyTree]:
        return self.hub(stacked, st), state

    # ---- row form (single-device event slots, `mll_harness_step`)
    def has_rows(self) -> bool:
        """Whether `mix_rows` computes this strategy's events bit for bit."""
        return False

    def mix_rows(self, rows: list[PyTree], st: MLLState, *,
                 hub: bool) -> PyTree:
        """The stateless subnet (``hub=False``) or hub event over the W
        per-worker trees ``rows``, returned as one stacked tree."""
        raise NotImplementedError

    # ---- SPMD (shard_map) lowering: inputs/outputs are this shard's
    # (W/size, ...) worker rows; collectives run over ``spmd.name``
    def validate_spmd(self, st: MLLState, spmd: SpmdAxis) -> None:
        """Raise (at harness build time, before any tracing) when this
        strategy cannot lower the given mesh layout to collectives."""
        if not self.spmd_capable:
            raise ValueError(
                f"mixing={self.name!r} has no SPMD collective lowering; "
                f"strategies that run on a mesh: {spmd_capable_mixing()}")

    def subnet_spmd(self, local: PyTree, st: MLLState,
                    spmd: SpmdAxis) -> PyTree:
        raise NotImplementedError(
            f"mixing={self.name!r} has no SPMD subnet lowering")

    def hub_spmd(self, local: PyTree, st: MLLState,
                 spmd: SpmdAxis) -> PyTree:
        raise NotImplementedError(
            f"mixing={self.name!r} has no SPMD hub lowering")

    def subnet_spmd_with_state(self, local: PyTree, st: MLLState,
                               state: PyTree, spmd: SpmdAxis,
                               ) -> tuple[PyTree, PyTree]:
        return self.subnet_spmd(local, st, spmd), state

    def hub_spmd_with_state(self, local: PyTree, st: MLLState,
                            state: PyTree, spmd: SpmdAxis,
                            ) -> tuple[PyTree, PyTree]:
        return self.hub_spmd(local, st, spmd), state


MIXING_REGISTRY: dict[str, type[MixingStrategy]] = {}


def register(name: str) -> Callable[[type[MixingStrategy]], type[MixingStrategy]]:
    """Class decorator: make a MixingStrategy reachable as MLLConfig(mixing=name)."""
    def deco(cls: type[MixingStrategy]) -> type[MixingStrategy]:
        cls.name = name
        MIXING_REGISTRY[name] = cls
        return cls
    return deco


def get_mixing(name: str, mix_dtype: str | None = None) -> MixingStrategy:
    try:
        cls = MIXING_REGISTRY[name]
    except KeyError:
        raise ValueError(f"unknown mixing {name!r}; registered strategies: "
                         f"{available_mixing()}") from None
    return cls(mix_dtype)


def available_mixing() -> tuple[str, ...]:
    return tuple(sorted(MIXING_REGISTRY))


def describe_mixing() -> str:
    """One line per registered strategy: name, SPMD capability, wire format.

    The text behind ``--mixing list`` on the launchers and the mixing-zoo
    example — the human-readable face of the compression ladder."""
    width = max(len(n) for n in MIXING_REGISTRY)
    lines = []
    for name in available_mixing():
        cls = MIXING_REGISTRY[name]
        spmd = "mesh" if cls.spmd_capable else "vmap"
        lines.append(f"  {name:<{width}}  [{spmd}]  {cls.wire_format}")
    return "registered mixing strategies (wire format on hub edges):\n" + \
        "\n".join(lines)


@register("dense")
class DenseMixing(MixingStrategy):
    """The paper's matrices verbatim: X V and X Z as W x W einsums.  Works
    for unequal-size sub-networks; GSPMD lowers the worker-axis contraction
    to data/pod collectives.  The explicit SPMD lowering is
    gather+contract: all-gather the worker axis, einsum into this shard's
    output rows only (bit-identical — same contraction per output row)."""
    spmd_capable = True
    wire_format = "f32 W x W contraction; full-precision models on every edge"

    def subnet(self, stacked, st):
        return subnet_average_dense(stacked, st, self.mix_dtype)

    def hub(self, stacked, st):
        return hub_average_dense(stacked, st, self.mix_dtype)

    def subnet_spmd(self, local, st, spmd):
        return _einsum_operator_spmd(st.v_op, local, self.mix_dtype, spmd)

    def hub_spmd(self, local, st, spmd):
        return _einsum_operator_spmd(st.z_op, local, self.mix_dtype, spmd)


@register("two_stage")
class TwoStageMixing(MixingStrategy):
    """Structured V/Z: within-pod replica-group all-reduce + small D x D
    hub mix instead of one dense W x W contraction.  SPMD lowering: the
    subnet mean is an intra-subnet grouped `psum`, the hub stage
    receiver-weighted `ppermute` rolls."""
    spmd_capable = True
    wire_format = "f32 hub models as rolls (4 B/elem; mix_dtype overrides)"

    def subnet(self, stacked, st):
        return subnet_average_two_stage(stacked, st, self.mix_dtype)

    def hub(self, stacked, st):
        return hub_average_two_stage(stacked, st, self.mix_dtype)

    def has_rows(self):
        # a subclass that replaces either event or adds state (ppermute and
        # the whole compression ladder do) keeps the composed form; so does
        # a mix_dtype, whose casts XLA may elide differently in each form
        cls = type(self)
        return self.mix_dtype is None and all(
            getattr(cls, n) is getattr(TwoStageMixing, n)
            for n in ("subnet", "hub", "init_state", "subnet_with_state",
                      "hub_with_state"))

    def mix_rows(self, rows, st, *, hub):
        return jax.tree.map(
            lambda *r: two_stage_rows(list(r), st, hub=hub), *rows)

    def validate_spmd(self, st, spmd):
        super().validate_spmd(st, spmd)
        grouped_spmd_layout(st, spmd)      # raises on misaligned shards

    def subnet_spmd(self, local, st, spmd):
        return subnet_average_two_stage_spmd(local, st, spmd, self.mix_dtype)

    def hub_spmd(self, local, st, spmd):
        return hub_average_two_stage_spmd(local, st, spmd, self.mix_dtype)


@register("ppermute")
class PPermuteMixing(TwoStageMixing):
    """Circulant-H hub mixing as coefficient-weighted rolls: DCN bytes scale
    with hub-graph degree, not D.  Subnet rounds stay two-stage.  SPMD
    lowering: one `ppermute` per nonzero circulant coefficient."""
    wire_format = "f32 hub models, one permute per nonzero circulant coeff"

    def hub(self, stacked, st):
        return hub_average_ppermute(stacked, st, self.mix_dtype)

    def validate_spmd(self, st, spmd):
        super().validate_spmd(st, spmd)
        _circulant_coeffs(st)              # raises on non-circulant H

    def hub_spmd(self, local, st, spmd):
        return hub_average_ppermute_spmd(local, st, spmd, self.mix_dtype)


@register("int8")
class Int8Mixing(TwoStageMixing):
    """ppermute wire format with int8-quantized hub models (biased).

    ``mix_dtype`` applies to the SUBNET rounds only (inherited two_stage);
    the hub wire format is int8 + f32 scales by definition.  NOT
    spmd-capable (despite inheriting TwoStageMixing): the int8 wire needs
    a typed collective path so the permute carries int8 buffers, not the
    f32 rolls the inherited lowering would silently emit."""
    spmd_capable = False
    wire_format = "int8 values + one f32 scale per hub model per leaf (biased)"

    def hub(self, stacked, st):
        return hub_average_int8(stacked, st)

    def subnet_spmd(self, local, st, spmd):
        raise NotImplementedError(
            f"mixing={self.name!r} has no SPMD lowering (compressed wire "
            f"format needs typed collectives); strategies that run on a "
            f"mesh: {spmd_capable_mixing()}")

    hub_spmd = subnet_spmd

    def hub_payload_bytes(self, st, spec):
        return sum(s.size + 4 for s in spec.slots)


@register("int8_ef")
class Int8EFMixing(Int8Mixing):
    """int8 hub mixing + error feedback: per-worker f32 residual buffers
    make the long-run averaging unbiased.  Stateful — the engine carries the
    residuals next to the params (same worker layout/sharding).  As with
    ``int8``, ``mix_dtype`` affects subnet rounds only — and as with
    ``int8``, NOT spmd-capable until the wire carries typed int8
    collectives."""
    spmd_capable = False
    levels = 127               # quantization levels of the integer wire
    wire_format = "int8 values + f32 scales, error-feedback residuals"

    def init_state(self, stacked_params):
        return init_error_feedback(stacked_params)

    def hub(self, stacked, st):
        out, _ = hub_average_intq_ef(stacked, init_error_feedback(stacked),
                                     st, levels=self.levels)
        return out

    def hub_with_state(self, stacked, st, state):
        if isinstance(state, tuple) and not state:   # caller without state
            state = init_error_feedback(stacked)
        return hub_average_intq_ef(stacked, state, st, levels=self.levels)


@register("int4_ef")
class Int4EFMixing(Int8EFMixing):
    """int4 hub wire (2 elements/byte + one f32 scale per hub model per
    leaf) with the same error-feedback compensation as ``int8_ef``: the
    coarser 15-level grid loses more per round, EF returns it next round.
    Simulation carries the 4-bit values in int8 buffers (jax has no packed
    int4 arrays); `hub_payload_bytes` charges the 4 bits that matter."""
    levels = 7
    wire_format = "int4 values (2 elem/byte) + f32 scales, EF residuals"

    def hub_payload_bytes(self, st, spec):
        return sum((s.size + 1) // 2 + 4 for s in spec.slots)


@register("bf16")
class Bf16Mixing(TwoStageMixing):
    """bf16 hub wire: neighbour hub models cross the pod boundary as bf16
    (half the DCN bytes of f32), dequantized on arrival; the receiver's OWN
    hub model stays f32.  Stateless and unbiased enough in practice that no
    EF buffer is carried (bf16 keeps f32's exponent range; the mantissa
    truncation is ~3 decimal digits).  First compressed rung WITH a real
    SPMD lowering: the `ppermute` rolls carry the bf16 wire buffers."""
    spmd_capable = True
    wire_format = "bf16 hub models (2 B/elem), stateless"

    def hub(self, stacked, st):
        return hub_average_bf16(stacked, st)

    def hub_spmd(self, local, st, spmd):
        return hub_average_bf16_spmd(local, st, spmd)

    def hub_payload_bytes(self, st, spec):
        return 2 * spec.total_cols


@register("topk_ef")
class TopKEFMixing(Int8Mixing):
    """Top-k sparsified hub wire with momentum error feedback: each hub
    model crosses as its k = ceil(size / 32) largest-|.| entries per leaf,
    sent as (f32 value, i32 index) pairs; dropped mass decays into the
    residual with factor ``ef_momentum`` and compensates the next round."""
    spmd_capable = False
    k_ratio = 1 / 32           # fraction of entries kept per leaf
    ef_momentum = 0.9          # residual decay (plain EF would be 1.0)
    wire_format = "top-k (f32 value, i32 index) pairs, momentum EF residuals"

    def init_state(self, stacked_params):
        return init_error_feedback(stacked_params)

    def hub(self, stacked, st):
        out, _ = hub_average_topk_ef(stacked, init_error_feedback(stacked),
                                     st, ratio=self.k_ratio,
                                     momentum=self.ef_momentum)
        return out

    def hub_with_state(self, stacked, st, state):
        if isinstance(state, tuple) and not state:   # caller without state
            state = init_error_feedback(stacked)
        return hub_average_topk_ef(stacked, state, st, ratio=self.k_ratio,
                                   momentum=self.ef_momentum)

    def hub_payload_bytes(self, st, spec):
        return sum(8 * _topk_count(s.size, self.k_ratio) for s in spec.slots)


@register("powersgd")
class PowerSGDMixing(Int8Mixing):
    """Low-rank hub wire: rank-r PowerSGD factors (P n x r, Q c x r, both
    f32) per matrix leaf, warm-started Q + EF residual; vector/scalar
    leaves sent exact.  State is {"ef": residual tree, "q": factor tree}."""
    spmd_capable = False
    rank = 2                   # target rank (clamped to min(n, c) per leaf)
    wire_format = "rank-r PowerSGD factors per matrix leaf, EF residuals"

    def init_state(self, stacked_params):
        return init_powersgd_state(stacked_params, self.rank)

    def hub(self, stacked, st):
        out, _ = self.hub_with_state(stacked, st, ())
        return out

    def hub_with_state(self, stacked, st, state):
        if isinstance(state, tuple) and not state:   # caller without state
            state = init_powersgd_state(stacked, self.rank)
        params, ef, q = hub_average_powersgd(stacked, state["ef"],
                                             state["q"], st)
        return params, {"ef": ef, "q": q}

    def hub_payload_bytes(self, st, spec):
        total = 0
        for s in spec.slots:
            if len(s.shape) >= 3:          # (W, n, ...) matrix leaf
                n = s.shape[1]
                c = s.size // n
                total += 4 * min(self.rank, n, c) * (n + c)
            else:
                total += 4 * s.size        # exact wire
        return total


# ------------------------------------------------------------ engine: mixing
def schedule_mix(strategy: MixingStrategy, stacked: PyTree, mix_state: PyTree,
                 step: jnp.ndarray, st: MLLState, tau: int, q: int, *,
                 static_phase: int | None = None) -> tuple[PyTree, PyTree]:
    """Apply T_k for this step via lax.switch (all branches lowered -> the
    dry-run HLO exposes every collective the protocol ever issues).  Returns
    (mixed params, new mixing state).

    An empty-tuple ``mix_state`` (the stateless placeholder) is normalized
    through ``strategy.init_state`` first, so every lax.switch branch
    returns the same state structure even for stateful strategies."""
    if isinstance(mix_state, tuple) and not mix_state:
        mix_state = strategy.init_state(stacked)
    branches = [
        lambda p, s: (p, s),
        lambda p, s: strategy.subnet_with_state(p, st, s),
        lambda p, s: strategy.hub_with_state(p, st, s),
    ]
    if static_phase is not None:
        # trace-time pinned branch: the dry-run lowers each phase separately
        # so the roofline analysis gets exact per-phase costs
        return branches[static_phase](stacked, mix_state)
    ph = phase_of(step, tau, q)
    return jax.lax.switch(ph, branches, stacked, mix_state)


# --------------------------------------------------- engine: gated inner opt
def init_gated_opt_state(optimizer: optim_mod.Optimizer,
                         stacked_params: PyTree) -> PyTree:
    """Inner-optimizer state wrapped with engine-owned per-worker step
    counts: ``{"inner": optimizer state, "counts": (W,) int32}``.  The
    counts feed the optimizer's ``step`` argument, so schedules like the
    adamw bias correction advance per ACTUAL update, not per global tick."""
    w = jax.tree.leaves(stacked_params)[0].shape[0]
    return {"inner": optimizer.init(stacked_params),
            "counts": jnp.zeros((w,), jnp.int32)}


def gated_inner_update(optimizer: optim_mod.Optimizer, stacked: PyTree,
                       opt_state: PyTree, grads: PyTree, theta: jnp.ndarray,
                       ) -> tuple[PyTree, PyTree]:
    """Bernoulli-gated inner-optimizer step on the worker axis (Eq. 2/3
    generalised): a gated-off worker keeps params, optimizer state AND its
    step count frozen — exactly as if it never computed the gradient.
    ``opt_state`` comes from `init_gated_opt_state`."""
    gate = theta != 0
    counts = opt_state["counts"] + gate.astype(jnp.int32)
    new_p, new_inner = optimizer.update(grads, opt_state["inner"], stacked,
                                        counts)

    def sel(new, old):
        g = gate.reshape(gate.shape + (1,) * (new.ndim - 1))
        return jnp.where(g, new, old.astype(new.dtype))

    params = jax.tree.map(sel, new_p, stacked)
    inner = jax.tree.map(sel, new_inner, opt_state["inner"])
    return params, {"inner": inner, "counts": counts}


def gated_update_rows(optimizer: optim_mod.Optimizer, stacked: PyTree,
                      opt_state: PyTree, grads: PyTree, theta: jnp.ndarray,
                      ) -> tuple[list[PyTree], PyTree]:
    """`gated_inner_update` worker by worker: returns the W updated
    per-worker trees (static slices of the worker axis) instead of one
    stacked tree, so a `MixingStrategy.mix_rows` event can consume each
    row where it is computed.  Same bits as `gated_inner_update`.

    Only for optimizers whose state holds no arrays (``sgd``): a stateful
    optimizer (momentum, adamw) would have to slice and restack its state
    as well, and keeps the composed form."""
    if jax.tree.leaves(opt_state["inner"]):
        raise ValueError("gated_update_rows needs an optimizer whose state "
                         "holds no arrays; use gated_inner_update")
    gate = theta != 0
    counts = opt_state["counts"] + gate.astype(jnp.int32)
    rows = []
    for i in range(theta.shape[0]):
        old = jax.tree.map(lambda x: x[i], stacked)
        new, _ = optimizer.update(jax.tree.map(lambda g: g[i], grads),
                                  opt_state["inner"], old, counts[i])
        rows.append(jax.tree.map(
            lambda n, o: jnp.where(gate[i], n, o.astype(n.dtype)), new, old))
    return rows, {"inner": opt_state["inner"], "counts": counts}


def resolve_inner_optimizer(cfg) -> optim_mod.Optimizer:
    """Inner optimizer from any config carrying (inner_opt, inner_opt_args, eta)."""
    name = getattr(cfg, "inner_opt", "sgd")
    args = dict(getattr(cfg, "inner_opt_args", ()) or ())
    return optim_mod.get(name, cfg.eta, **args)


def resolve_mixing(cfg) -> MixingStrategy:
    """Mixing strategy from any config carrying (mixing, mix_dtype)."""
    return get_mixing(cfg.mixing, getattr(cfg, "mix_dtype", None))


# --------------------------------------------------------- engine: full step
class MLLTrainState(NamedTuple):
    """Everything a protocol run carries between ticks, worker axis leading.

    ``step`` counts completed ticks (0-based; tick k+1 is the paper's
    1-based step), so ``phase_of(state.step)`` after a step tells which
    operator was just applied."""
    params: PyTree       # stacked params, leading worker axis on every leaf
    opt_state: PyTree    # gated inner-opt state: {"inner": ..., "counts": (W,)}
    mix_state: PyTree    # per-strategy mixing state (() when stateless)
    step: jnp.ndarray    # scalar int32: completed ticks


def init_train_state(stacked_params: PyTree,
                     optimizer: optim_mod.Optimizer | None = None,
                     strategy: MixingStrategy | None = None, *,
                     cfg=None) -> MLLTrainState:
    """Fresh protocol state.  Pass (optimizer, strategy) explicitly or a
    config (MLLConfig-like) to resolve them from."""
    if optimizer is None:
        optimizer = resolve_inner_optimizer(cfg)
    if strategy is None:
        strategy = resolve_mixing(cfg)
    return MLLTrainState(
        params=stacked_params,
        opt_state=init_gated_opt_state(optimizer, stacked_params),
        mix_state=strategy.init_state(stacked_params),
        step=jnp.zeros((), jnp.int32),
    )


def protocol_step(state: MLLTrainState, grads: PyTree, cfg, st: MLLState, *,
                  optimizer: optim_mod.Optimizer | None = None,
                  strategy: MixingStrategy | None = None,
                  static_phase: int | None = None) -> MLLTrainState:
    """One full protocol tick: gate, inner-optimizer update, scheduled mixing.

    `grads` are per-worker minibatch gradients with the worker axis leading
    on every leaf.  With ``sgd`` + a stateless strategy this reduces
    bit-for-bit to the legacy ``mll_train_step``.
    """
    if optimizer is None:
        optimizer = resolve_inner_optimizer(cfg)
    if strategy is None:
        strategy = resolve_mixing(cfg)
    step = state.step.astype(jnp.int32) + 1
    theta = gate_sample(cfg.seed, step, st.rates)
    params, opt_state = gated_inner_update(optimizer, state.params,
                                           state.opt_state, grads, theta)
    params, mix_state = schedule_mix(strategy, params, state.mix_state, step,
                                     st, cfg.tau, cfg.q,
                                     static_phase=static_phase)
    return MLLTrainState(params, opt_state, mix_state, step)
