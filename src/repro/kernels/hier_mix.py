"""Fused hierarchical mixing kernel (Pallas, TPU target).

The tau-step hot loop of MLL-SGD applies, per parameter leaf,

    out[j] = sum_i T[i, j] * (x[i] - eta * theta[i] * g[i])        (Eq. 2-6)

i.e. a gated SGD update immediately followed by the averaging operator
T_k in {I, V, Z}.  Unfused this costs three HBM round-trips over the full
parameter set (update write, mix read, mix write); fused it is one read of
x/g and one write of out per chunk — the operation is purely
bandwidth-bound, so the fusion is worth ~1.5x on the memory roofline term of
every averaging step.  It also serves the *simulator* (many workers per
device) where the W x W operator contraction runs on the MXU.

Two launch granularities:

  * **Per leaf** (`hier_mix_chunks` / `hier_mix_tree`, the original path):
    one `pallas_call` per pytree leaf.  Every launch re-fetches the (W, W)
    operator and theta, and every tiny bias leaf is tile-padded to a full
    (sublane, 128) block on its own.
  * **Packed single launch** (`hier_mix_packed`): the whole stacked pytree
    is flattened into ONE (W, sum C_i) float32 buffer under the packing
    contract of `repro.core.packing` (leaf i owns columns
    [offset_i, offset_i + size_i), `jax.tree.leaves` order, f32 storage),
    and a single `pallas_call` runs a chunk grid over the packed columns —
    the operator and theta are read once per launch, bias leaves share
    blocks with their neighbours, and the whole tree costs exactly one
    Pallas lowering per (W, treedef).  Packed and per-leaf execution agree
    bit for bit: both accumulate in f32 and round once to the leaf dtype on
    the way out, and tile padding is zeros that contribute nothing to the
    contraction.

Operators: the packed kernel takes either a dense (W, W) matrix (the
paper's V/Z verbatim) or a `GroupedOperator` fusing the STRUCTURED
strategies (`mixing="two_stage"` / `"ppermute"`): the block-diagonal
subnet mean runs as a skinny (D, W) scatter matmul + (W, D) broadcast
matmul (2*W*D*C flops instead of the dense 2*W*W*C), and the circulant /
two-stage hub mix inserts the small (D, D) hub contraction between them —
the whole subnet-mean -> hub-mix -> broadcast chain fused into the same
single launch as the gated SGD update.

Tiling: the lane (chunk) dim is padded to 128-lane multiples, sublane dims
(W, D) to the dtype's minimum sublane count; zero padding is exact (padded
workers carry x = g = theta = 0 and zero operator rows/columns).
"""
from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.packing import chunk_views, pack, pack_spec, unpack


def _kernel(x_ref, g_ref, t_ref, theta_ref, o_ref, *, eta: float):
    x = x_ref[...].astype(jnp.float32)
    g = g_ref[...].astype(jnp.float32)
    theta = theta_ref[...].astype(jnp.float32)          # (W, 1)
    u = x - eta * theta * g
    t_op = t_ref[...].astype(jnp.float32)               # (W, W)
    o_ref[...] = jax.lax.dot_general(
        t_op, u, (((0,), (0,)), ((), ()))).astype(o_ref.dtype)   # T^T @ u


def _grouped_kernel(x_ref, g_ref, a_ref, b_ref, theta_ref, o_ref, *,
                    eta: float, hub: bool, h_ref=None):
    """Fused structured mixing: subnet mean via skinny scatter/broadcast
    matmuls, optionally composed with the small (D, D) hub mix."""
    x = x_ref[...].astype(jnp.float32)
    g = g_ref[...].astype(jnp.float32)
    theta = theta_ref[...].astype(jnp.float32)          # (W, 1)
    u = x - eta * theta * g
    a = a_ref[...].astype(jnp.float32)                  # (D, W) v-scatter
    z = jax.lax.dot_general(a, u, (((1,), (0,)), ((), ())))   # hub models
    if hub:
        h = h_ref[...].astype(jnp.float32)              # (D, D)
        z = jax.lax.dot_general(h, z, (((0,), (0,)), ((), ())))  # H^T mix
    b = b_ref[...].astype(jnp.float32)                  # (W, D) broadcast
    o_ref[...] = jax.lax.dot_general(
        b, z, (((1,), (0,)), ((), ()))).astype(o_ref.dtype)


def _round_up(n: int, m: int) -> int:
    return -(-n // m) * m


def hier_mix_chunks(x: jnp.ndarray, g: jnp.ndarray, t_op: jnp.ndarray,
                    theta: jnp.ndarray, eta: float, *, block_c: int = 512,
                    interpret: bool = False) -> jnp.ndarray:
    """x, g: (W, C); t_op: (W, W); theta: (W,) -> (W, C).

    Blocks are padded to the TPU tile grid — lane dim (C chunks) to a
    multiple of 128, sublane dim (W) to the dtype's minimum sublane count —
    so the kernel compiles on real hardware for awkward leaf shapes, not
    just in interpret mode.  Zero padding is exact: padded workers carry
    x = g = theta = 0 and zero rows/columns of T, contributing nothing to
    the contraction.
    """
    w, c = x.shape
    # lane alignment: the chunk dim must tile in 128-lane multiples
    block_c = _round_up(min(block_c, _round_up(c, 128)), 128)
    cp = _round_up(c, block_c)
    # sublane alignment: min tile is (8, 128) for f32, (16, 128) for bf16
    sub = 16 if x.dtype == jnp.bfloat16 else 8
    wp = _round_up(w, sub)
    if (wp, cp) != (w, c):
        x = jnp.pad(x, ((0, wp - w), (0, cp - c)))
        g = jnp.pad(g, ((0, wp - w), (0, cp - c)))
        t_op = jnp.pad(t_op, ((0, wp - w), (0, wp - w)))
        theta = jnp.pad(theta, ((0, wp - w),))
    grid = (cp // block_c,)
    out = pl.pallas_call(
        functools.partial(_kernel, eta=eta),
        grid=grid,
        in_specs=[
            pl.BlockSpec((wp, block_c), lambda i: (0, i)),
            pl.BlockSpec((wp, block_c), lambda i: (0, i)),
            pl.BlockSpec((wp, wp), lambda i: (0, 0)),
            pl.BlockSpec((wp, 1), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((wp, block_c), lambda i: (0, i)),
        out_shape=jax.ShapeDtypeStruct((wp, cp), x.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
        name="hier_mix",
        interpret=interpret,
    )(x, g, t_op, theta[:, None])
    return out[:w, :c]


def hier_mix_tree(stacked_params, stacked_grads, t_op, theta, eta: float, *,
                  block_c: int = 512, interpret: bool = False):
    """Per-leaf launch loop (legacy path): one `pallas_call` per leaf.

    Kept as the packed path's equivalence oracle and benchmark baseline —
    new code should prefer `hier_mix_packed`."""
    def leaf(x, g):
        w = x.shape[0]
        flat_x = x.reshape(w, -1)
        flat_g = g.reshape(w, -1)
        out = hier_mix_chunks(flat_x, flat_g, t_op, theta, eta,
                              block_c=block_c, interpret=interpret)
        return out.reshape(x.shape)
    return jax.tree.map(leaf, stacked_params, stacked_grads)


# ------------------------------------------------------- structured operators
@dataclasses.dataclass(frozen=True)
class GroupedOperator:
    """Structured mixing operator for the packed kernel.

    ``scatter`` (D, W) holds the v-weighted subnet assignment
    (scatter[d, i] = v_i iff subnet_of[i] == d), ``broadcast`` (W, D) the
    membership indicator, and ``hub`` the optional (D, D) hub-mixing matrix
    H (None for a pure subnet/V round).  The kernel computes

        out = broadcast @ (H^T?) @ (scatter @ u)

    which is the two-stage / circulant structure of
    `protocol.subnet_average_two_stage` / `hub_average_two_stage` as two
    skinny matmuls + a small (D, D) contraction instead of a dense (W, W)
    one.
    """
    scatter: jnp.ndarray
    broadcast: jnp.ndarray
    hub: jnp.ndarray | None = None


jax.tree_util.register_pytree_node(
    GroupedOperator,
    lambda op: ((op.scatter, op.broadcast, op.hub), None),
    lambda _, ch: GroupedOperator(*ch))


def make_grouped_operator(subnet_of, v_weights, h=None) -> GroupedOperator:
    """Build the structured operator from raw network arrays.

    subnet_of: (W,) int subnet index per worker; v_weights: (W,) within-
    subnet weights (summing to 1 per subnet); h: optional (D, D) hub matrix
    (its circulant-ness, when required by ``mixing="ppermute"``, is the
    caller's contract — see `protocol._circulant_coeffs`).
    """
    sub = np.asarray(subnet_of)
    v = np.asarray(v_weights, np.float32)
    d = int(sub.max()) + 1
    w = sub.shape[0]
    scatter = np.zeros((d, w), np.float32)
    scatter[sub, np.arange(w)] = v
    broadcast = np.zeros((w, d), np.float32)
    broadcast[np.arange(w), sub] = 1.0
    hub = None if h is None else jnp.asarray(h, jnp.float32)
    return GroupedOperator(jnp.asarray(scatter), jnp.asarray(broadcast), hub)


# --------------------------------------------------------- packed single launch
def _packed_call(x, g, op, theta, eta: float, block_c: int, interpret: bool):
    """One `pallas_call` over the packed (W, C) buffer; returns (wp, cp)."""
    w, c = x.shape
    block_c = _round_up(min(block_c, _round_up(c, 128)), 128)
    cp = _round_up(c, block_c)
    wp = _round_up(w, 8)                      # packed buffers are always f32
    if (wp, cp) != (w, c):
        x = jnp.pad(x, ((0, wp - w), (0, cp - c)))
        g = jnp.pad(g, ((0, wp - w), (0, cp - c)))
        theta = jnp.pad(theta, ((0, wp - w),))
    grid = (cp // block_c,)
    xgt_specs = [
        pl.BlockSpec((wp, block_c), lambda i: (0, i)),
        pl.BlockSpec((wp, block_c), lambda i: (0, i)),
    ]
    theta_spec = pl.BlockSpec((wp, 1), lambda i: (0, 0))
    out_spec = pl.BlockSpec((wp, block_c), lambda i: (0, i))
    out_shape = jax.ShapeDtypeStruct((wp, cp), jnp.float32)
    params = pltpu.CompilerParams(dimension_semantics=("parallel",))

    if isinstance(op, GroupedOperator):
        d = op.scatter.shape[0]
        dp = _round_up(d, 8)
        scat = jnp.pad(op.scatter, ((0, dp - d), (0, wp - w)))
        bcast = jnp.pad(op.broadcast, ((0, wp - w), (0, dp - d)))
        operands = [x, g, scat, bcast]
        in_specs = xgt_specs + [
            pl.BlockSpec((dp, wp), lambda i: (0, 0)),
            pl.BlockSpec((wp, dp), lambda i: (0, 0)),
        ]
        if op.hub is not None:
            kernel = functools.partial(
                _hub_grouped_kernel, eta=eta)
            operands.append(jnp.pad(op.hub, ((0, dp - d), (0, dp - d))))
            in_specs.append(pl.BlockSpec((dp, dp), lambda i: (0, 0)))
        else:
            kernel = functools.partial(_grouped_kernel, eta=eta, hub=False)
        operands.append(theta[:, None])
        in_specs.append(theta_spec)
        return pl.pallas_call(
            kernel, grid=grid, in_specs=in_specs, out_specs=out_spec,
            out_shape=out_shape, compiler_params=params,
            name="hier_mix",
            interpret=interpret)(*operands)

    t_op = op
    if wp != w:
        t_op = jnp.pad(t_op, ((0, wp - w), (0, wp - w)))
    return pl.pallas_call(
        functools.partial(_kernel, eta=eta),
        grid=grid,
        in_specs=xgt_specs + [pl.BlockSpec((wp, wp), lambda i: (0, 0)),
                              theta_spec],
        out_specs=out_spec, out_shape=out_shape, compiler_params=params,
        name="hier_mix",
        interpret=interpret)(x, g, t_op, theta[:, None])


def _hub_grouped_kernel(x_ref, g_ref, a_ref, b_ref, h_ref, theta_ref, o_ref,
                        *, eta: float):
    _grouped_kernel(x_ref, g_ref, a_ref, b_ref, theta_ref, o_ref, eta=eta,
                    hub=True, h_ref=h_ref)


def hier_mix_packed(stacked_params, stacked_grads, op, theta, eta: float, *,
                    block_c: int = 512, interpret: bool = False):
    """Fused update+mix over a whole stacked pytree in ONE kernel launch.

    The tree is packed into a (W, sum C_i) f32 buffer (`repro.core.packing`
    contract), a single `pallas_call` runs the chunk grid — the operator
    and theta are fetched once — and the result is unpacked back to the
    tree's leaf shapes/dtypes.  ``op`` is a dense (W, W) matrix or a
    `GroupedOperator` (fused two_stage / circulant structured mixing).
    Bit-for-bit equal to the per-leaf `hier_mix_tree` for dense ``op``.
    """
    spec = pack_spec(stacked_params)
    x = pack(stacked_params, spec)
    g = pack(stacked_grads, spec)
    out = _packed_call(x, g, op, jnp.asarray(theta, jnp.float32), eta,
                       block_c, interpret)
    return unpack(out, spec)


def hier_mix_packed_chunked(stacked_params, stacked_grads, op, theta,
                            eta: float, *, num_chunks: int = 4,
                            block_c: int = 512, interpret: bool = False):
    """`hier_mix_packed` as CHUNK-GRANULAR launches: the packed (W, sum C)
    buffer is split into lane-aligned `packing.chunk_views` and each chunk
    gets its OWN `pallas_call` (operator + theta re-fetched per launch).

    The point is overlap: with one launch per chunk the runtime can overlap
    chunk i's update+mix with chunk i+1's operand DMA (double-buffered in
    the FSDP-stream idiom) instead of serializing one monolithic launch
    behind the full buffer's fetch.  The contraction reduces over the
    WORKER axis only, so every packed column's arithmetic is independent of
    the chunking — bit-for-bit equal to the single-launch `hier_mix_packed`
    (each launch pads its own lane tail with zeros, which contribute
    nothing).  The extra cost is num_chunks - 1 re-fetches of the small
    operator/theta operands.
    """
    spec = pack_spec(stacked_params)
    x = pack(stacked_params, spec)
    g = pack(stacked_grads, spec)
    theta = jnp.asarray(theta, jnp.float32)
    w = x.shape[0]
    outs = [_packed_call(x[:, ch.lo:ch.hi], g[:, ch.lo:ch.hi], op, theta,
                         eta, block_c, interpret)[:w, :ch.size]
            for ch in chunk_views(spec, num_chunks)]
    return unpack(outs[0] if len(outs) == 1
                  else jnp.concatenate(outs, axis=1), spec)
