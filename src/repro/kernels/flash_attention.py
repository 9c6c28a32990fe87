"""Blocked flash attention (Pallas, TPU target): forward AND backward.

Layout: the public wrappers take token-major (B, T, H, hd) arrays and move
them head-major, (B, H, T, hd), before the kernels run.  Every block then
ends in a (rows, head_dim) tile of the array it reads — the TPU compiler
requires the last two block dims to be multiples of (8, 128) or equal to
the array's own dims, which a (block_q, 1 head) slice of (B, T, H, hd)
never is.  The per-row softmax statistics (logsumexp ``lse`` and the
backward's ``delta``) live as (B, H, T, 1) columns with (1, 1, block_q, 1)
blocks, so kernels read and write them as (block_q, 1) tiles without a
lane/sublane transpose.

Forward tiling: grid = (batch, q_heads, T/block_q, S/block_kv); the kv axis
is the minormost ("arbitrary") grid dimension, accumulating the online
softmax in VMEM scratch (running max m, normalizer l, weighted output acc)
and writing the tile + the logsumexp residual out on the last kv step.
Block shapes are MXU/VPU aligned: block_q x block_kv defaults to 128 x 128.

Head-dim padding: head_dim is zero-padded up to a multiple of 64 by the
wrappers (80 -> 128 for the stablelm-style heads; 64/128 stay put).  Because
the pad lanes of q/k/v/do are EXACT zeros, every matmul of both passes
(q.kT, p.v, do.vT, ds.k, ds.q, p.do) carries exact zeros through them — the
sliced-off gradient lanes are exactly zero, not merely small
(regression-tested at head_dim 80 in tests/test_kernels.py).

Backward: recomputation-based, two kernels sharing the forward's masking and
softcap semantics.  The forward saves only `o` and the per-row logsumexp
``lse = m + log(l)``; the backward recomputes the probability tile
``p = exp(s - lse)`` instead of materializing the (T, S) matrix:

  * dq kernel — grid (B, H, T/block_q, S/block_kv), kv minormost arbitrary;
    dq accumulates over the kv axis in VMEM scratch,
  * dkv kernel — grid (B, Hkv, S/block_kv, T/block_q), q minormost
    arbitrary; dk/dv accumulate over the q-block axis in VMEM scratch and
    reduce over the q-head GQA group with a static in-kernel loop (the
    whole group's (group, block_q, hd) q/do tiles arrive in one block).

``delta = rowsum(do * o)`` is precomputed in f32 by the wrapper (one fused
elementwise-reduce pass; the FlashAttention "preprocess" step).  Fully
masked tiles short-circuit in all three kernels via `pl.when` — the causal
upper triangle and windows far in the past skip their matmuls entirely.

Decode (`flash_decode_paged`) reads the serving pool in its head-major
(num_blocks, Hkv, block_size, hd) layout (`serve.kv_cache`): one
(block_size, hd) tile per kv head and physical block.

VMEM budget per program instance (bf16 inputs, f32 scratch, hd padded to
128; the (rows, 1) columns occupy a full 128-lane tile row each):
  forward: q tile 128x128x2 = 32 KiB, k/v tiles 2x32 KiB,
           acc f32 64 KiB, m/l/lse columns 3x64 KiB
  dq:      q/do/k/v tiles 4x32 KiB, dq acc f32 64 KiB, lse/delta 2x64 KiB
  dkv:     k/v tiles 2x32 KiB, q/do tiles 2x(group x 32 KiB),
           dk/dv acc f32 2x64 KiB, lse/delta 2x(group x 64 KiB)
  -> every variant stays well under the ~16 MiB v5e VMEM ceiling up to
     GQA groups of 8 at head_dim 128, double-buffered; block sizes are
     tunable.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _round_up(n: int, m: int) -> int:
    return (n + m - 1) // m * m


def _pad_head_dim(hd: int) -> int:
    """Lane alignment: head_dim rounds up to a multiple of 64 (all assigned
    archs have head_dim in {64, 80, 128}; 80 pads to 128)."""
    return _round_up(hd, 64)


def _head_major(x: jnp.ndarray, t_pad: int, hd_pad: int) -> jnp.ndarray:
    """(B, T, H, hd) -> (B, H, T + t_pad, hd + hd_pad), zero-padded."""
    x = jnp.swapaxes(x, 1, 2)
    if t_pad or hd_pad:
        x = jnp.pad(x, ((0, 0), (0, 0), (0, t_pad), (0, hd_pad)))
    return x


def _token_major(x: jnp.ndarray, t: int, hd: int) -> jnp.ndarray:
    """Inverse of `_head_major`: (B, H, Tp, hd_p) -> (B, t, H, hd)."""
    return jnp.swapaxes(x[:, :, :t, :hd], 1, 2)


def _rows(x: jnp.ndarray, t_pad: int) -> jnp.ndarray:
    """A per-row statistic (B, H, T) as the kernels' (B, H, T + t_pad, 1)
    column."""
    if t_pad:
        x = jnp.pad(x, ((0, 0), (0, 0), (0, t_pad)))
    return x[..., None]


def _tile_live(q_start, k_start, *, causal: bool, window: int,
               block_q: int, block_kv: int):
    """Tile-level reachability (skip fully-masked tiles entirely)."""
    live = jnp.bool_(True)
    if causal:
        live &= k_start <= q_start + block_q - 1          # below/at diagonal
    if window > 0:
        live &= k_start + block_kv - 1 >= q_start - window + 1  # inside window
    return live


def _tile_mask(q_start, k_start, *, causal: bool, window: int,
               block_q: int, block_kv: int, kv_len: int):
    qpos = q_start + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_kv), 0)
    kpos = k_start + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_kv), 1)
    mask = kpos < kv_len
    if causal:
        mask &= kpos <= qpos
    if window > 0:
        mask &= (qpos - kpos) < window
    return mask


def _online_softmax_step(s, mask, v, acc_ref, m_ref, l_ref):
    """One kv tile of the online softmax: fold the masked score tile ``s``
    (rows, kv) and its values ``v`` (kv, hd) into the running (acc, m, l)
    scratch; m/l are (rows, 1) columns."""
    m_prev = m_ref[...]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
    # guard: rows with no live keys yet keep NEG_INF max
    p = jnp.where(mask, jnp.exp(s - m_new), 0.0)
    alpha = jnp.exp(m_prev - m_new)
    l_ref[...] = alpha * l_ref[...] + jnp.sum(p, axis=1, keepdims=True)
    acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())))
    m_ref[...] = m_new


# ------------------------------------------------------------------ forward
def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, acc_ref, m_ref, l_ref, *,
                scale: float, causal: bool, window: int, softcap: float,
                block_q: int, block_kv: int, kv_len: int):
    qb = pl.program_id(2)
    kb = pl.program_id(3)
    nkv = pl.num_programs(3)

    @pl.when(kb == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    q_start = qb * block_q
    k_start = kb * block_kv
    live = _tile_live(q_start, k_start, causal=causal, window=window,
                      block_q=block_q, block_kv=block_kv)

    @pl.when(live)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32) * scale            # (bq, hd)
        k = k_ref[0, 0].astype(jnp.float32)                    # (bkv, hd)
        v = v_ref[0, 0].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())))  # (bq, bkv)
        if softcap > 0:
            s = softcap * jnp.tanh(s / softcap)
        mask = _tile_mask(q_start, k_start, causal=causal, window=window,
                          block_q=block_q, block_kv=block_kv, kv_len=kv_len)
        _online_softmax_step(jnp.where(mask, s, NEG_INF), mask, v,
                             acc_ref, m_ref, l_ref)

    @pl.when(kb == nkv - 1)
    def _finalize():
        l = l_ref[...]
        denom = jnp.where(l == 0.0, 1.0, l)
        o_ref[0, 0] = (acc_ref[...] / denom).astype(o_ref.dtype)
        lse_ref[0, 0] = m_ref[...] + jnp.log(denom)


def flash_attention_fwd_res(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray, *,
                            causal: bool = True, window: int = 0,
                            softcap: float = 0.0, block_q: int = 128,
                            block_kv: int = 128, interpret: bool = False
                            ) -> tuple[jnp.ndarray, jnp.ndarray]:
    """q: (B, T, H, hd), k/v: (B, S, Hkv, hd) -> (o (B, T, H, hd),
    lse (B, H, T) f32) — the logsumexp residual the backward recomputes
    probabilities from."""
    b, t, h, hd = q.shape
    s, hkv = k.shape[1], k.shape[2]
    group = h // hkv
    hd_p = _pad_head_dim(hd)
    block_q = min(block_q, t)
    block_kv = min(block_kv, s)
    t_pad = -t % block_q
    s_pad = -s % block_kv
    qh = _head_major(q, t_pad, hd_p - hd)
    kh = _head_major(k, s_pad, hd_p - hd)
    vh = _head_major(v, s_pad, hd_p - hd)
    tp, sp = t + t_pad, s + s_pad

    kernel = functools.partial(
        _fwd_kernel, scale=1.0 / np.sqrt(hd), causal=causal, window=window,
        softcap=softcap, block_q=block_q, block_kv=block_kv, kv_len=s)
    q_spec = pl.BlockSpec((1, 1, block_q, hd_p),
                          lambda b_, h_, qb, kb: (b_, h_, qb, 0))
    kv_spec = pl.BlockSpec((1, 1, block_kv, hd_p),
                           lambda b_, h_, qb, kb: (b_, h_ // group, kb, 0))
    row_spec = pl.BlockSpec((1, 1, block_q, 1),
                            lambda b_, h_, qb, kb: (b_, h_, qb, 0))
    out, lse = pl.pallas_call(
        kernel,
        grid=(b, h, tp // block_q, sp // block_kv),
        in_specs=[q_spec, kv_spec, kv_spec],
        out_specs=[q_spec, row_spec],
        out_shape=[
            jax.ShapeDtypeStruct((b, h, tp, hd_p), q.dtype),
            jax.ShapeDtypeStruct((b, h, tp, 1), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, hd_p), jnp.float32),   # acc
            pltpu.VMEM((block_q, 1), jnp.float32),      # running max m
            pltpu.VMEM((block_q, 1), jnp.float32),      # normalizer l
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel", "arbitrary")),
        name="flash_fwd",
        interpret=interpret,
    )(qh, kh, vh)
    return _token_major(out, t, hd), lse[:, :, :t, 0]


def flash_attention_fwd(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray, *,
                        causal: bool = True, window: int = 0,
                        softcap: float = 0.0, block_q: int = 128,
                        block_kv: int = 128, interpret: bool = False
                        ) -> jnp.ndarray:
    """q: (B, T, H, hd), k/v: (B, S, Hkv, hd) -> (B, T, H, hd)."""
    return flash_attention_fwd_res(q, k, v, causal=causal, window=window,
                                   softcap=softcap, block_q=block_q,
                                   block_kv=block_kv, interpret=interpret)[0]


# ------------------------------------------------------------ flash decode
def _decode_kernel(tbl_ref, len_ref, q_ref, k_ref, v_ref,
                   o_ref, m_ref, l_ref, acc_ref, mx_ref, lx_ref, *,
                   scale: float, window: int, softcap: float,
                   block_kv: int, blocks_per_split: int, group: int):
    """Single-query attention over a paged KV cache, one (batch, kv-head,
    split) program sequence per scratch lifetime.

    Grid: (B, Hkv, num_splits, blocks_per_split); the block axis is the
    minormost "arbitrary" dimension, accumulating the online softmax in VMEM
    scratch.  The k/v tiles arrive through the BLOCK-TABLE indirection: the
    in_specs' index maps read the scalar-prefetched ``tbl_ref`` so each grid
    step DMAs exactly the physical block the logical position maps to.  The
    whole GQA group's queries ride in one (group, hd) tile, so each fetched
    KV block is reused ``group`` times.

    Outputs are per-split partials — UNNORMALIZED accumulator plus the
    (m, l) softmax state — combined across splits by the wrapper's
    logsumexp epilogue (flash-decoding split-KV reduction).
    """
    b = pl.program_id(0)
    s = pl.program_id(2)
    j = pl.program_id(3)
    nj = pl.num_programs(3)

    @pl.when(j == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        mx_ref[...] = jnp.full_like(mx_ref, NEG_INF)
        lx_ref[...] = jnp.zeros_like(lx_ref)

    length = len_ref[b]                   # tokens in cache incl. the current
    qpos = length - 1                     # the query's absolute position
    start = (s * blocks_per_split + j) * block_kv
    live = start < length                 # block holds any live position
    if window > 0:                        # entirely left of the window?
        live &= start + block_kv - 1 >= qpos - window + 1

    @pl.when(live)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32) * scale          # (group, hd)
        k = k_ref[0, 0].astype(jnp.float32)                  # (bkv, hd)
        v = v_ref[0, 0].astype(jnp.float32)
        sc = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())))
        if softcap > 0:
            sc = softcap * jnp.tanh(sc / softcap)
        kpos = start + jax.lax.broadcasted_iota(
            jnp.int32, (group, block_kv), 1)
        mask = kpos < length              # causal: everything cached is past
        if window > 0:
            mask &= (qpos - kpos) < window
        _online_softmax_step(jnp.where(mask, sc, NEG_INF), mask, v,
                             acc_ref, mx_ref, lx_ref)

    @pl.when(j == nj - 1)
    def _finalize():
        o_ref[0, 0, 0] = acc_ref[...]
        m_ref[0, 0, 0] = mx_ref[...]
        l_ref[0, 0, 0] = lx_ref[...]


def flash_decode_paged(q: jnp.ndarray, k_pool: jnp.ndarray,
                       v_pool: jnp.ndarray, block_tables: jnp.ndarray,
                       lengths: jnp.ndarray, *, window: int = 0,
                       softcap: float = 0.0, num_splits: int = 0,
                       interpret: bool = False) -> jnp.ndarray:
    """Flash-decode: one query token per sequence against a paged KV cache.

    q: (B, H, hd) — the new token's queries.
    k_pool/v_pool: (num_blocks, Hkv, block_size, hd) — the shared block
        pool, head-major (`serve.kv_cache` layout).
    block_tables: (B, max_blocks) int32 — physical block of each logical
        block (rows padded with any valid block id; padded entries are
        masked out by ``lengths``).
    lengths: (B,) int32 — tokens in the cache INCLUDING the one being
        decoded (the query sits at absolute position ``lengths - 1``);
        0 marks an inactive lane (output is all zeros).
    -> (B, H, hd), same dtype as q.

    Split-KV: the logical block axis is divided into ``num_splits``
    independent grid lanes, each producing an unnormalized partial
    (acc, m, l); the wrapper combines them with a logsumexp weighting —
    exact, order-independent.  GQA: each kv head serves its whole q-head
    group from one fetched block.
    """
    bsz, h, hd = q.shape
    nb, hkv, bs, _ = k_pool.shape
    group = h // hkv
    hd_p = _pad_head_dim(hd)
    if hd_p != hd:
        q = jnp.pad(q, ((0, 0), (0, 0), (0, hd_p - hd)))
        k_pool = jnp.pad(k_pool, ((0, 0), (0, 0), (0, 0), (0, hd_p - hd)))
        v_pool = jnp.pad(v_pool, ((0, 0), (0, 0), (0, 0), (0, hd_p - hd)))
    nmax = block_tables.shape[1]
    if num_splits <= 0:                       # enough lanes to matter, but
        num_splits = min(8, nmax)             # never empty splits
    num_splits = max(1, min(num_splits, nmax))
    bps = -(-nmax // num_splits)              # blocks per split (ceil)
    pad_blocks = num_splits * bps - nmax
    if pad_blocks:                            # padded entries point at block
        block_tables = jnp.pad(block_tables,  # 0 (valid memory, masked out)
                               ((0, 0), (0, pad_blocks)))
    qg = q.reshape(bsz, hkv, group, hd_p)     # head h = kv*group + g

    kernel = functools.partial(
        _decode_kernel, scale=1.0 / np.sqrt(hd), window=window,
        softcap=softcap, block_kv=bs, blocks_per_split=bps, group=group)
    kv_spec = pl.BlockSpec((1, 1, bs, hd_p),
                           lambda b, h_, s, j, tbl, lens:
                           (tbl[b, s * bps + j], h_, 0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(bsz, hkv, num_splits, bps),
        in_specs=[
            pl.BlockSpec((1, 1, group, hd_p),
                         lambda b, h_, s, j, tbl, lens: (b, h_, 0, 0)),
            kv_spec, kv_spec,
        ],
        out_specs=[
            pl.BlockSpec((1, 1, 1, group, hd_p),
                         lambda b, h_, s, j, tbl, lens: (b, h_, s, 0, 0)),
            pl.BlockSpec((1, 1, 1, group, 1),
                         lambda b, h_, s, j, tbl, lens: (b, h_, s, 0, 0)),
            pl.BlockSpec((1, 1, 1, group, 1),
                         lambda b, h_, s, j, tbl, lens: (b, h_, s, 0, 0)),
        ],
        scratch_shapes=[
            pltpu.VMEM((group, hd_p), jnp.float32),   # unnormalized acc
            pltpu.VMEM((group, 1), jnp.float32),      # running max m
            pltpu.VMEM((group, 1), jnp.float32),      # normalizer l
        ],
    )
    o_parts, m_parts, l_parts = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((bsz, hkv, num_splits, group, hd_p),
                                 jnp.float32),
            jax.ShapeDtypeStruct((bsz, hkv, num_splits, group, 1),
                                 jnp.float32),
            jax.ShapeDtypeStruct((bsz, hkv, num_splits, group, 1),
                                 jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        name="flash_decode",
        interpret=interpret,
    )(block_tables.astype(jnp.int32), lengths.astype(jnp.int32),
      qg, k_pool, v_pool)
    m_parts, l_parts = m_parts[..., 0], l_parts[..., 0]

    # split combine: exact logsumexp reduction over the split axis.  Dead
    # splits carry (m=NEG_INF, l=0) and contribute exactly zero; a fully
    # dead row (lengths == 0) is guarded to zeros.
    m = jnp.max(m_parts, axis=2)                              # (B, Hkv, G)
    w = jnp.exp(m_parts - m[:, :, None])                      # (B, Hkv, S, G)
    acc = jnp.einsum("bhsg,bhsgd->bhgd", w, o_parts)
    l = jnp.sum(w * l_parts, axis=2)
    out = acc / jnp.where(l == 0.0, 1.0, l)[..., None]
    return out.reshape(bsz, h, hd_p)[..., :hd].astype(q.dtype)


# ----------------------------------------------------------------- backward
def _recompute_p_ds(q, k, v, do, lse_col, delta_col, mask, *,
                    softcap: float):
    """Shared bwd tile math: p from the lse residual, ds with the softcap
    chain rule.  q arrives pre-scaled; lse/delta are (bq, 1) columns; all
    f32."""
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())))     # (bq, bkv)
    if softcap > 0:
        s = softcap * jnp.tanh(s / softcap)
    p = jnp.where(mask, jnp.exp(s - lse_col), 0.0)
    dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())))   # (bq, bkv)
    ds = p * (dp - delta_col)
    if softcap > 0:
        ds = ds * (1.0 - (s / softcap) ** 2)                    # 1 - tanh^2
    return p, ds


def _bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref,
                   acc_ref, *, scale: float, causal: bool, window: int,
                   softcap: float, block_q: int, block_kv: int, kv_len: int):
    qb = pl.program_id(2)
    kb = pl.program_id(3)
    nkv = pl.num_programs(3)

    @pl.when(kb == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q_start = qb * block_q
    k_start = kb * block_kv
    live = _tile_live(q_start, k_start, causal=causal, window=window,
                      block_q=block_q, block_kv=block_kv)

    @pl.when(live)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32) * scale
        k = k_ref[0, 0].astype(jnp.float32)
        v = v_ref[0, 0].astype(jnp.float32)
        do = do_ref[0, 0].astype(jnp.float32)
        mask = _tile_mask(q_start, k_start, causal=causal, window=window,
                          block_q=block_q, block_kv=block_kv, kv_len=kv_len)
        _, ds = _recompute_p_ds(q, k, v, do, lse_ref[0, 0], delta_ref[0, 0],
                                mask, softcap=softcap)
        acc_ref[...] += jax.lax.dot_general(
            ds, k, (((1,), (0,)), ((), ()))) * scale

    @pl.when(kb == nkv - 1)
    def _finalize():
        dq_ref[0, 0] = acc_ref[...].astype(dq_ref.dtype)


def _bwd_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                    dk_ref, dv_ref, dk_acc, dv_acc, *, scale: float,
                    causal: bool, window: int, softcap: float, block_q: int,
                    block_kv: int, kv_len: int, group: int):
    kb = pl.program_id(2)
    qb = pl.program_id(3)
    nq = pl.num_programs(3)

    @pl.when(qb == 0)
    def _init():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    q_start = qb * block_q
    k_start = kb * block_kv
    live = _tile_live(q_start, k_start, causal=causal, window=window,
                      block_q=block_q, block_kv=block_kv)

    @pl.when(live)
    def _compute():
        k = k_ref[0, 0].astype(jnp.float32)
        v = v_ref[0, 0].astype(jnp.float32)
        mask = _tile_mask(q_start, k_start, causal=causal, window=window,
                          block_q=block_q, block_kv=block_kv, kv_len=kv_len)
        # dk/dv reduce over the q-head GQA group: the block carries the whole
        # group's q/do tiles, the loop is static (unrolled at trace time)
        for g in range(group):
            q = q_ref[0, g].astype(jnp.float32) * scale
            do = do_ref[0, g].astype(jnp.float32)
            p, ds = _recompute_p_ds(q, k, v, do, lse_ref[0, g],
                                    delta_ref[0, g], mask, softcap=softcap)
            dv_acc[...] += jax.lax.dot_general(
                p, do, (((0,), (0,)), ((), ())))                # (bkv, hd)
            dk_acc[...] += jax.lax.dot_general(
                ds, q, (((0,), (0,)), ((), ())))                # q pre-scaled

    @pl.when(qb == nq - 1)
    def _finalize():
        dk_ref[0, 0] = dk_acc[...].astype(dk_ref.dtype)
        dv_ref[0, 0] = dv_acc[...].astype(dv_ref.dtype)


def flash_attention_bwd(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                        o: jnp.ndarray, lse: jnp.ndarray, do: jnp.ndarray, *,
                        causal: bool = True, window: int = 0,
                        softcap: float = 0.0, block_q: int = 128,
                        block_kv: int = 128, interpret: bool = False
                        ) -> tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Recomputation-based backward. q/do/o: (B, T, H, hd),
    k/v: (B, S, Hkv, hd), lse: (B, H, T) -> (dq, dk, dv) matching the
    primal shapes/dtypes (dk/dv reduced over the q-head group)."""
    b, t, h, hd = q.shape
    s_len, hkv = k.shape[1], k.shape[2]
    group = h // hkv
    hd_p = _pad_head_dim(hd)
    block_q = min(block_q, t)
    block_kv = min(block_kv, s_len)
    t_pad = -t % block_q
    s_pad = -s_len % block_kv
    # preprocess: delta_i = sum_d do_id * o_id, in f32 (one elementwise pass)
    delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32), axis=-1)
    delta = _rows(jnp.swapaxes(delta, 1, 2), t_pad)            # (B, H, Tp, 1)
    lse = _rows(lse, t_pad)
    qh = _head_major(q, t_pad, hd_p - hd)
    doh = _head_major(do, t_pad, hd_p - hd)
    kh = _head_major(k, s_pad, hd_p - hd)
    vh = _head_major(v, s_pad, hd_p - hd)
    tp, sp = t + t_pad, s_len + s_pad
    scale = 1.0 / np.sqrt(hd)
    params = pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel", "parallel", "arbitrary"))

    dq_kernel = functools.partial(
        _bwd_dq_kernel, scale=scale, causal=causal, window=window,
        softcap=softcap, block_q=block_q, block_kv=block_kv, kv_len=s_len)
    q_spec = pl.BlockSpec((1, 1, block_q, hd_p),
                          lambda b_, h_, qb, kb: (b_, h_, qb, 0))
    kv_spec = pl.BlockSpec((1, 1, block_kv, hd_p),
                           lambda b_, h_, qb, kb: (b_, h_ // group, kb, 0))
    row_spec = pl.BlockSpec((1, 1, block_q, 1),
                            lambda b_, h_, qb, kb: (b_, h_, qb, 0))
    dq = pl.pallas_call(
        dq_kernel,
        grid=(b, h, tp // block_q, sp // block_kv),
        in_specs=[q_spec, kv_spec, kv_spec, q_spec, row_spec, row_spec],
        out_specs=q_spec,
        out_shape=jax.ShapeDtypeStruct((b, h, tp, hd_p), q.dtype),
        scratch_shapes=[pltpu.VMEM((block_q, hd_p), jnp.float32)],
        compiler_params=params,
        name="flash_dq",
        interpret=interpret,
    )(qh, kh, vh, doh, lse, delta)

    dkv_kernel = functools.partial(
        _bwd_dkv_kernel, scale=scale, causal=causal, window=window,
        softcap=softcap, block_q=block_q, block_kv=block_kv, kv_len=s_len,
        group=group)
    # grid (b, kv head, kv block, q block): the q-side blocks carry the kv
    # head's whole group of q heads
    gq_spec = pl.BlockSpec((1, group, block_q, hd_p),
                           lambda b_, h_, kb, qb: (b_, h_, qb, 0))
    grow_spec = pl.BlockSpec((1, group, block_q, 1),
                             lambda b_, h_, kb, qb: (b_, h_, qb, 0))
    kvo_spec = pl.BlockSpec((1, 1, block_kv, hd_p),
                            lambda b_, h_, kb, qb: (b_, h_, kb, 0))
    dk, dv = pl.pallas_call(
        dkv_kernel,
        grid=(b, hkv, sp // block_kv, tp // block_q),
        in_specs=[gq_spec, kvo_spec, kvo_spec, gq_spec, grow_spec, grow_spec],
        out_specs=[kvo_spec, kvo_spec],
        out_shape=[
            jax.ShapeDtypeStruct((b, hkv, sp, hd_p), k.dtype),
            jax.ShapeDtypeStruct((b, hkv, sp, hd_p), v.dtype),
        ],
        scratch_shapes=[pltpu.VMEM((block_kv, hd_p), jnp.float32),
                        pltpu.VMEM((block_kv, hd_p), jnp.float32)],
        compiler_params=params,
        name="flash_dkv",
        interpret=interpret,
    )(qh, kh, vh, doh, lse, delta)
    return (_token_major(dq, t, hd), _token_major(dk, s_len, hd),
            _token_major(dv, s_len, hd))
