"""Fused sLSTM recurrence (Pallas, TPU target) — forward AND backward.

The XLA lowering of the sLSTM `lax.scan` issues per-time-step HBM
round-trips for the gate pre-activations and the running state (h, c, n, m)
— ~24k tiny fusions per layer at seq 4096, the dominant memory-roofline
term for xlstm-125m.  This kernel keeps the state in VMEM across the whole
sequence and streams the gate pre-activations chunk by chunk:

  HBM traffic per layer = read zx once + write h once      (vs 2 x T round
  trips), a predicted ~50x reduction of the recurrence's memory term.

Grid = (B/bb, H, T/chunk); the T axis is the minormost ("arbitrary") grid
dim so the VMEM state scratch persists across chunks.  Per head the
recurrent weights R (hd, 4*hd) sit in VMEM for the whole program; each
step runs one (bb, hd) x (hd, 4*hd) MXU matmul.

Stabilised exponential gating follows the paper (m-stabiliser), matching
`xlstm.slstm_train` numerics; validated against it in interpret mode
(tests/test_kernels.py).

Backward (`slstm_scan_bwd`): a reverse-time Pallas scan over the same grid
with the T chunks visited LAST-TO-FIRST (reversed index maps).  The adjoint
state (dh, dc, dn, dm) lives in VMEM scratch across chunks — it is never
materialized to HBM.  Instead of saving per-step state, the forward-with-
residuals variant saves only the state ENTERING each chunk ((B, T/chunk, H,
hd) x 4 — a 1/chunk-sized footprint); the backward re-runs the stabilised
gate recurrence forward WITHIN the chunk from that boundary state (storing
z and the entering (h, c, n, m) per step in VMEM only), then walks the
chunk in reverse applying the exact VJP of the gating math — including the
max-stabiliser subgradient routing, so gradients match `jax.grad` of the
pure-scan reference.  dR/db are accumulated in VMEM across all chunks and
emitted once per (batch-block, head) as partial sums ((B/bb, H, hd, 4hd) /
(B/bb, H, 4hd)), reduced by the wrapper — keeping the batch grid axis
parallel (no cross-program output race).

VMEM budget per backward program instance (f32):
  R + dR acc          2 x (hd x 4hd x 4 B)              = 128 KiB @ hd 64
  z buffer            chunk x bb x 4hd x 4 B            = 1 MiB   @ 128x8x64
  entering h/c/n/m    4 x chunk x bb x hd x 4 B         = 1 MiB
  adjoints + db       ~5 x bb x hd x 4 B                < 10 KiB
  zx / dh / dzx tiles chunk x bb x (4hd + hd + 4hd)     ~ 2.25 MiB
  -> ~4.5 MiB at the (bb=8, chunk=128, hd=64) defaults, well under the
     ~16 MiB v5e ceiling; shrink `chunk` first if a bigger head overflows.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

EPS = 1e-6


def _gates(z, hd: int):
    return (z[:, 0:hd], z[:, hd:2 * hd], z[:, 2 * hd:3 * hd], z[:, 3 * hd:])


def _fwd_kernel(zx_ref, r_ref, b_ref, o_ref, *refs, chunk: int, hd: int,
                save_bounds: bool):
    if save_bounds:
        (hb_ref, cb_ref, nb_ref, mb_ref,
         h_ref, c_ref, n_ref, m_ref) = refs
    else:
        h_ref, c_ref, n_ref, m_ref = refs
    tc = pl.program_id(2)

    @pl.when(tc == 0)
    def _init():
        h_ref[...] = jnp.zeros_like(h_ref)
        c_ref[...] = jnp.zeros_like(c_ref)
        n_ref[...] = jnp.ones_like(n_ref)
        m_ref[...] = jnp.zeros_like(m_ref)

    if save_bounds:
        # the state ENTERING this chunk — the backward's recompute seed
        hb_ref[:, 0, 0, :] = h_ref[...]
        cb_ref[:, 0, 0, :] = c_ref[...]
        nb_ref[:, 0, 0, :] = n_ref[...]
        mb_ref[:, 0, 0, :] = m_ref[...]

    r = r_ref[0].astype(jnp.float32)                 # (hd, 4hd)
    bias = b_ref[0].astype(jnp.float32)              # (4hd,)

    def step(t, _):
        zx_t = zx_ref[:, t, 0, :].astype(jnp.float32)        # (bb, 4hd)
        h = h_ref[...]
        rec = jax.lax.dot_general(h, r, (((1,), (0,)), ((), ())))
        z = zx_t + rec + bias
        zi, zf, zz, zo = _gates(z, hd)
        logf = jax.nn.log_sigmoid(zf)
        m_new = jnp.maximum(logf + m_ref[...], zi)
        i_t = jnp.exp(zi - m_new)
        f_t = jnp.exp(logf + m_ref[...] - m_new)
        c = f_t * c_ref[...] + i_t * jnp.tanh(zz)
        n = f_t * n_ref[...] + i_t
        h_new = jax.nn.sigmoid(zo) * c / jnp.maximum(n, EPS)
        h_ref[...] = h_new
        c_ref[...] = c
        n_ref[...] = n
        m_ref[...] = m_new
        o_ref[:, t, 0, :] = h_new.astype(o_ref.dtype)
        return ()

    jax.lax.fori_loop(0, chunk, step, ())


def _pad_bt(x, pad_b: int, pad_t: int):
    if pad_b or pad_t:
        x = jnp.pad(x, ((0, pad_b), (0, pad_t)) + ((0, 0),) * (x.ndim - 2))
    return x


def _fwd_call(zx, r_gates, b_gates, *, block_b: int, chunk: int,
              interpret: bool, save_bounds: bool):
    bsz, t, h, hd4 = zx.shape
    hd = hd4 // 4
    block_b = min(block_b, bsz)
    chunk = min(chunk, t)
    pad_b = -bsz % block_b
    pad_t = -t % chunk
    zx = _pad_bt(zx, pad_b, pad_t)
    bp, tp = bsz + pad_b, t + pad_t
    nt = tp // chunk

    grid = (bp // block_b, h, nt)
    out_specs = [pl.BlockSpec((block_b, chunk, 1, hd),
                              lambda bb, hh, tc: (bb, tc, hh, 0))]
    out_shape = [jax.ShapeDtypeStruct((bp, tp, h, hd), zx.dtype)]
    if save_bounds:
        bound_spec = pl.BlockSpec((block_b, 1, 1, hd),
                                  lambda bb, hh, tc: (bb, tc, hh, 0))
        bound_shape = jax.ShapeDtypeStruct((bp, nt, h, hd), jnp.float32)
        out_specs += [bound_spec] * 4
        out_shape += [bound_shape] * 4

    outs = pl.pallas_call(
        functools.partial(_fwd_kernel, chunk=chunk, hd=hd,
                          save_bounds=save_bounds),
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_b, chunk, 1, hd4),
                         lambda bb, hh, tc: (bb, tc, hh, 0)),
            pl.BlockSpec((1, hd, hd4), lambda bb, hh, tc: (hh, 0, 0)),
            pl.BlockSpec((1, hd4), lambda bb, hh, tc: (hh, 0)),
        ],
        out_specs=out_specs if save_bounds else out_specs[0],
        out_shape=out_shape if save_bounds else out_shape[0],
        scratch_shapes=[
            pltpu.VMEM((block_b, hd), jnp.float32),   # h
            pltpu.VMEM((block_b, hd), jnp.float32),   # c
            pltpu.VMEM((block_b, hd), jnp.float32),   # n
            pltpu.VMEM((block_b, hd), jnp.float32),   # m
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        name="slstm_fwd",
        interpret=interpret,
    )(zx, r_gates, b_gates)
    if not save_bounds:
        return outs[:bsz, :t], None
    out, hb, cb, nb, mb = outs
    # bounds stay in PADDED-batch layout: the backward re-pads with the same
    # block_b/chunk and its padded rows carry zero adjoints regardless
    return out[:bsz, :t], (hb, cb, nb, mb)


def slstm_scan(zx: jnp.ndarray, r_gates: jnp.ndarray, b_gates: jnp.ndarray,
               *, block_b: int = 8, chunk: int = 128,
               interpret: bool = False) -> jnp.ndarray:
    """zx: (B, T, H, 4*hd) gate pre-activations (input part, no bias);
    r_gates: (H, hd, 4*hd); b_gates: (H, 4*hd) -> h: (B, T, H, hd)."""
    return _fwd_call(zx, r_gates, b_gates, block_b=block_b, chunk=chunk,
                     interpret=interpret, save_bounds=False)[0]


def slstm_scan_fwd_res(zx: jnp.ndarray, r_gates: jnp.ndarray,
                       b_gates: jnp.ndarray, *, block_b: int = 8,
                       chunk: int = 128, interpret: bool = False):
    """Forward + residuals for the custom VJP: returns (h, bounds) where
    ``bounds = (h, c, n, m) entering each chunk``, each (Bp, T/chunk, H, hd)
    f32 in padded-batch layout (Bp = B rounded up to block_b)."""
    return _fwd_call(zx, r_gates, b_gates, block_b=block_b, chunk=chunk,
                     interpret=interpret, save_bounds=True)


# ----------------------------------------------------------------- backward
def _bwd_kernel(zx_ref, r_ref, b_ref, hb_ref, cb_ref, nb_ref, mb_ref, dh_ref,
                dzx_ref, drp_ref, dbp_ref,
                z_buf, h_buf, c_buf, n_buf, m_buf,
                dh_s, dc_s, dn_s, dm_s, dr_acc, db_acc, *,
                chunk: int, hd: int, nt: int):
    tc = pl.program_id(2)          # 0 = LAST chunk (index maps reverse T)

    @pl.when(tc == 0)
    def _init():
        dh_s[...] = jnp.zeros_like(dh_s)
        dc_s[...] = jnp.zeros_like(dc_s)
        dn_s[...] = jnp.zeros_like(dn_s)
        dm_s[...] = jnp.zeros_like(dm_s)
        dr_acc[...] = jnp.zeros_like(dr_acc)
        db_acc[...] = jnp.zeros_like(db_acc)

    r = r_ref[0].astype(jnp.float32)                 # (hd, 4hd)
    bias = b_ref[0].astype(jnp.float32)              # (4hd,)

    # pass 1: re-run the recurrence forward within the chunk from the saved
    # boundary state, stashing z and the ENTERING (h, c, n, m) per step
    def fwd_step(t, state):
        h, c, n, m = state
        h_buf[t] = h
        c_buf[t] = c
        n_buf[t] = n
        m_buf[t] = m
        zx_t = zx_ref[:, t, 0, :].astype(jnp.float32)
        rec = jax.lax.dot_general(h, r, (((1,), (0,)), ((), ())))
        z = zx_t + rec + bias
        z_buf[t] = z
        zi, zf, zz, zo = _gates(z, hd)
        logf = jax.nn.log_sigmoid(zf)
        m_new = jnp.maximum(logf + m, zi)
        i_t = jnp.exp(zi - m_new)
        f_t = jnp.exp(logf + m - m_new)
        c_new = f_t * c + i_t * jnp.tanh(zz)
        n_new = f_t * n + i_t
        h_new = jax.nn.sigmoid(zo) * c_new / jnp.maximum(n_new, EPS)
        return (h_new, c_new, n_new, m_new)

    state0 = (hb_ref[:, 0, 0, :], cb_ref[:, 0, 0, :],
              nb_ref[:, 0, 0, :], mb_ref[:, 0, 0, :])
    jax.lax.fori_loop(0, chunk, fwd_step, state0)

    # pass 2: reverse-time exact VJP of the gating math
    def bwd_step(ti, _):
        t = chunk - 1 - ti
        z = z_buf[t]
        h_prev, c_prev = h_buf[t], c_buf[t]
        n_prev, m_prev = n_buf[t], m_buf[t]
        zi, zf, zz, zo = _gates(z, hd)
        logf = jax.nn.log_sigmoid(zf)
        a = logf + m_prev
        m = jnp.maximum(a, zi)
        i_t = jnp.exp(zi - m)
        f_t = jnp.exp(a - m)
        tz = jnp.tanh(zz)
        ct = f_t * c_prev + i_t * tz
        nt_ = f_t * n_prev + i_t
        nd = jnp.maximum(nt_, EPS)
        sig_o = jax.nn.sigmoid(zo)
        hdn = ct / nd

        dh = dh_s[...] + dh_ref[:, t, 0, :].astype(jnp.float32)
        dzo = dh * hdn * sig_o * (1.0 - sig_o)
        dct = dh * sig_o / nd + dc_s[...]
        # max(nt, EPS): gradient flows only on the live branch
        dnt = dn_s[...] - jnp.where(nt_ >= EPS, dh * sig_o * hdn / nd, 0.0)
        df = dct * c_prev + dnt * n_prev
        di = dct * tz + dnt
        dzz = dct * i_t * (1.0 - tz * tz)
        # i = exp(zi - m), f = exp(a - m): both push -grad into m
        dm = dm_s[...] - di * i_t - df * f_t
        # m = max(a, zi) subgradient routing (ties -> the a branch, matching
        # jnp.maximum's convention in the reference scan)
        sel = (a >= zi).astype(jnp.float32)
        da = df * f_t + dm * sel
        dzi = di * i_t + dm * (1.0 - sel)
        dzf = da * jax.nn.sigmoid(-zf)       # d log_sigmoid = sigmoid(-x)
        dz = jnp.concatenate([dzi, dzf, dzz, dzo], axis=-1)   # (bb, 4hd)

        dzx_ref[:, t, 0, :] = dz.astype(dzx_ref.dtype)
        db_acc[...] += jnp.sum(dz, axis=0, keepdims=True)
        dr_acc[...] += jax.lax.dot_general(
            h_prev, dz, (((0,), (0,)), ((), ())))             # (hd, 4hd)
        dh_s[...] = jax.lax.dot_general(
            dz, r, (((1,), (1,)), ((), ())))                  # (bb, hd)
        dc_s[...] = dct * f_t
        dn_s[...] = dnt * f_t
        dm_s[...] = da
        return ()

    jax.lax.fori_loop(0, chunk, bwd_step, ())

    @pl.when(tc == nt - 1)
    def _emit():
        drp_ref[0, 0] = dr_acc[...]
        dbp_ref[0, 0, :] = db_acc[0, :]


def slstm_scan_bwd(zx: jnp.ndarray, r_gates: jnp.ndarray,
                   b_gates: jnp.ndarray, bounds, dh: jnp.ndarray, *,
                   block_b: int = 8, chunk: int = 128,
                   interpret: bool = False):
    """Reverse-time scan: (zx, R, b, chunk-boundary states, dh) ->
    (dzx, dR, db) matching the primal shapes/dtypes."""
    bsz, t, h, hd4 = zx.shape
    hd = hd4 // 4
    block_b = min(block_b, bsz)
    chunk = min(chunk, t)
    pad_b = -bsz % block_b
    pad_t = -t % chunk
    zx = _pad_bt(zx, pad_b, pad_t)
    dh = _pad_bt(dh, pad_b, pad_t)
    bp, tp = bsz + pad_b, t + pad_t
    nt = tp // chunk
    nb = bp // block_b
    hb, cb, nb_state, mb = bounds
    if hb.shape != (bp, nt, h, hd):
        raise ValueError(f"chunk-boundary residuals {hb.shape} do not match "
                         f"the padded layout {(bp, nt, h, hd)} — forward and "
                         f"backward must use the same block_b/chunk")

    rev = lambda tc: nt - 1 - tc   # chunks visited last-to-first
    seq_spec = lambda width: pl.BlockSpec(
        (block_b, chunk, 1, width), lambda bb, hh, tc: (bb, rev(tc), hh, 0))
    bound_spec = pl.BlockSpec((block_b, 1, 1, hd),
                              lambda bb, hh, tc: (bb, rev(tc), hh, 0))

    dzx, drp, dbp = pl.pallas_call(
        functools.partial(_bwd_kernel, chunk=chunk, hd=hd, nt=nt),
        grid=(nb, h, nt),
        in_specs=[
            seq_spec(hd4),                                        # zx
            pl.BlockSpec((1, hd, hd4), lambda bb, hh, tc: (hh, 0, 0)),
            pl.BlockSpec((1, hd4), lambda bb, hh, tc: (hh, 0)),
            bound_spec, bound_spec, bound_spec, bound_spec,       # h/c/n/m
            seq_spec(hd),                                         # dh
        ],
        out_specs=[
            seq_spec(hd4),                                        # dzx
            pl.BlockSpec((1, 1, hd, hd4), lambda bb, hh, tc: (bb, hh, 0, 0)),
            pl.BlockSpec((1, 1, hd4), lambda bb, hh, tc: (bb, hh, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bp, tp, h, hd4), zx.dtype),
            jax.ShapeDtypeStruct((nb, h, hd, hd4), jnp.float32),
            jax.ShapeDtypeStruct((nb, h, hd4), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((chunk, block_b, hd4), jnp.float32),  # z per step
            pltpu.VMEM((chunk, block_b, hd), jnp.float32),   # entering h
            pltpu.VMEM((chunk, block_b, hd), jnp.float32),   # entering c
            pltpu.VMEM((chunk, block_b, hd), jnp.float32),   # entering n
            pltpu.VMEM((chunk, block_b, hd), jnp.float32),   # entering m
            pltpu.VMEM((block_b, hd), jnp.float32),          # dh adjoint
            pltpu.VMEM((block_b, hd), jnp.float32),          # dc adjoint
            pltpu.VMEM((block_b, hd), jnp.float32),          # dn adjoint
            pltpu.VMEM((block_b, hd), jnp.float32),          # dm adjoint
            pltpu.VMEM((hd, hd4), jnp.float32),              # dR accumulator
            pltpu.VMEM((1, hd4), jnp.float32),               # db accumulator
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        name="slstm_bwd",
        interpret=interpret,
    )(zx, r_gates, b_gates, hb, cb, nb_state, mb, dh)
    dr = jnp.sum(drp, axis=0).astype(r_gates.dtype)
    db = jnp.sum(dbp, axis=0).astype(b_gates.dtype)
    return dzx[:bsz, :t], dr, db
