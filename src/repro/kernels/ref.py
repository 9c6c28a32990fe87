"""Pure-jnp oracles for every Pallas kernel (the allclose ground truth)."""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

NEG_INF = -1e30


def flash_attention_ref(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray, *,
                        causal: bool = True, window: int = 0,
                        softcap: float = 0.0) -> jnp.ndarray:
    """q: (B, T, H, hd); k/v: (B, S, Hkv, hd) with H % Hkv == 0.
    Returns (B, T, H, hd).  float32 softmax, same numerics contract as the
    kernel."""
    b, t, h, hd = q.shape
    s, hkv = k.shape[1], k.shape[2]
    group = h // hkv
    qg = q.reshape(b, t, hkv, group, hd)
    logits = jnp.einsum("bthgk,bshk->bhgts", qg, k).astype(jnp.float32)
    logits = logits / np.sqrt(hd)
    if softcap > 0:
        logits = softcap * jnp.tanh(logits / softcap)
    qpos = jnp.arange(t)[:, None]
    kpos = jnp.arange(s)[None, :]
    mask = jnp.ones((t, s), bool)
    if causal:
        mask &= kpos <= qpos
    if window > 0:
        mask &= (qpos - kpos) < window
    logits = jnp.where(mask[None, None, None], logits, NEG_INF)
    probs = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bhgts,bshk->bthgk", probs.astype(v.dtype), v)
    return out.reshape(b, t, h, hd)


def flash_decode_ref(q: jnp.ndarray, k_pool: jnp.ndarray,
                     v_pool: jnp.ndarray, block_tables: jnp.ndarray,
                     lengths: jnp.ndarray, *, window: int = 0,
                     softcap: float = 0.0) -> jnp.ndarray:
    """Paged single-query attention oracle (gather + dense softmax).

    q: (B, H, hd); k_pool/v_pool: (num_blocks, Hkv, block_size, hd);
    block_tables: (B, max_blocks) int32; lengths: (B,) int32 — tokens in
    cache including the one being decoded (query position = lengths - 1).
    Rows with lengths == 0 return zeros.  -> (B, H, hd)."""
    b, h, hd = q.shape
    nb, hkv, bs, _ = k_pool.shape
    group = h // hkv
    nmax = block_tables.shape[1]
    s = nmax * bs
    # (B, nmax, Hkv, bs, hd) -> logical order (B, S, Hkv, hd)
    k = jnp.swapaxes(k_pool[block_tables], 2, 3).reshape(b, s, hkv, hd)
    v = jnp.swapaxes(v_pool[block_tables], 2, 3).reshape(b, s, hkv, hd)
    qg = q.reshape(b, hkv, group, hd)
    logits = jnp.einsum("bhgk,bshk->bhgs", qg, k).astype(jnp.float32)
    logits = logits / np.sqrt(hd)
    if softcap > 0:
        logits = softcap * jnp.tanh(logits / softcap)
    kpos = jnp.arange(s, dtype=jnp.int32)[None, :]    # logical positions
    qpos = (lengths - 1)[:, None]
    mask = kpos < lengths[:, None]
    if window > 0:
        mask &= (qpos - kpos) < window
    logits = jnp.where(mask[:, None, None, :], logits, NEG_INF)
    probs = jax.nn.softmax(logits, axis=-1)
    probs = jnp.where(lengths[:, None, None, None] > 0, probs, 0.0)
    out = jnp.einsum("bhgs,bshk->bhgk", probs, v.astype(jnp.float32))
    return out.reshape(b, h, hd).astype(q.dtype)


def hier_mix_ref(x: jnp.ndarray, g: jnp.ndarray, t_op: jnp.ndarray,
                 theta: jnp.ndarray, eta: float) -> jnp.ndarray:
    """Fused gated-SGD + averaging operator (paper Eq. 5, one leaf):
       out[j] = sum_i T[i, j] * (x[i] - eta * theta[i] * g[i])
    x, g: (W, C); t_op: (W, W); theta: (W,)."""
    u = x - eta * theta[:, None].astype(x.dtype) * g
    return jnp.einsum("ij,ic->jc", t_op.astype(x.dtype), u)


def slstm_scan_ref(zx, r_gates, b_gates):
    """Per-head sLSTM recurrence oracle.  zx: (B, T, H, 4*hd) gate
    pre-activations laid out [i|f|z|o] per head; r_gates: (H, hd, 4*hd);
    b_gates: (H, 4*hd) -> h: (B, T, H, hd)."""
    b, t, h, hd4 = zx.shape
    hd = hd4 // 4
    zf32 = zx.astype(jnp.float32)

    def step(state, z_t):
        hh, c, n, m = state
        rec = jnp.einsum("bhk,hkg->bhg", hh, r_gates.astype(jnp.float32))
        z = z_t + rec + b_gates.astype(jnp.float32)
        zi, zf, zz, zo = (z[..., :hd], z[..., hd:2 * hd],
                          z[..., 2 * hd:3 * hd], z[..., 3 * hd:])
        logf = jax.nn.log_sigmoid(zf)
        m_new = jnp.maximum(logf + m, zi)
        i_t = jnp.exp(zi - m_new)
        f_t = jnp.exp(logf + m - m_new)
        c_new = f_t * c + i_t * jnp.tanh(zz)
        n_new = f_t * n + i_t
        h_new = jax.nn.sigmoid(zo) * c_new / jnp.maximum(n_new, 1e-6)
        return (h_new, c_new, n_new, m_new), h_new

    z0 = jnp.zeros((b, h, hd), jnp.float32)
    state0 = (z0, z0, jnp.ones_like(z0), jnp.zeros_like(z0))
    _, hs = jax.lax.scan(step, state0, jnp.moveaxis(zf32, 1, 0))
    return jnp.moveaxis(hs, 0, 1).astype(zx.dtype)
