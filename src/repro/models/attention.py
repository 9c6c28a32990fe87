"""Grouped-query attention with RoPE variants, qk-norm, QKV-bias, logit
soft-cap, sliding windows, and a rotating-buffer KV cache for decode.

Train/prefill uses either the pure-XLA path (default, used by the dry-run)
or the Pallas flash-attention kernel (``impl="flash"`` / ``"pallas"``, TPU
target, validated in interpret mode).  Both are differentiable: the kernel
path carries a custom VJP through the Pallas backward kernels
(`kernels.flash_attention`), so training steps never fall back to the
XLA attention.
"""
from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ArchConfig
from repro.models import rope as rope_mod
from repro.models.layers import init_norm, norm_apply, trunc_normal
from repro.models.pjit_utils import constraint

PyTree = Any
NEG_INF = -1e30


def init_attention(key, cfg: ArchConfig) -> PyTree:
    d, hd = cfg.d_model, cfg.resolved_head_dim
    dtype = jnp.dtype(cfg.param_dtype)
    ks = jax.random.split(key, 4)
    scale = 1.0 / np.sqrt(d)
    p = {
        "wq": trunc_normal(ks[0], (d, cfg.n_heads, hd), scale, dtype),
        "wk": trunc_normal(ks[1], (d, cfg.n_kv_heads, hd), scale, dtype),
        "wv": trunc_normal(ks[2], (d, cfg.n_kv_heads, hd), scale, dtype),
        "wo": trunc_normal(ks[3], (cfg.n_heads, hd, d),
                           1.0 / np.sqrt(cfg.n_heads * hd), dtype),
    }
    if cfg.qkv_bias:
        p["bq"] = jnp.zeros((cfg.n_heads, hd), dtype)
        p["bk"] = jnp.zeros((cfg.n_kv_heads, hd), dtype)
        p["bv"] = jnp.zeros((cfg.n_kv_heads, hd), dtype)
    if cfg.qk_norm:
        p["q_norm"] = init_norm(cfg, hd)
        p["k_norm"] = init_norm(cfg, hd)
    return p


def _project_qkv(params: PyTree, x: jnp.ndarray, cfg: ArchConfig,
                 positions: jnp.ndarray):
    cdt = jnp.dtype(cfg.compute_dtype)
    x = x.astype(cdt)
    q = jnp.einsum("bsd,dhk->bshk", x, params["wq"].astype(cdt))
    k = jnp.einsum("bsd,dhk->bshk", x, params["wk"].astype(cdt))
    v = jnp.einsum("bsd,dhk->bshk", x, params["wv"].astype(cdt))
    if cfg.qkv_bias:
        q = q + params["bq"].astype(cdt)
        k = k + params["bk"].astype(cdt)
        v = v + params["bv"].astype(cdt)
    if cfg.qk_norm:
        q = norm_apply(params["q_norm"], q, cfg)
        k = norm_apply(params["k_norm"], k, cfg)
    if cfg.rope != "none":
        cos, sin = rope_mod.rope_angles(cfg, positions, cfg.resolved_head_dim)
        q = rope_mod.apply_rope(q, cos, sin)
        k = rope_mod.apply_rope(k, cos, sin)
    q = constraint(q, "act_batch", "mixer_seq", "heads", None)
    k = constraint(k, "act_batch", "mixer_seq", "kv_heads", None)
    v = constraint(v, "act_batch", "mixer_seq", "kv_heads", None)
    return q, k, v


def _sdpa(q, k, v, cfg: ArchConfig, mask) -> jnp.ndarray:
    """Grouped-query attention core. q: (B,T,H,hd), k/v: (B,S,Hkv,hd),
    mask: (B,T,S) or broadcastable boolean (True = attend)."""
    b, t, h, hd = q.shape
    hkv = k.shape[2]
    group = h // hkv
    qg = q.reshape(b, t, hkv, group, hd)
    logits = jnp.einsum("bthgk,bshk->bhgts", qg, k).astype(jnp.float32)
    logits = logits / np.sqrt(hd)
    if cfg.logit_softcap > 0:
        c = cfg.logit_softcap
        logits = c * jnp.tanh(logits / c)
    logits = jnp.where(mask[:, None, None, :, :], logits, NEG_INF)
    probs = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bhgts,bshk->bthgk", probs.astype(v.dtype), v)
    return out.reshape(b, t, h, hd)


def causal_mask(t: int, s: int, window: int, offset: int = 0) -> jnp.ndarray:
    """(t, s) boolean mask. Query i (absolute pos offset+i) may attend to key
    j iff j <= offset+i and, when window > 0, offset+i - j < window."""
    qpos = np.arange(t)[:, None] + offset
    kpos = np.arange(s)[None, :]
    m = kpos <= qpos
    if window > 0:
        m &= (qpos - kpos) < window
    return jnp.asarray(m)


def _sdpa_chunked(q, k, v, cfg: ArchConfig, *, block_q: int = 512) -> jnp.ndarray:
    """Memory-bounded causal attention: lax.scan over query chunks so the
    materialised score tensor is (B, Hkv, G, block_q, S) instead of the full
    (B, Hkv, G, T, S).  Pure XLA (differentiable, dry-run lowerable); same
    numerics contract as `_sdpa`.  Default for long sequences."""
    b, t, h, hd = q.shape
    s = k.shape[1]
    block_q = min(block_q, t)
    pad = -t % block_q
    if pad:
        q = jnp.pad(q, ((0, 0), (0, pad), (0, 0), (0, 0)))
    tp = t + pad
    nq = tp // block_q
    qr = jnp.moveaxis(q.reshape(b, nq, block_q, h, hd), 1, 0)  # (nq,B,bq,h,hd)
    kpos = jnp.arange(s, dtype=jnp.int32)[None, :]

    def body(_, args):
        idx, qc = args
        qpos = idx * block_q + jnp.arange(block_q, dtype=jnp.int32)[:, None]
        mask = kpos <= qpos
        if cfg.sliding_window > 0:
            mask = mask & ((qpos - kpos) < cfg.sliding_window)
        out = _sdpa(qc, k, v, cfg, mask[None])
        return None, out

    _, outs = jax.lax.scan(body, None, (jnp.arange(nq, dtype=jnp.int32), qr))
    out = jnp.moveaxis(outs, 0, 1).reshape(b, tp, h, hd)
    return out[:, :t]


def attention_train(params: PyTree, x: jnp.ndarray, cfg: ArchConfig,
                    positions: jnp.ndarray, impl: str = "xla") -> jnp.ndarray:
    """Full-sequence causal attention (training / prefill)."""
    return attention_prefill(params, x, cfg, positions, impl)[0]


def attention_prefill(params: PyTree, x: jnp.ndarray, cfg: ArchConfig,
                      positions: jnp.ndarray, impl: str = "xla"
                      ) -> tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Full-sequence causal attention that ALSO hands back the projected
    (post-RoPE) k/v so a serving prefill can fill its KV cache from the
    same batched forward pass.  -> (y (B,S,d), k, v (B,S,Hkv,hd))."""
    b, s, _ = x.shape
    q, k, v = _project_qkv(params, x, cfg, positions)
    if impl in ("flash", "pallas"):
        from repro.kernels import ops as kops
        out = kops.flash_attention(q, k, v, causal=True,
                                   window=cfg.sliding_window,
                                   softcap=cfg.logit_softcap)
    elif impl == "chunked" or (impl == "auto" and s >= 2048):
        out = _sdpa_chunked(q, k, v, cfg)
    else:
        mask = causal_mask(s, s, cfg.sliding_window)[None]
        out = _sdpa(q, k, v, cfg, mask)
    out = constraint(out, "act_batch", "mixer_seq", "heads", None)
    cdt = jnp.dtype(cfg.compute_dtype)
    return jnp.einsum("bshk,hkd->bsd", out, params["wo"].astype(cdt)), k, v


# ------------------------------------------------------------------ KV cache
def init_cache(cfg: ArchConfig, batch: int, max_len: int) -> PyTree:
    """Rotating-buffer cache. Window attention keeps only `window` slots —
    O(window) memory, the sub-quadratic mode used for long_500k."""
    buf = min(max_len, cfg.sliding_window) if cfg.sliding_window else max_len
    hd, hkv = cfg.resolved_head_dim, cfg.n_kv_heads
    dt = jnp.dtype(cfg.compute_dtype)
    return {
        "k": jnp.zeros((batch, buf, hkv, hd), dt),
        "v": jnp.zeros((batch, buf, hkv, hd), dt),
        "pos": jnp.full((batch, buf), -1, jnp.int32),
    }


def attention_decode(params: PyTree, x: jnp.ndarray, cfg: ArchConfig,
                     cur: jnp.ndarray, cache: PyTree) -> tuple[jnp.ndarray, PyTree]:
    """One-token decode. x: (B, 1, d); cur: scalar int32 absolute position of
    the new token. Cache slots carry absolute positions for masking, so the
    same code path serves full and sliding-window attention."""
    b = x.shape[0]
    positions = rope_mod.default_positions(cfg, b, 1, offset=cur)
    q, k, v = _project_qkv(params, x, cfg, positions)
    # co-shard q and k/v with the cache layout (kv-head or head_dim on
    # "model") so the attention contraction never moves the cache
    if cfg.decode_coshard:
        q = constraint(q, "act_batch", None, "heads", "kv_hd")
        k = constraint(k, "act_batch", None, "kv_heads", "kv_hd")
        v = constraint(v, "act_batch", None, "kv_heads", "kv_hd")
    buf = cache["k"].shape[1]
    slot = jnp.mod(cur, buf)
    ck = jax.lax.dynamic_update_slice_in_dim(cache["k"], k, slot, axis=1)
    cv = jax.lax.dynamic_update_slice_in_dim(cache["v"], v, slot, axis=1)
    if cfg.decode_coshard:
        ck = constraint(ck, "act_batch", None, "kv_heads", "kv_hd")
        cv = constraint(cv, "act_batch", None, "kv_heads", "kv_hd")
    cpos = jax.lax.dynamic_update_slice_in_dim(
        cache["pos"], jnp.broadcast_to(cur[None, None].astype(jnp.int32), (b, 1)),
        slot, axis=1)
    valid = (cpos >= 0) & (cpos <= cur)
    if cfg.sliding_window:
        valid &= (cur - cpos) < cfg.sliding_window
    mask = valid[:, None, :]                     # (B, T=1, S=buf)
    out = _sdpa(q, ck, cv, cfg, mask)
    cdt = jnp.dtype(cfg.compute_dtype)
    y = jnp.einsum("bshk,hkd->bsd", out, params["wo"].astype(cdt))
    return y, {"k": ck, "v": cv, "pos": cpos}


def fill_cache_from_prefill(cache: PyTree, k: jnp.ndarray, v: jnp.ndarray,
                            cfg: ArchConfig) -> PyTree:
    """Fill a rotating-buffer decode cache from a batched prefill's k/v.

    k/v: (B, S, Hkv, hd) — the projected prompt keys/values for absolute
    positions 0..S-1.  Writes land exactly where S sequential
    `attention_decode` steps would have put them (slot = pos % buf; only
    the last ``buf`` positions survive a sliding-window rotation)."""
    s = k.shape[1]
    buf = cache["k"].shape[1]
    m = min(s, buf)
    pos = jnp.arange(s - m, s, dtype=jnp.int32)
    slots = pos % buf
    ck = cache["k"].at[:, slots].set(k[:, s - m:].astype(cache["k"].dtype))
    cv = cache["v"].at[:, slots].set(v[:, s - m:].astype(cache["v"].dtype))
    cpos = cache["pos"].at[:, slots].set(
        jnp.broadcast_to(pos[None], (cache["pos"].shape[0], m)))
    return {"k": ck, "v": cv, "pos": cpos}


# -------------------------------------------------------- paged decode
def attention_paged_decode(params: PyTree, x: jnp.ndarray, cfg: ArchConfig,
                           pools: PyTree, block_tables: jnp.ndarray,
                           lengths: jnp.ndarray, impl: str = "xla"
                           ) -> tuple[jnp.ndarray, PyTree]:
    """One-token decode against the paged block pool.

    x: (B, 1, d); pools: {"k_pool", "v_pool"} (num_blocks, Hkv, bs, hd);
    block_tables: (B, max_blocks) int32; lengths: (B,) int32 — context
    length INCLUDING the token being decoded (it sits at position
    ``lengths - 1``; 0 marks an inactive lane, whose write is dropped and
    whose output is garbage the engine ignores).

    ``impl="flash"|"pallas"`` reads through the Pallas flash-decode kernel
    (split-KV + block-table indirection); ``"xla"`` gathers the table into
    a dense view and reuses `_sdpa` — the parity oracle.
    """
    from repro.serve import kv_cache as kvc

    b = x.shape[0]
    positions = rope_mod.default_positions(
        cfg, b, 1, offset=jnp.maximum(lengths - 1, 0)[:, None])
    q, k, v = _project_qkv(params, x, cfg, positions)
    kp, vp = kvc.write_token_kv(pools["k_pool"], pools["v_pool"],
                                k[:, 0], v[:, 0], block_tables, lengths - 1)
    if impl in ("flash", "pallas"):
        from repro.kernels import ops as kops
        out = kops.flash_decode(q[:, 0], kp, vp, block_tables, lengths,
                                window=cfg.sliding_window,
                                softcap=cfg.logit_softcap)[:, None]
    elif impl == "xla":
        ck = kvc.gather_kv(kp, block_tables)
        cv = kvc.gather_kv(vp, block_tables)
        s = ck.shape[1]
        kpos = jnp.arange(s, dtype=jnp.int32)[None, :]
        mask = kpos < lengths[:, None]
        if cfg.sliding_window:
            mask &= ((lengths - 1)[:, None] - kpos) < cfg.sliding_window
        out = _sdpa(q, ck, cv, cfg, mask[:, None, :])
    else:
        raise ValueError(f"unknown impl {impl!r} (xla | flash | pallas)")
    cdt = jnp.dtype(cfg.compute_dtype)
    y = jnp.einsum("bshk,hkd->bsd", out, params["wo"].astype(cdt))
    return y, {"k_pool": kp, "v_pool": vp}
