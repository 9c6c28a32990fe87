"""Block assembly: pattern-driven super-blocks scanned over depth.

A *super-block* is one repetition of ``cfg.pattern`` (e.g. ``("attn",)`` for
dense models, ``("mamba",)*3 + ("attn",) + ("mamba",)*4`` for Jamba, or
``("mlstm", "slstm")`` for xLSTM).  Parameters for all
``cfg.num_super_blocks`` repetitions are stacked on a leading axis and the
depth loop is a single `jax.lax.scan` — keeping compiled HLO size independent
of depth (crucial for 64–94-layer dry-runs) and enabling one remat decision
per super-block.
"""
from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig
from repro.models import attention as attn_mod
from repro.models import mamba as mamba_mod
from repro.models import xlstm as xlstm_mod
from repro.models import moe as moe_mod
from repro.models.layers import init_mlp, init_norm, mlp_apply, norm_apply

PyTree = Any


def _position_uses_moe(cfg: ArchConfig, pos: int) -> bool:
    return cfg.n_experts > 0 and pos in cfg.moe_positions


def _has_ffn(cfg: ArchConfig, kind: str, pos: int) -> bool:
    if kind in ("mlstm", "slstm"):
        return False                      # xLSTM blocks subsume the FFN
    return cfg.d_ff > 0 or _position_uses_moe(cfg, pos)


# ----------------------------------------------------------------- init
_MIXER_INIT = {
    "attn": attn_mod.init_attention,
    "mamba": mamba_mod.init_mamba,
    "mlstm": xlstm_mod.init_mlstm,
    "slstm": xlstm_mod.init_slstm,
}


def init_super_block(key, cfg: ArchConfig) -> PyTree:
    """Params for one repetition of the pattern (dict keyed by position)."""
    blocks = {}
    for pos, kind in enumerate(cfg.pattern):
        key, k1, k2 = jax.random.split(key, 3)
        b = {"norm1": init_norm(cfg), "mixer": _MIXER_INIT[kind](k1, cfg)}
        if _has_ffn(cfg, kind, pos):
            b["norm2"] = init_norm(cfg)
            if _position_uses_moe(cfg, pos):
                b["ffn"] = moe_mod.init_moe(k2, cfg)
            else:
                b["ffn"] = init_mlp(k2, cfg)
        blocks[f"pos{pos}"] = b
    return blocks


def init_stacked_blocks(key, cfg: ArchConfig) -> PyTree:
    keys = jax.random.split(key, cfg.num_super_blocks)
    return jax.vmap(lambda k: init_super_block(k, cfg))(keys)


# ----------------------------------------------------------------- train fwd
def super_block_train(params: PyTree, x: jnp.ndarray, cfg: ArchConfig,
                      positions: jnp.ndarray, impl: str = "xla"
                      ) -> tuple[jnp.ndarray, jnp.ndarray]:
    """x: (B, S, d) -> (y, aux_loss_sum)."""
    aux = jnp.zeros((), jnp.float32)
    for pos, kind in enumerate(cfg.pattern):
        b = params[f"pos{pos}"]
        h = norm_apply(b["norm1"], x, cfg)
        if kind == "attn":
            mixed = attn_mod.attention_train(b["mixer"], h, cfg, positions, impl)
        elif kind == "mamba":
            mixed = mamba_mod.mamba_train(b["mixer"], h, cfg)
        elif kind == "mlstm":
            mixed = xlstm_mod.mlstm_train(b["mixer"], h, cfg)
        else:
            mixed = xlstm_mod.slstm_train(b["mixer"], h, cfg, impl=impl)
        x = x + mixed
        if _has_ffn(cfg, kind, pos):
            h = norm_apply(b["norm2"], x, cfg)
            if _position_uses_moe(cfg, pos):
                y, a = moe_mod.moe_apply(b["ffn"], h, cfg)
                aux = aux + a
            else:
                y = mlp_apply(b["ffn"], h, cfg)
            x = x + y
    return x, aux


def stack_train(stacked: PyTree, x: jnp.ndarray, cfg: ArchConfig,
                positions: jnp.ndarray, *, impl: str = "xla",
                remat: str = "none") -> tuple[jnp.ndarray, jnp.ndarray]:
    def body(carry, blk_params):
        x, aux = carry
        y, a = super_block_train(blk_params, x, cfg, positions, impl)
        return (y, aux + a), None

    if remat == "full":
        body = jax.checkpoint(body)
    elif remat == "dots":
        body = jax.checkpoint(
            body, policy=jax.checkpoint_policies.checkpoint_dots_with_no_batch_dims)
    (x, aux), _ = jax.lax.scan(body, (x, jnp.zeros((), jnp.float32)), stacked)
    return x, aux


# ------------------------------------------------------------------ prefill
def _require_attn_only(cfg: ArchConfig, what: str) -> None:
    if any(kind != "attn" for kind in cfg.pattern):
        raise NotImplementedError(
            f"{what} supports attention-only patterns; {cfg.name} has "
            f"pattern {cfg.pattern} (recurrent blocks would need their "
            "final state threaded out of the batched forward)")


def super_block_prefill(params: PyTree, x: jnp.ndarray, cfg: ArchConfig,
                        positions: jnp.ndarray, impl: str = "xla"
                        ) -> tuple[jnp.ndarray, PyTree]:
    """Training-path math over the whole prompt, additionally capturing
    each attention position's projected k/v (the serving prefill).
    -> (y, {"pos{i}": (k, v)})."""
    kvs = {}
    for pos, kind in enumerate(cfg.pattern):
        b = params[f"pos{pos}"]
        h = norm_apply(b["norm1"], x, cfg)
        mixed, k, v = attn_mod.attention_prefill(b["mixer"], h, cfg,
                                                 positions, impl)
        kvs[f"pos{pos}"] = (k, v)
        x = x + mixed
        if _has_ffn(cfg, kind, pos):
            h = norm_apply(b["norm2"], x, cfg)
            if _position_uses_moe(cfg, pos):
                y, _ = moe_mod.moe_apply(b["ffn"], h, cfg)
            else:
                y = mlp_apply(b["ffn"], h, cfg)
            x = x + y
    return x, kvs


def stack_prefill(stacked: PyTree, x: jnp.ndarray, cfg: ArchConfig,
                  positions: jnp.ndarray, *, impl: str = "xla"
                  ) -> tuple[jnp.ndarray, PyTree]:
    """One batched forward over the prompt, returning the final hidden
    states AND every layer's k/v stacked on the super-block axis:
    {"pos{i}": (k, v)} with leaves (n_sb, B, S, Hkv, hd).  The caller owns
    the cache layout (rotating dense buffer or paged block pool)."""
    _require_attn_only(cfg, "stack_prefill")

    def body(x, blk_params):
        y, kvs = super_block_prefill(blk_params, x, cfg, positions, impl)
        return y, kvs

    x, kv_stacked = jax.lax.scan(body, x, stacked)
    return x, kv_stacked


# -------------------------------------------------------------- paged decode
def init_stacked_paged_state(cfg: ArchConfig, num_blocks: int,
                             block_size: int) -> PyTree:
    """Per-layer paged block pools, stacked on the super-block axis:
    {"pos{i}": {"k_pool", "v_pool"}} with leaves
    (n_sb, num_blocks, Hkv, block_size, hd)."""
    from repro.serve import kv_cache as kvc

    _require_attn_only(cfg, "paged decode")
    pc = kvc.PagedCacheConfig(block_size=block_size, num_blocks=num_blocks,
                              max_len=block_size)  # geometry only
    one = {f"pos{pos}": kvc.init_layer_pools(
        pc, cfg.n_kv_heads, cfg.resolved_head_dim,
        jnp.dtype(cfg.compute_dtype)) for pos in range(len(cfg.pattern))}
    n = cfg.num_super_blocks
    return jax.tree.map(lambda z: jnp.broadcast_to(z[None], (n,) + z.shape),
                        one)


def super_block_paged_decode(params: PyTree, x: jnp.ndarray, cfg: ArchConfig,
                             state: PyTree, block_tables: jnp.ndarray,
                             lengths: jnp.ndarray, impl: str = "xla"
                             ) -> tuple[jnp.ndarray, PyTree]:
    new_state = {}
    for pos, kind in enumerate(cfg.pattern):
        b, s = params[f"pos{pos}"], state[f"pos{pos}"]
        h = norm_apply(b["norm1"], x, cfg)
        mixed, ns = attn_mod.attention_paged_decode(
            b["mixer"], h, cfg, s, block_tables, lengths, impl)
        new_state[f"pos{pos}"] = ns
        x = x + mixed
        if _has_ffn(cfg, kind, pos):
            h = norm_apply(b["norm2"], x, cfg)
            if _position_uses_moe(cfg, pos):
                y, _ = moe_mod.moe_apply(b["ffn"], h, cfg)
            else:
                y = mlp_apply(b["ffn"], h, cfg)
            x = x + y
    return x, new_state


def stack_paged_decode(stacked: PyTree, stacked_state: PyTree,
                       x: jnp.ndarray, cfg: ArchConfig,
                       block_tables: jnp.ndarray, lengths: jnp.ndarray, *,
                       impl: str = "xla") -> tuple[jnp.ndarray, PyTree]:
    def body(x, blk):
        blk_params, blk_state = blk
        y, ns = super_block_paged_decode(blk_params, x, cfg, blk_state,
                                         block_tables, lengths, impl)
        return y, ns

    x, new_states = jax.lax.scan(body, x, (stacked, stacked_state))
    return x, new_states


# ------------------------------------------------------------------- decode
def init_super_block_state(cfg: ArchConfig, batch: int, max_len: int) -> PyTree:
    st = {}
    for pos, kind in enumerate(cfg.pattern):
        if kind == "attn":
            st[f"pos{pos}"] = attn_mod.init_cache(cfg, batch, max_len)
        elif kind == "mamba":
            st[f"pos{pos}"] = mamba_mod.init_mamba_state(cfg, batch)
        elif kind == "mlstm":
            st[f"pos{pos}"] = xlstm_mod.init_mlstm_state(cfg, batch)
        else:
            st[f"pos{pos}"] = xlstm_mod.init_slstm_state(cfg, batch)
    return st


def init_stacked_state(cfg: ArchConfig, batch: int, max_len: int) -> PyTree:
    one = init_super_block_state(cfg, batch, max_len)
    n = cfg.num_super_blocks
    return jax.tree.map(lambda z: jnp.broadcast_to(z[None], (n,) + z.shape), one)


def super_block_decode(params: PyTree, x: jnp.ndarray, cfg: ArchConfig,
                       cur: jnp.ndarray, state: PyTree
                       ) -> tuple[jnp.ndarray, PyTree]:
    new_state = {}
    for pos, kind in enumerate(cfg.pattern):
        b, s = params[f"pos{pos}"], state[f"pos{pos}"]
        h = norm_apply(b["norm1"], x, cfg)
        if kind == "attn":
            mixed, ns = attn_mod.attention_decode(b["mixer"], h, cfg, cur, s)
        elif kind == "mamba":
            mixed, ns = mamba_mod.mamba_decode(b["mixer"], h, cfg, s)
        elif kind == "mlstm":
            mixed, ns = xlstm_mod.mlstm_decode(b["mixer"], h, cfg, s)
        else:
            mixed, ns = xlstm_mod.slstm_decode(b["mixer"], h, cfg, s)
        new_state[f"pos{pos}"] = ns
        x = x + mixed
        if _has_ffn(cfg, kind, pos):
            h = norm_apply(b["norm2"], x, cfg)
            if _position_uses_moe(cfg, pos):
                y, _ = moe_mod.moe_apply(b["ffn"], h, cfg)
            else:
                y = mlp_apply(b["ffn"], h, cfg)
            x = x + y
    return x, new_state


def stack_decode(stacked: PyTree, stacked_state: PyTree, x: jnp.ndarray,
                 cfg: ArchConfig, cur: jnp.ndarray
                 ) -> tuple[jnp.ndarray, PyTree]:
    def body(x, blk):
        blk_params, blk_state = blk
        y, ns = super_block_decode(blk_params, x, cfg, cur, blk_state)
        return y, ns

    x, new_states = jax.lax.scan(body, x, (stacked, stacked_state))
    return x, new_states
