"""Paged/block KV cache for the batched serving engine.

Layout: one shared pool of ``num_blocks`` fixed-size blocks per attention
layer, shape (num_blocks, Hkv, block_size, head_dim) — head-major, so one
kv head of one block is a contiguous (block_size, head_dim) tile, the unit
the flash-decode kernel fetches.  A request's cache is a row of the BLOCK
TABLE — (max_batch, max_blocks_per_seq) int32 physical block ids — so
requests of different lengths batch together and a finished request's
blocks return to the free list for immediate reuse.  Logical token
position p of lane b lives at
``pool[table[b, p // block_size], :, p % block_size]``.

Everything device-side here is functional (pure jnp in, new arrays out) so
the write helpers compose inside jitted/scanned model code; the
`BlockAllocator` is the host-side free list the engine drives admission
with.  Writes for inactive lanes / padded positions are routed to a
one-past-the-end block id and dropped (``.at[].set(mode="drop")``) —
no masking data dependencies inside the kernel path.
"""
from __future__ import annotations

import dataclasses

import jax.numpy as jnp


@dataclasses.dataclass(frozen=True)
class PagedCacheConfig:
    """Geometry of the block pool (shared by every attention layer)."""
    block_size: int = 16          # tokens per block
    num_blocks: int = 128         # physical blocks in the pool
    max_len: int = 256            # max context (prompt + generated) per seq

    def __post_init__(self):
        if self.block_size <= 0 or self.num_blocks <= 0:
            raise ValueError("block_size and num_blocks must be positive")
        if self.max_len > self.block_size * self.num_blocks:
            raise ValueError(
                f"max_len={self.max_len} cannot fit in the pool "
                f"({self.num_blocks} x {self.block_size} tokens)")

    @property
    def max_blocks_per_seq(self) -> int:
        return -(-self.max_len // self.block_size)

    def blocks_for(self, tokens: int) -> int:
        """Physical blocks a context of ``tokens`` tokens occupies."""
        return -(-tokens // self.block_size)


def init_layer_pools(pc: PagedCacheConfig, n_kv_heads: int, head_dim: int,
                     dtype) -> dict[str, jnp.ndarray]:
    """One attention layer's {k_pool, v_pool}."""
    shape = (pc.num_blocks, n_kv_heads, pc.block_size, head_dim)
    return {"k_pool": jnp.zeros(shape, dtype), "v_pool": jnp.zeros(shape, dtype)}


def _token_write(pool: jnp.ndarray, blk: jnp.ndarray, off: jnp.ndarray,
                 values: jnp.ndarray) -> jnp.ndarray:
    """Scatter ``values`` (N, Hkv, hd) at token slots (blk[n], off[n]) of the
    pool; out-of-range block ids (the drop sentinel) are discarded."""
    return pool.at[blk, :, off].set(values.astype(pool.dtype), mode="drop")


def write_token_kv(k_pool: jnp.ndarray, v_pool: jnp.ndarray,
                   k: jnp.ndarray, v: jnp.ndarray,
                   block_tables: jnp.ndarray, positions: jnp.ndarray
                   ) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Decode-phase write: one new token per lane.

    k/v: (B, Hkv, hd); positions: (B,) absolute position of the new token,
    negative = inactive lane (write dropped)."""
    nb, bs = k_pool.shape[0], k_pool.shape[2]
    safe = jnp.maximum(positions, 0)
    blk = jnp.take_along_axis(block_tables, (safe // bs)[:, None],
                              axis=1)[:, 0]
    blk = jnp.where(positions >= 0, blk, nb)
    off = safe % bs
    return (_token_write(k_pool, blk, off, k),
            _token_write(v_pool, blk, off, v))


def write_prefill_kv(k_pool: jnp.ndarray, v_pool: jnp.ndarray,
                     k: jnp.ndarray, v: jnp.ndarray,
                     block_tables: jnp.ndarray, plens: jnp.ndarray
                     ) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Prefill-phase write: a whole (padded) prompt per lane in one scatter.

    k/v: (B, S, Hkv, hd) from the batched forward pass; plens: (B,) — only
    positions < plens[b] are written (pad tail dropped)."""
    nb, bs = k_pool.shape[0], k_pool.shape[2]
    b, s = k.shape[:2]
    pos = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32)[None], (b, s))
    blk = jnp.take_along_axis(block_tables, pos // bs, axis=1)     # (B, S)
    blk = jnp.where(pos < plens[:, None], blk, nb).reshape(-1)
    off = (pos % bs).reshape(-1)
    return (_token_write(k_pool, blk, off, k.reshape(b * s, *k.shape[2:])),
            _token_write(v_pool, blk, off, v.reshape(b * s, *v.shape[2:])))


def gather_kv(pool: jnp.ndarray, block_tables: jnp.ndarray) -> jnp.ndarray:
    """Dense view of a paged pool: (B, max_blocks * block_size, Hkv, hd)
    in logical position order (the XLA decode path's input)."""
    b, nmax = block_tables.shape
    _, hkv, bs, hd = pool.shape
    return jnp.swapaxes(pool[block_tables], 2, 3).reshape(
        b, nmax * bs, hkv, hd)


class BlockAllocator:
    """Host-side free list over the physical block ids.

    Allocation is all-or-nothing (a request either gets its full
    worst-case block budget at admission or stays queued), so decode can
    never run out of blocks mid-request.  Freed blocks go back LIFO —
    a finished request's blocks are the next ones reassigned, which the
    block-reuse tests pin down.
    """

    def __init__(self, num_blocks: int):
        self.num_blocks = num_blocks
        self._free = list(range(num_blocks - 1, -1, -1))  # pop() -> block 0 first

    @property
    def available(self) -> int:
        return len(self._free)

    def alloc(self, n: int) -> list[int] | None:
        """n physical blocks, or None (and no change) if not enough free."""
        if n > len(self._free):
            return None
        got = [self._free.pop() for _ in range(n)]
        return got

    def free(self, blocks: list[int]) -> None:
        for blk in blocks:
            if not 0 <= blk < self.num_blocks:
                raise ValueError(f"freeing unknown block {blk}")
            if blk in self._free:
                raise ValueError(f"double free of block {blk}")
            self._free.append(blk)
