"""The main-path Pallas kernels compile for a TPU v5e, without the chip.

Each case lowers a kernel with ``interpret=False`` for one chip of a
described ``v5e:2x2`` topology and compiles it with the TPU compiler that
ships with jaxlib.  This catches what interpret mode cannot: block shapes
the chip's tiling refuses, too much VMEM, kernels that cannot be
partitioned.  Nothing runs; results are checked by tests/test_kernels.py.

The topology is described inside a fixture, never at import, so every
test worker collects the same tests and only the worker running this file
loads the TPU library.  Keep these cases in this one file.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import flash_attention as fa
from repro.kernels import hier_mix

CUSTOM_CALL = 'custom_call_target="tpu_custom_call"'

# (n_heads, n_kv_heads, head_dim) at published widths
ATTN_SHAPES = {"qwen2-0.5b": (14, 2, 64), "qwen3-1.7b": (16, 8, 128)}


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means "cannot describe"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _compiled_calls(fn, *args) -> int:
    """Number of Pallas TPU kernels in the compiled program."""
    return jax.jit(fn).lower(*args).compile().as_text().count(CUSTOM_CALL)


def _sds(sharding, shape, dtype=jnp.bfloat16):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


@pytest.mark.parametrize("arch", sorted(ATTN_SHAPES))
@pytest.mark.parametrize("pass_", ["fwd", "bwd"])
def test_flash_attention_compiles(one_chip, arch, pass_):
    """Forward, and backward (the dq and dkv kernels), at seq 1024."""
    h, hkv, hd = ATTN_SHAPES[arch]
    b, t = 1, 1024
    q = _sds(one_chip, (b, t, h, hd))
    kv = _sds(one_chip, (b, t, hkv, hd))
    if pass_ == "fwd":
        n = _compiled_calls(lambda q, k, v: fa.flash_attention_fwd_res(
            q, k, v, interpret=False), q, kv, kv)
        assert n == 1
    else:
        lse = _sds(one_chip, (b, h, t), jnp.float32)
        n = _compiled_calls(lambda q, k, v, o, lse, do: fa.flash_attention_bwd(
            q, k, v, o, lse, do, interpret=False), q, kv, kv, q, lse, q)
        assert n == 2


def test_flash_decode_paged_compiles(one_chip):
    """Paged decode over a qwen2-0.5b pool of 16-token blocks."""
    h, hkv, hd = ATTN_SHAPES["qwen2-0.5b"]
    batch, num_blocks, block_size, max_blocks = 8, 256, 16, 16
    pool = _sds(one_chip, (num_blocks, hkv, block_size, hd))
    n = _compiled_calls(
        lambda q, kp, vp, tbl, lens: fa.flash_decode_paged(
            q, kp, vp, tbl, lens, interpret=False),
        _sds(one_chip, (batch, h, hd)), pool, pool,
        _sds(one_chip, (batch, max_blocks), jnp.int32),
        _sds(one_chip, (batch,), jnp.int32))
    assert n == 1


def test_hier_mix_packed_compiles(one_chip):
    """One fused update+mix launch for 20 workers over ~1M packed lanes."""
    w = 20
    tree = {"w": _sds(one_chip, (w, 1024, 1000), jnp.float32),
            "b": _sds(one_chip, (w, 1000), jnp.float32)}
    n = _compiled_calls(
        lambda x, g, op, theta: hier_mix.hier_mix_packed(
            x, g, op, theta, 0.05, interpret=False),
        tree, tree, _sds(one_chip, (w, w), jnp.float32),
        _sds(one_chip, (w,), jnp.float32))
    assert n == 1
