"""Pallas kernel validation: shape/dtype sweeps against the ref.py pure-jnp
oracles, run in interpret mode on CPU (the kernel bodies execute in Python).

The hypothesis property sweeps skip when hypothesis is absent (pip install
-e .[dev]); the deterministic forward checks and ALL gradient-correctness
tests (`jax.grad` straight through the custom-vjp Pallas backward kernels
vs `jax.grad` of the pure-JAX references) run everywhere.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

try:                        # property sweeps only; everything else runs bare
    from hypothesis import given, settings, strategies as st
except ImportError:         # pragma: no cover - exercised in slim containers
    given = settings = st = None

from repro.kernels import ref
from repro.kernels.flash_attention import (flash_attention_bwd,
                                           flash_attention_fwd,
                                           flash_attention_fwd_res)
from repro.kernels.hier_mix import hier_mix_chunks
from repro.kernels import ops as kops


def _qkv(key, b, t, s, h, hkv, hd, dtype):
    kq, kk, kv = jax.random.split(key, 3)
    q = jax.random.normal(kq, (b, t, h, hd), jnp.float32).astype(dtype)
    k = jax.random.normal(kk, (b, s, hkv, hd), jnp.float32).astype(dtype)
    v = jax.random.normal(kv, (b, s, hkv, hd), jnp.float32).astype(dtype)
    return q, k, v


def _tol(dtype):
    return dict(atol=2e-2, rtol=2e-2) if dtype == jnp.bfloat16 else \
        dict(atol=2e-5, rtol=2e-5)


if st is not None:
    @settings(max_examples=25, deadline=None)
    @given(st.data())
    def test_flash_attention_sweep(data):
        b = data.draw(st.sampled_from([1, 2]))
        t = data.draw(st.sampled_from([17, 64, 128, 200]))
        hkv = data.draw(st.sampled_from([1, 2, 4]))
        group = data.draw(st.sampled_from([1, 2, 4]))
        hd = data.draw(st.sampled_from([32, 64, 80, 128]))
        dtype = data.draw(st.sampled_from([jnp.float32, jnp.bfloat16]))
        window = data.draw(st.sampled_from([0, 16, 64]))
        softcap = data.draw(st.sampled_from([0.0, 20.0]))
        bq = data.draw(st.sampled_from([32, 128]))
        q, k, v = _qkv(jax.random.PRNGKey(b * t + hd), b, t, t, hkv * group,
                       hkv, hd, dtype)
        out = flash_attention_fwd(q, k, v, causal=True, window=window,
                                  softcap=softcap, block_q=bq, block_kv=bq,
                                  interpret=True)
        want = ref.flash_attention_ref(q, k, v, causal=True, window=window,
                                       softcap=softcap)
        np.testing.assert_allclose(np.asarray(out, np.float32),
                                   np.asarray(want, np.float32), **_tol(dtype))
else:
    @pytest.mark.skip(reason="property sweep needs hypothesis "
                      "(pip install -e .[dev])")
    def test_flash_attention_sweep():
        pass


def test_flash_attention_cross_attention_lengths():
    """T != S (prefix attending a longer key sequence), non-causal."""
    q, k, v = _qkv(jax.random.PRNGKey(3), 2, 48, 96, 4, 2, 64, jnp.float32)
    out = flash_attention_fwd(q, k, v, causal=False, block_q=32, block_kv=32,
                              interpret=True)
    want = ref.flash_attention_ref(q, k, v, causal=False)
    np.testing.assert_allclose(out, want, atol=2e-5)


def test_flash_attention_fully_masked_rows_zero():
    """Sliding window far smaller than the sequence: early tiles are skipped
    entirely (pl.when) yet rows keep finite outputs."""
    q, k, v = _qkv(jax.random.PRNGKey(4), 1, 256, 256, 2, 2, 64, jnp.float32)
    out = flash_attention_fwd(q, k, v, causal=True, window=32, block_q=64,
                              block_kv=64, interpret=True)
    want = ref.flash_attention_ref(q, k, v, causal=True, window=32)
    assert np.isfinite(np.asarray(out)).all()
    np.testing.assert_allclose(out, want, atol=2e-5)


# ------------------------------------------------- flash attention backward
# every forward feature combo: causal/window masking, GQA groups, softcap,
# head_dim {64, 80, 128} (80 exercises the pad-to-128 path), bf16 + f32
FLASH_GRAD_CASES = [
    # (t, hkv, group, hd, window, softcap, causal, dtype)
    (48, 2, 1, 64, 0, 0.0, True, jnp.float32),
    (48, 2, 2, 64, 16, 0.0, True, jnp.float32),      # GQA + sliding window
    (48, 2, 2, 80, 0, 0.0, True, jnp.float32),       # padded head_dim
    (33, 1, 4, 128, 0, 20.0, True, jnp.float32),     # softcap + odd T
    (48, 2, 1, 64, 0, 0.0, False, jnp.float32),      # non-causal
    (48, 2, 2, 64, 0, 0.0, True, jnp.bfloat16),
    (48, 2, 2, 80, 16, 20.0, True, jnp.bfloat16),    # everything at once
]


@pytest.mark.parametrize(
    "t,hkv,group,hd,window,softcap,causal,dtype", FLASH_GRAD_CASES,
    ids=lambda v: str(getattr(v, "__name__", v)))
def test_flash_attention_grad_sweep(t, hkv, group, hd, window, softcap,
                                    causal, dtype):
    """jax.grad straight through the Pallas backward kernels (interpret
    mode) vs jax.grad of the pure-jnp reference, for every forward feature
    combo."""
    q, k, v = _qkv(jax.random.PRNGKey(t + hd + group), 2, t, t, hkv * group,
                   hkv, hd, dtype)

    def f_kernel(q_, k_, v_):
        out = kops.flash_attention(q_, k_, v_, causal, window, softcap)
        return (out.astype(jnp.float32) ** 2).sum()

    def f_ref(q_, k_, v_):
        out = ref.flash_attention_ref(q_, k_, v_, causal=causal,
                                      window=window, softcap=softcap)
        return (out.astype(jnp.float32) ** 2).sum()

    g1 = jax.grad(f_kernel, argnums=(0, 1, 2))(q, k, v)
    g2 = jax.grad(f_ref, argnums=(0, 1, 2))(q, k, v)
    tol = dict(atol=5e-2, rtol=5e-2) if dtype == jnp.bfloat16 else \
        dict(atol=2e-4, rtol=2e-3)
    for a, b, name in zip(g1, g2, ("dq", "dk", "dv")):
        assert a.dtype == b.dtype == dtype
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32),
                                   err_msg=name, **tol)


def test_flash_attention_grad_matches_ref():
    """ops.flash_attention carries a custom VJP through the Pallas backward
    kernels — gradients must match the pure-jnp path."""
    q, k, v = _qkv(jax.random.PRNGKey(5), 1, 32, 32, 2, 1, 32, jnp.float32)

    def f_kernel(q, k, v):
        return (kops.flash_attention(q, k, v, True, 0, 0.0) ** 2).sum()

    def f_ref(q, k, v):
        return (ref.flash_attention_ref(q, k, v, causal=True) ** 2).sum()

    g1 = jax.grad(f_kernel, argnums=(0, 1, 2))(q, k, v)
    g2 = jax.grad(f_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g1, g2):
        np.testing.assert_allclose(a, b, atol=1e-4, rtol=1e-4)


def test_flash_attention_head_dim_80_pad_lanes_exact_zero():
    """Regression (head_dim 80 -> padded to 128): feeding the backward
    kernels inputs that are zero in the pad lanes must yield gradients that
    are EXACTLY zero there — that exactness is what makes the wrapper's
    slice-off correct."""
    hd, hd_pad = 80, 128
    q, k, v = _qkv(jax.random.PRNGKey(7), 1, 32, 32, 4, 2, hd_pad,
                   jnp.float32)
    lanes = jnp.arange(hd_pad) < hd
    q, k, v = (x * lanes for x in (q, k, v))
    o, lse = flash_attention_fwd_res(q, k, v, causal=True, block_q=16,
                                     block_kv=16, interpret=True)
    do = jax.random.normal(jax.random.PRNGKey(8), o.shape) * lanes
    dq, dk, dv = flash_attention_bwd(q, k, v, o, lse, do, causal=True,
                                     block_q=16, block_kv=16, interpret=True)
    for g, name in ((dq, "dq"), (dk, "dk"), (dv, "dv")):
        pad = np.asarray(g[..., hd:])
        assert (pad == 0.0).all(), f"{name} pad lanes not exactly zero"
    # and the public wrapper at true head_dim 80 matches the reference
    qs, ks, vs = q[..., :hd], k[..., :hd], v[..., :hd]
    g1 = jax.grad(lambda a, b, c: (kops.flash_attention(
        a, b, c, True, 0, 0.0) ** 2).sum(), argnums=(0, 1, 2))(qs, ks, vs)
    g2 = jax.grad(lambda a, b, c: (ref.flash_attention_ref(
        a, b, c, causal=True) ** 2).sum(), argnums=(0, 1, 2))(qs, ks, vs)
    for a, b in zip(g1, g2):
        np.testing.assert_allclose(a, b, atol=2e-4, rtol=2e-3)


def test_attention_train_flash_grads_match_xla():
    """Model-level: jax.grad of `attention_train` through the kernel path
    (projections + RoPE + flash custom-vjp) vs the pure-XLA path."""
    from repro.configs.registry import get_smoke_config
    from repro.models import attention as attn_mod
    from repro.models import rope as rope_mod
    cfg = dataclasses.replace(get_smoke_config("qwen2-0.5b"),
                              param_dtype="float32", compute_dtype="float32")
    params = attn_mod.init_attention(jax.random.PRNGKey(0), cfg)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 24, cfg.d_model))
    pos = rope_mod.default_positions(cfg, 2, 24)

    def loss(impl):
        return lambda p_, x_: (attn_mod.attention_train(
            p_, x_, cfg, pos, impl) ** 2).sum()

    g_f = jax.grad(loss("flash"), argnums=(0, 1))(params, x)
    g_x = jax.grad(loss("xla"), argnums=(0, 1))(params, x)
    for a, b in zip(jax.tree.leaves(g_f), jax.tree.leaves(g_x)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=2e-3, rtol=2e-3)


if st is not None:
    @settings(max_examples=25, deadline=None)
    @given(st.data())
    def test_hier_mix_sweep(data):
        w = data.draw(st.sampled_from([1, 2, 4, 9, 16]))
        c = data.draw(st.sampled_from([1, 7, 128, 513, 1000]))
        dtype = data.draw(st.sampled_from([jnp.float32, jnp.bfloat16]))
        eta = data.draw(st.sampled_from([0.0, 0.1, 1.0]))
        bc = data.draw(st.sampled_from([128, 512]))
        key = jax.random.PRNGKey(w * c)
        x = jax.random.normal(key, (w, c), jnp.float32).astype(dtype)
        g = jax.random.normal(jax.random.fold_in(key, 1), (w, c),
                              jnp.float32).astype(dtype)
        t_op = jax.nn.softmax(
            jax.random.normal(jax.random.fold_in(key, 2), (w, w)), axis=0)
        theta = (jax.random.uniform(jax.random.fold_in(key, 3), (w,)) > 0.4
                 ).astype(jnp.float32)
        out = hier_mix_chunks(x, g, t_op, theta, eta, block_c=bc,
                              interpret=True)
        want = ref.hier_mix_ref(x, g, t_op, theta, eta)
        np.testing.assert_allclose(np.asarray(out, np.float32),
                                   np.asarray(want, np.float32), **_tol(dtype))
else:
    @pytest.mark.skip(reason="property sweep needs hypothesis "
                      "(pip install -e .[dev])")
    def test_hier_mix_sweep():
        pass


def test_hier_mix_awkward_shape_is_tile_aligned():
    """(20, 37): neither dim matches the TPU tile grid ((8, 128) f32 /
    (16, 128) bf16) — the kernel must pad W to a sublane multiple and C to a
    lane multiple instead of emitting non-aligned blocks that only work in
    interpret mode."""
    from repro.kernels.hier_mix import _round_up
    w, c = 20, 37
    key = jax.random.PRNGKey(6)
    t_op = jax.nn.softmax(jax.random.normal(key, (w, w)), axis=0)
    theta = (jax.random.uniform(jax.random.fold_in(key, 1), (w,)) > 0.3
             ).astype(jnp.float32)
    for dtype, sub in ((jnp.float32, 8), (jnp.bfloat16, 16)):
        assert _round_up(w, sub) % sub == 0 and _round_up(c, 128) % 128 == 0
        x = jax.random.normal(jax.random.fold_in(key, 2), (w, c),
                              jnp.float32).astype(dtype)
        g = jax.random.normal(jax.random.fold_in(key, 3), (w, c),
                              jnp.float32).astype(dtype)
        out = hier_mix_chunks(x, g, t_op, theta, 0.1, interpret=True)
        assert out.shape == (w, c) and out.dtype == dtype
        want = ref.hier_mix_ref(x, g, t_op, theta, 0.1)
        np.testing.assert_allclose(np.asarray(out, np.float32),
                                   np.asarray(want, np.float32),
                                   **_tol(dtype))


def test_simulator_pallas_and_xla_carries_stay_equivalent():
    """The simulator's two backends must advance the SAME carry: params
    within tolerance and the engine-owned per-worker update counts exactly —
    the Pallas branch folds the gated update into the kernel but may not
    freeze `opt_state['counts']` at zero."""
    from repro.core import baselines
    from repro.core.hierarchy import MLLSchedule
    from repro.core.simulator import (SimConfig, init_sim_carry, make_step_fn,
                                      _phase_ids, replicate)
    from repro.data.pipeline import make_classification

    rates = [1.0, 0.8, 0.6, 0.9, 1.0, 0.7, 0.5, 1.0]
    net, _ = baselines.mll_sgd("ring", [4, 4], tau=3, q=2,
                               worker_rates=rates)
    sched = MLLSchedule(tau=3, q=2)
    data = make_classification(8, 64, dim=6, num_classes=3, test_size=16)

    def loss_fn(p, batch):
        logits = batch["x"] @ p["w"]
        lse = jax.nn.logsumexp(logits, axis=-1)
        gold = jnp.take_along_axis(logits, batch["y"][:, None], axis=1)[:, 0]
        return (lse - gold).mean()

    stacked = replicate({"w": jnp.zeros((6, 3))}, 8)
    op_ids = jnp.asarray(_phase_ids(sched, 0, 12))
    carries = {}
    for kernel in ("xla", "pallas"):
        cfg = SimConfig(eta=0.1, batch_size=8, kernel=kernel)
        step = make_step_fn(loss_fn, net, cfg)
        carries[kernel] = step(init_sim_carry(stacked, cfg, seed=0),
                               data.worker_data(), op_ids)
    px, pk = carries["xla"][0], carries["pallas"][0]
    np.testing.assert_allclose(np.asarray(px["w"]), np.asarray(pk["w"]),
                               atol=1e-5, rtol=1e-5)
    cx = carries["xla"][1]["counts"]
    ck = carries["pallas"][1]["counts"]
    np.testing.assert_array_equal(np.asarray(cx), np.asarray(ck))
    assert int(np.asarray(ck).sum()) > 0, "counts frozen at zero"
    # identical PRNG stream -> identical gate draws -> identical keys
    np.testing.assert_array_equal(np.asarray(carries["xla"][3]),
                                  np.asarray(carries["pallas"][3]))


def test_hier_mix_identity_operator_is_plain_sgd():
    w, c = 4, 300
    key = jax.random.PRNGKey(0)
    x = jax.random.normal(key, (w, c))
    g = jax.random.normal(jax.random.fold_in(key, 1), (w, c))
    theta = jnp.ones((w,))
    out = hier_mix_chunks(x, g, jnp.eye(w), theta, 0.25, interpret=True)
    np.testing.assert_allclose(out, x - 0.25 * g, atol=1e-6)


# ----------------------------------------------------------- slstm scan
if st is not None:
    @settings(max_examples=12, deadline=None)
    @given(st.data())
    def test_slstm_scan_sweep(data):
        from repro.kernels.slstm_scan import slstm_scan
        b = data.draw(st.sampled_from([1, 3, 8]))
        t = data.draw(st.sampled_from([1, 17, 64]))
        h = data.draw(st.sampled_from([1, 2, 4]))
        hd = data.draw(st.sampled_from([16, 32]))
        chunk = data.draw(st.sampled_from([8, 32]))
        bb = data.draw(st.sampled_from([1, 4]))
        key = jax.random.PRNGKey(b * t + hd)
        zx = 0.5 * jax.random.normal(key, (b, t, h, 4 * hd), jnp.float32)
        r = 0.3 * jax.random.normal(jax.random.fold_in(key, 1),
                                    (h, hd, 4 * hd), jnp.float32)
        bias = 0.1 * jax.random.normal(jax.random.fold_in(key, 2),
                                       (h, 4 * hd), jnp.float32)
        out = slstm_scan(zx, r, bias, block_b=bb, chunk=chunk, interpret=True)
        want = ref.slstm_scan_ref(zx, r, bias)
        np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                                   atol=1e-5, rtol=1e-5)
else:
    @pytest.mark.skip(reason="property sweep needs hypothesis "
                      "(pip install -e .[dev])")
    def test_slstm_scan_sweep():
        pass


@pytest.mark.parametrize("b,t,h,hd,bb,chunk,dtype", [
    (2, 21, 2, 16, 8, 8, jnp.float32),     # T not a chunk multiple
    (3, 17, 1, 32, 2, 32, jnp.float32),    # B not a block multiple, T<chunk
    (8, 64, 4, 16, 4, 16, jnp.float32),
    (2, 24, 2, 16, 2, 8, jnp.bfloat16),
])
def test_slstm_scan_grad_matches_ref(b, t, h, hd, bb, chunk, dtype):
    """jax.grad through the reverse-time Pallas backward (adjoint state in
    VMEM, per-chunk forward recompute from the boundary residuals) vs
    jax.grad of the pure lax.scan reference — dzx, dR and db."""
    key = jax.random.PRNGKey(b * t + hd)
    zx = (0.5 * jax.random.normal(key, (b, t, h, 4 * hd),
                                  jnp.float32)).astype(dtype)
    r = 0.3 * jax.random.normal(jax.random.fold_in(key, 1),
                                (h, hd, 4 * hd), jnp.float32)
    bias = 0.1 * jax.random.normal(jax.random.fold_in(key, 2),
                                   (h, 4 * hd), jnp.float32)

    def f_kernel(z_, r_, b_):
        out = kops.slstm_scan(z_, r_, b_, block_b=bb, chunk=chunk)
        return (out.astype(jnp.float32) ** 2).sum()

    def f_ref(z_, r_, b_):
        return (ref.slstm_scan_ref(z_, r_, b_).astype(jnp.float32) ** 2).sum()

    g1 = jax.grad(f_kernel, argnums=(0, 1, 2))(zx, r, bias)
    g2 = jax.grad(f_ref, argnums=(0, 1, 2))(zx, r, bias)
    tol = dict(atol=2e-2, rtol=2e-2) if dtype == jnp.bfloat16 else \
        dict(atol=2e-5, rtol=2e-4)
    for a, g, name in zip(g1, g2, ("dzx", "dR", "db")):
        assert a.dtype == g.dtype
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(g, np.float32),
                                   err_msg=name, **tol)


def test_slstm_train_kernel_path_matches_xla():
    import dataclasses
    from repro.configs.registry import get_smoke_config
    from repro.models import xlstm as xlstm_mod
    cfg = dataclasses.replace(get_smoke_config("xlstm-125m"),
                              param_dtype="float32", compute_dtype="float32")
    p = xlstm_mod.init_slstm(jax.random.PRNGKey(3), cfg)
    x = jax.random.normal(jax.random.PRNGKey(4), (2, 20, cfg.d_model))
    y_xla = xlstm_mod.slstm_train(p, x, cfg, impl="xla")
    y_ker = xlstm_mod.slstm_train(p, x, cfg, impl="flash")
    np.testing.assert_allclose(np.asarray(y_xla), np.asarray(y_ker),
                               atol=1e-4, rtol=1e-4)


def test_slstm_train_kernel_grads_match_xla():
    """Model-level: jax.grad of `slstm_train` through the kernel path (up-
    projection + gate layout transposes + slstm custom-vjp + down-projection)
    vs the pure lax.scan path, for params AND inputs."""
    from repro.configs.registry import get_smoke_config
    from repro.models import xlstm as xlstm_mod
    cfg = dataclasses.replace(get_smoke_config("xlstm-125m"),
                              param_dtype="float32", compute_dtype="float32")
    p = xlstm_mod.init_slstm(jax.random.PRNGKey(3), cfg)
    x = jax.random.normal(jax.random.PRNGKey(4), (2, 20, cfg.d_model))

    def loss(impl):
        return lambda p_, x_: (xlstm_mod.slstm_train(
            p_, x_, cfg, impl=impl) ** 2).sum()

    g_k = jax.grad(loss("flash"), argnums=(0, 1))(p, x)
    g_x = jax.grad(loss("xla"), argnums=(0, 1))(p, x)
    for a, b in zip(jax.tree.leaves(g_k), jax.tree.leaves(g_x)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=2e-3, rtol=2e-3)


# ----------------------------------------------------------- flash decode
def _paged_case(key, b, hkv, group, hd, bs, nb, nmax, lengths):
    """Random pools + a permuted block table + query for a decode case."""
    kq, kk, kv, kt = jax.random.split(key, 4)
    q = jax.random.normal(kq, (b, hkv * group, hd), jnp.float32)
    k_pool = jax.random.normal(kk, (nb, hkv, bs, hd), jnp.float32)
    v_pool = jax.random.normal(kv, (nb, hkv, bs, hd), jnp.float32)
    # each lane gets a distinct random set of physical blocks — the kernel
    # must follow the indirection, not read the pool in order
    perm = jax.random.permutation(kt, nb)[:b * nmax].reshape(b, nmax)
    tables = perm.astype(jnp.int32)
    return q, k_pool, v_pool, tables, jnp.asarray(lengths, jnp.int32)


@pytest.mark.parametrize(
    "hkv,group,hd,bs,nmax,window,splits,lengths",
    [
        (2, 2, 64, 8, 6, 0, 2, [41, 17]),          # GQA, multi-split
        (1, 4, 80, 16, 4, 0, 4, [64, 3]),          # hd padded 80 -> 128
        (2, 1, 32, 8, 32, 20, 8, [256, 129]),      # long cache + window
        (4, 2, 128, 4, 5, 0, 0, [0, 20]),          # inactive lane, default splits
        (2, 7, 16, 4, 3, 4, 2, [12, 1]),           # qwen2-smoke geometry
    ])
def test_flash_decode_matches_paged_ref(hkv, group, hd, bs, nmax, window,
                                        splits, lengths):
    """The split-KV flash-decode kernel against the gather+dense-softmax
    oracle across GQA grouping, non-64 head dims, sliding windows, ragged
    lengths and inactive (length-0) lanes — the ISSUE's <= 2e-5 bound."""
    b = len(lengths)
    nb = max(b * nmax + 1, 8)
    q, kp, vp, tables, lens = _paged_case(
        jax.random.PRNGKey(hkv * 1000 + hd), b, hkv, group, hd, bs, nb,
        nmax, lengths)
    out = kops.flash_decode(q, kp, vp, tables, lens, window=window,
                            num_splits=splits)
    want = ref.flash_decode_ref(q, kp, vp, tables, lens, window=window)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               atol=2e-5, rtol=2e-5)
    # inactive lanes are exact zeros, not just small
    inactive = np.asarray(lens) == 0
    if inactive.any():
        assert (np.asarray(out)[inactive] == 0).all()


def test_flash_decode_softcap_matches_ref():
    q, kp, vp, tables, lens = _paged_case(
        jax.random.PRNGKey(7), 2, 2, 2, 64, 8, 17, 4, [25, 31])
    out = kops.flash_decode(q, kp, vp, tables, lens, softcap=30.0)
    want = ref.flash_decode_ref(q, kp, vp, tables, lens, softcap=30.0)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               atol=2e-5, rtol=2e-5)
