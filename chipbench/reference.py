"""Plain reference of the qwen dense decoder and of MLL-SGD's first steps.

Written from the published architecture (Qwen2 / Qwen3 model cards and
``modeling_qwen2.py`` / ``modeling_qwen3.py``) and from the MLL-SGD paper
(arXiv:2007.13819, Algorithm 1 and Eq. 5), in straightforward `jax.numpy`.
It imports nothing of the trainer and takes none of its arrays: the
weights come from `weights.make_weights`, the batches from the benchmark's
own token stream, the gates and mixing matrices from the paper's
definitions below.

Arithmetic: every matmul, norm, softmax and the loss run in float32 at
full matmul precision.  Parameters are STORED in the dtype the
configuration states (bfloat16), as the trainer stores them, so each update
rounds exactly where a bf16 trainer's must: p <- bf16(f32(p) - eta*theta*g).
Departures from the published models: none in the layer equations; the
initial weights are random (`weights.py`).

``lowp`` names a dtype below the stated bfloat16 (``"int8"`` or
``"float8_e4m3fn"``) in which every matmul's operands, forward and
backward, are rounded after a per-tensor scale (the tensor's largest
magnitude maps to the dtype's largest value): the control that `correct`
must reject.

Memory: worker i lives on chip i mod chips of the cell, so no chip holds
more models than the trainer puts there, and each worker is run layer by
layer.  The forward keeps each layer's input (T x d floats); the backward
walks the layers in reverse, updating each layer's weights as soon as its
gradient exists, so no full gradient and no float32 copy of the model is
ever held.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from weights import Spec, make_weights

F32 = jnp.float32


# ------------------------------------------------------------ arithmetic
def _rounder(lowp: str | None):
    if lowp is None:
        return None
    dt = jnp.dtype(lowp)
    integer = jnp.issubdtype(dt, jnp.integer)
    top = float(jnp.iinfo(dt).max if integer else jnp.finfo(dt).max)

    def rnd(x):
        s = jnp.max(jnp.abs(x)) / top
        s = jnp.where(s > 0, s, 1.0)
        y = jnp.round(x / s) if integer else x / s
        return y.astype(dt).astype(F32) * s
    return rnd


def _matmul(lowp: str | None):
    """einsum(eq, a, b) in float32, or with operands (and, backward, the
    incoming cotangent) rounded to ``lowp``."""
    rnd = _rounder(lowp)
    if rnd is None:
        return lambda eq, a, b: jnp.einsum(eq, a, b)

    @partial(jax.custom_vjp, nondiff_argnums=(0,))
    def mm(eq, a, b):
        return jnp.einsum(eq, rnd(a), rnd(b))

    def fwd(eq, a, b):
        ra, rb = rnd(a), rnd(b)
        return jnp.einsum(eq, ra, rb), (ra, rb)

    def bwd(eq, res, g):
        _, vjp = jax.vjp(lambda x, y: jnp.einsum(eq, x, y), *res)
        return vjp(rnd(g))

    mm.defvjp(fwd, bwd)
    return mm


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def _rope(x, theta):
    """Rotary embedding, rotate-half form; x: (B, T, heads, hd)."""
    t, hd = x.shape[1], x.shape[-1]
    half = hd // 2
    inv = 1.0 / theta ** (np.arange(half, dtype=np.float64) * 2.0 / hd)
    ang = jnp.arange(t, dtype=F32)[:, None] * jnp.asarray(inv, F32)[None]
    c, s = jnp.cos(ang)[None, :, None], jnp.sin(ang)[None, :, None]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], -1)


def block(spec: Spec, mm, p: dict, h):
    """One decoder layer; h: (B, T, d) float32."""
    p = {k: v.astype(F32) for k, v in p.items()}
    x = _rms(h, p["attn_norm"], spec.eps)
    q = mm("btd,dhk->bthk", x, p["wq"])
    k = mm("btd,dhk->bthk", x, p["wk"])
    v = mm("btd,dhk->bthk", x, p["wv"])
    if spec.attention_bias:
        q, k, v = q + p["bq"], k + p["bk"], v + p["bv"]
    if spec.qk_norm:
        q, k = _rms(q, p["q_norm"], spec.eps), _rms(k, p["k_norm"], spec.eps)
    q, k = _rope(q, spec.rope_theta), _rope(k, spec.rope_theta)
    group = spec.heads // spec.kv_heads
    k, v = jnp.repeat(k, group, axis=2), jnp.repeat(v, group, axis=2)
    t = h.shape[1]
    s = mm("bthk,bshk->bhts", q, k) / np.sqrt(spec.head_dim)
    causal = jnp.tril(jnp.ones((t, t), bool))
    s = jnp.where(causal, s, -jnp.inf)
    o = mm("bhts,bshk->bthk", jax.nn.softmax(s, -1), v)
    h = h + mm("bthk,hkd->btd", o, p["wo"])
    x = _rms(h, p["mlp_norm"], spec.eps)
    a = jax.nn.silu(mm("btd,df->btf", x, p["w_gate"])) \
        * mm("btd,df->btf", x, p["w_up"])
    return h + mm("btf,fd->btd", a, p["w_down"])


def head_loss(spec: Spec, mm, keep: float, embed, final_norm, h, labels):
    """Mean next-token cross-entropy through the tied head.  ``keep`` < 1
    averages over that leading share of positions only (a planted fault)."""
    x = _rms(h, final_norm.astype(F32), spec.eps)
    logits = mm("btd,vd->btv", x, embed.astype(F32))
    nll = jax.nn.logsumexp(logits, -1) - jnp.take_along_axis(
        logits, labels[..., None], -1)[..., 0]
    t = int(round(nll.shape[1] * keep))
    return nll[:, :t].mean()


# ----------------------------------------------------- one worker's step
class Worker:
    """Jitted pieces of one worker's forward, backward and SGD update."""

    def __init__(self, spec: Spec, lowp: str | None = None,
                 keep: float = 1.0):
        mm = _matmul(lowp)
        dt = jnp.dtype(spec.dtype)
        blk = partial(block, spec, mm)
        self.embed = jax.jit(lambda e, tok: e.astype(F32)[tok])
        self.fwd = jax.jit(blk)
        # gradients are taken with respect to float32 copies, so that they
        # are not rounded back to the stored dtype
        head = jax.value_and_grad(partial(head_loss, spec, mm, keep),
                                  argnums=(0, 1, 2))
        self.head = jax.jit(lambda e, fn, h, lab: head(
            e.astype(F32), fn.astype(F32), h, lab))

        def bwd(p, h, dh):
            p = jax.tree.map(lambda x: x.astype(F32), p)
            return jax.vjp(blk, p, h)[1](dh)
        self.bwd = jax.jit(bwd)

        def update(p, g, lr):
            new = jax.tree.map(lambda x, y: (x.astype(F32) - lr * y).astype(dt),
                               p, g)
            norms = jax.tree.map(lambda y: jnp.sqrt(jnp.sum(y * y)), g)
            return new, norms
        self.update = jax.jit(update)
        self.scatter = jax.jit(
            lambda g, tok, dh: g.at[tok.reshape(-1)].add(
                dh.reshape(-1, dh.shape[-1])))

    def step(self, w: dict, tokens, labels, lr):
        """One gradient step of one worker.  ``w`` = {"embed", "final_norm",
        "layers": [per-layer dicts]}; ``lr`` = eta * gate.  Returns (loss,
        updated w, gradient norms keyed "<leaf>" or "<leaf>.<layer>")."""
        h = self.embed(w["embed"], tokens)
        hs = [h]
        for p in w["layers"]:
            hs.append(self.fwd(p, hs[-1]))
        loss, (g_emb, g_fn, dh) = self.head(w["embed"], w["final_norm"],
                                            hs.pop(), labels)
        layers, gn = [None] * len(w["layers"]), {}
        for i in reversed(range(len(w["layers"]))):
            g, dh = self.bwd(w["layers"][i], hs.pop(), dh)
            layers[i], n = self.update(w["layers"][i], g, lr)
            gn.update({f"{k}.{i}": v for k, v in n.items()})
        g_emb = self.scatter(g_emb, tokens, dh)
        (emb, fn), (ne, nf) = self.update((w["embed"], w["final_norm"]),
                                          (g_emb, g_fn), lr)
        gn.update(embed=ne, final_norm=nf)
        return loss, {"embed": emb, "final_norm": fn, "layers": layers}, gn


# ------------------------------------------------------------ MLL-SGD
def diffusion_matrix(topology: str, hubs: int) -> np.ndarray:
    """The hub mixing matrix H (paper Assumption 2) for equal hub weights:
    Metropolis-Hastings weights S_ij = min(b_i, b_j) / (1 + max(deg_i,
    deg_j)) on the graph's edges, H_ij = S_ij / b_j, diagonal = 1 - column
    sum."""
    adj = np.zeros((hubs, hubs), bool)
    if topology == "ring" and hubs > 1:
        for i in range(hubs):
            adj[i, (i + 1) % hubs] = adj[(i + 1) % hubs, i] = True
    elif topology == "complete":
        adj[:] = ~np.eye(hubs, dtype=bool)
    elif hubs > 1:
        raise ValueError(f"topology {topology!r} is not in the reference")
    b = np.full(hubs, 1.0 / hubs)
    deg = adj.sum(1)
    h = np.where(adj, np.minimum(b[:, None], b[None]) /
                 (1.0 + np.maximum(deg[:, None], deg[None])), 0.0) / b[None]
    h[np.diag_indices(hubs)] = 1.0 - h.sum(0)
    return h


def mixing_matrices(subnets: int, per_subnet: int, topology: str):
    """(V, Z) of paper Eq. 5 for equal worker weights: column j of the new
    models is sum_i T[i, j] x_i."""
    n = subnets * per_subnet
    sub = np.repeat(np.arange(subnets), per_subnet)
    v = np.full(n, 1.0 / per_subnet)
    same = sub[:, None] == sub[None]
    big_v = np.where(same, v[:, None], 0.0)
    big_z = diffusion_matrix(topology, subnets)[sub[:, None], sub[None]] \
        * v[:, None]
    return big_v, big_z


def phase(k: int, tau: int, q: int) -> int:
    """0 local, 1 subnet average, 2 hub average after 1-based step k."""
    return 2 if k % (q * tau) == 0 else (1 if k % tau == 0 else 0)


def gates(seed: int, k: int, rates) -> np.ndarray:
    """theta_k ~ Bernoulli(p_i), counter-based on (seed, k): the deadline
    policy's draw, which the trainer's configuration fixes."""
    key = jax.random.fold_in(jax.random.PRNGKey(seed), k)
    u = jax.random.uniform(key, (len(rates),), F32)
    return np.asarray(u < jnp.asarray(rates, F32), np.float32)


def _split_layers(w: dict, spec: Spec) -> dict:
    layers = [{k: w[k][i] for k in w if k not in ("embed", "final_norm")}
              for i in range(spec.layers)]
    return {"embed": w["embed"], "final_norm": w["final_norm"],
            "layers": layers}


@jax.jit
def _delta_norms(a: dict, b: dict) -> dict:
    return jax.tree.map(
        lambda x, y: jnp.sqrt(jnp.sum(jnp.square(x.astype(F32)
                                                 - y.astype(F32)))), a, b)


def _flat_norms(w0: dict, w: dict) -> dict:
    out = {k: v for k, v in _delta_norms(
        {"embed": w0["embed"], "final_norm": w0["final_norm"]},
        {"embed": w["embed"], "final_norm": w["final_norm"]}).items()}
    for i, (a, b) in enumerate(zip(w0["layers"], w["layers"])):
        out.update({f"{k}.{i}": v for k, v in _delta_norms(a, b).items()})
    return out


@partial(jax.jit, static_argnums=(2,))
def _mix_terms(xs, coef, dtype):
    acc = sum(c * x.astype(F32) for c, x in zip(coef, xs))
    return acc.astype(dtype)


def _mix(workers: list, t: np.ndarray, home: list) -> list:
    """Column j of ``t`` mixes the workers' models into worker j's, each
    leaf formed on worker j's device (``home[j]``) from copies of its
    sources."""
    n = len(workers)
    leaves = [jax.tree.leaves(w) for w in workers]
    treedef = jax.tree.structure(workers[0])
    out = [[] for _ in range(n)]
    for li in range(len(leaves[0])):
        for j in range(n):
            src = [i for i in range(n) if t[i, j] != 0]
            coef = jnp.asarray([t[i, j] for i in src], F32)
            out[j].append(_mix_terms(
                tuple(jax.device_put(leaves[i][li], home[j]) for i in src),
                coef, leaves[j][li].dtype))
    return [jax.tree.unflatten(treedef, o) for o in out]


def run(spec: Spec, train: dict, seed: int, gate_seed: int,
        batches: list[dict], devices: list, *, lowp: str | None = None,
        keep: float = 1.0, exchange: bool = True) -> dict:
    """Follow the first ``len(batches)`` MLL-SGD steps from the seed, worker
    i living and stepping on ``devices[i % len(devices)]`` (the cell's
    chips: W models need not fit on one).

    ``train`` holds the schedule (tau, q, eta, subnets, workers_per_subnet,
    topology, rates) and ``check_slots``, the steps whose losses are
    reported.  ``batches[k]`` = {"tokens", "labels"}, (W, B, T) numpy
    arrays.  Returns the check slots, the per-worker losses at them
    (slots, W),
    the first step's gradient norms, and the norms of each worker's change
    from the initial weights after each check slot (``deltas``; the first
    and last also as ``delta1`` and ``delta_last``), keyed
    "w<i>/<leaf>[.<layer>]".  ``keep`` and ``exchange=False`` plant
    faults."""
    n = train["subnets"] * train["workers_per_subnet"]
    report = set(train["check_slots"])
    v_op, z_op = mixing_matrices(train["subnets"],
                                 train["workers_per_subnet"],
                                 train["topology"])
    wk = Worker(spec, lowp, keep)
    home = [devices[i % len(devices)] for i in range(n)]
    split = _split_layers(make_weights(spec, seed), spec)
    start = {d: jax.device_put(split, d) for d in home}
    del split
    workers = [start[d] for d in home]
    losses, deltas, grad1 = [], [], {}
    with jax.default_matmul_precision("highest"), \
            jax.default_device(devices[0]):
        for k, batch in enumerate(batches, start=1):
            theta = gates(gate_seed, k, train["rates"])
            step = [wk.step(workers[i],
                            jax.device_put(batch["tokens"][i], home[i]),
                            jax.device_put(batch["labels"][i], home[i]),
                            jnp.float32(train["eta"] * theta[i]))
                    for i in range(n)]
            workers = [s[1] for s in step]
            if k in report:
                losses.append([s[0] for s in step])
            if k == 1:
                grad1 = {f"w{i}/{key}": float(val)
                         for i, s in enumerate(step) for key, val in s[2].items()}
            ph = phase(k, train["tau"], train["q"])
            if ph and exchange:
                # every hub first averages its sub-network (a model, stored
                # in the configured dtype like every model); at a hub event
                # the hubs then mix those averages (Algorithm 1)
                workers = _mix(workers, v_op, home)
                if ph == 2:
                    workers = _mix(workers, z_op, home)
            if k in report:
                deltas.append({
                    f"w{i}/{key}": float(val) for i in range(n)
                    for key, val in _flat_norms(start[home[i]],
                                                workers[i]).items()})
    return {"slots": sorted(report),
            "loss": np.asarray([[float(x) for x in row] for row in losses]),
            "grad1": grad1,
            "deltas": deltas, "delta1": deltas[0], "delta_last": deltas[-1]}
