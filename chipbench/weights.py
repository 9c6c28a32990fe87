"""Model configurations and seeded weights, in the benchmark's own layout.

A configuration file (`configs/<name>.json`) holds the published sizes under
the keys of the model's own ``config.json``.  `make_weights` draws every
weight from the seed in one jitted call, on the device, in the stored dtype.
The reference (`reference.py`) reads this layout directly; `to_program`
rearranges it into the trainer's parameter tree, and `from_program` back.

Layout (layers stacked on the leading axis):
  embed (V, d), final_norm (d,), attn_norm / mlp_norm (L, d),
  wq (L, d, H, hd), wk / wv (L, d, Hkv, hd), wo (L, H, hd, d),
  bq (L, H, hd), bk / bv (L, Hkv, hd)            when attention_bias,
  q_norm / k_norm (L, hd)                        when qk_norm,
  w_gate / w_up (L, d, f), w_down (L, f, d).
"""
from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np

LAYER_KEYS = ("attn_norm", "wq", "wk", "wv", "wo", "bq", "bk", "bv",
              "q_norm", "k_norm", "mlp_norm", "w_gate", "w_up", "w_down")


@dataclasses.dataclass(frozen=True)
class Spec:
    """The sizes a configuration file states."""
    layers: int
    d: int
    heads: int
    kv_heads: int
    head_dim: int
    ffn: int
    vocab: int
    rope_theta: float
    eps: float
    attention_bias: bool
    qk_norm: bool
    tied: bool
    dtype: str

    @staticmethod
    def from_json(c: dict) -> "Spec":
        if c["hidden_act"] != "silu" or not c["tie_word_embeddings"]:
            raise ValueError("the qwen dense reference covers SwiGLU decoders "
                             "with tied embeddings only")
        return Spec(layers=c["num_hidden_layers"], d=c["hidden_size"],
                    heads=c["num_attention_heads"],
                    kv_heads=c["num_key_value_heads"],
                    head_dim=c["head_dim"], ffn=c["intermediate_size"],
                    vocab=c["vocab_size"], rope_theta=float(c["rope_theta"]),
                    eps=float(c["rms_norm_eps"]),
                    attention_bias=bool(c["attention_bias"]),
                    qk_norm=bool(c["qk_norm"]),
                    tied=bool(c["tie_word_embeddings"]),
                    dtype=c["torch_dtype"])

    def shapes(self) -> dict:
        L, d, H, K, hd, f = (self.layers, self.d, self.heads, self.kv_heads,
                             self.head_dim, self.ffn)
        s = {"embed": (self.vocab, d), "final_norm": (d,),
             "attn_norm": (L, d), "wq": (L, d, H, hd), "wk": (L, d, K, hd),
             "wv": (L, d, K, hd), "wo": (L, H, hd, d), "mlp_norm": (L, d),
             "w_gate": (L, d, f), "w_up": (L, d, f), "w_down": (L, f, d)}
        if self.attention_bias:
            s.update(bq=(L, H, hd), bk=(L, K, hd), bv=(L, K, hd))
        if self.qk_norm:
            s.update(q_norm=(L, hd), k_norm=(L, hd))
        return s

    def scale(self, name: str) -> float:
        """Standard deviation of each weight at initialisation.  The tied
        table has std 1, as the trainer's own initialiser gives it; norm
        scales start at 1 (see `make_weights`)."""
        return {"embed": 1.0, "wq": self.d ** -0.5, "wk": self.d ** -0.5,
                "wv": self.d ** -0.5,
                "wo": (self.heads * self.head_dim) ** -0.5,
                "w_gate": self.d ** -0.5, "w_up": self.d ** -0.5,
                "w_down": self.ffn ** -0.5, "bq": 0.02, "bk": 0.02,
                "bv": 0.02}.get(name, 0.0)

    def param_count(self) -> int:
        return int(sum(np.prod(s) for s in self.shapes().values()))


def seed_key(seed: int) -> jax.Array:
    """A PRNG key from any non-negative seed, including ones wider than 32
    bits: the high and low words are folded in separately."""
    key = jax.random.PRNGKey(seed & 0x7FFFFFFF)
    return jax.random.fold_in(key, (seed >> 31) & 0x7FFFFFFF)


def draw(spec: Spec, key) -> dict:
    """The weights of one model from a PRNG key (traceable)."""
    out = {}
    dt = jnp.dtype(spec.dtype)
    for i, (name, shape) in enumerate(sorted(spec.shapes().items())):
        if name.endswith("norm"):
            out[name] = jnp.ones(shape, dt)
        else:
            k = jax.random.fold_in(key, i)
            out[name] = (jax.random.normal(k, shape, jnp.float32)
                         * spec.scale(name)).astype(dt)
    return out


@functools.lru_cache(maxsize=None)
def _drawer(spec: Spec):
    return jax.jit(functools.partial(draw, spec))


def make_weights(spec: Spec, seed: int) -> dict:
    """The weights of one model, drawn from ``seed`` on the device.  One
    compiled program draws them wherever they are needed (the trainer's
    initial state, its change from the start, the reference), so all three
    see the same bits: the same draw compiled into a larger program may
    round differently."""
    return _drawer(spec)(seed_key(seed))


# ------------------------------------------------------- trainer's layout
_MIXER = ("wq", "wk", "wv", "wo", "bq", "bk", "bv")


def to_program(w: dict) -> dict:
    """Benchmark layout -> the trainer's parameter tree (same arrays)."""
    mixer = {k: w[k] for k in _MIXER if k in w}
    if "q_norm" in w:
        mixer["q_norm"] = {"scale": w["q_norm"]}
        mixer["k_norm"] = {"scale": w["k_norm"]}
    block = {"norm1": {"scale": w["attn_norm"]}, "mixer": mixer,
             "norm2": {"scale": w["mlp_norm"]},
             "ffn": {"w_gate": w["w_gate"], "w_up": w["w_up"],
                     "w_down": w["w_down"]}}
    return {"embed": {"table": w["embed"]}, "blocks": {"pos0": block},
            "final_norm": {"scale": w["final_norm"]}}


def from_program(p: dict) -> dict:
    """The trainer's parameter tree -> benchmark layout (inverse of
    `to_program`; leading axes such as the worker axis are kept)."""
    b = p["blocks"]["pos0"]
    m = b["mixer"]
    w = {"embed": p["embed"]["table"], "final_norm": p["final_norm"]["scale"],
         "attn_norm": b["norm1"]["scale"], "mlp_norm": b["norm2"]["scale"],
         **{k: m[k] for k in _MIXER if k in m}, **b["ffn"]}
    if "q_norm" in m:
        w["q_norm"] = m["q_norm"]["scale"]
        w["k_norm"] = m["k_norm"]["scale"]
    return w
