"""Operations and bytes the algorithm needs, computed from shapes.

These are the yardstick's own counts: a roofline share divides the least
time they allow by a measured device time, so they count what the
mathematics requires and nothing that an implementation chooses to redo
(no recomputation, no padding).
"""
from __future__ import annotations

from weights import Spec


def matmul_params(spec: Spec) -> int:
    """Weights that enter a matmul once per token: every projection of
    every layer plus the (tied) output head; the embedding lookup is not a
    matmul."""
    d, hd = spec.d, spec.head_dim
    per_layer = (2 * d * spec.heads * hd + 2 * d * spec.kv_heads * hd
                 + 3 * d * spec.ffn)
    return spec.layers * per_layer + spec.vocab * d


def attention_fwd_flops(b: int, h: int, t: int, hd: int) -> int:
    """Causal attention forward: Q K^T and P V over the t(t+1)/2 query-key
    pairs a causal mask leaves, 2 operations per multiply-add."""
    return 2 * 2 * b * h * hd * t * (t + 1) // 2


def attention_bwd_flops(b: int, h: int, t: int, hd: int) -> int:
    """The four matmuls of the backward (dP = dO V^T, dV = P^T dO,
    dQ = dS K, dK = dS^T Q): twice the forward.  The recomputed Q K^T of a
    flash backward is not counted."""
    return 2 * attention_fwd_flops(b, h, t, hd)


def attention_fwd_bytes(b: int, h: int, hkv: int, t: int, hd: int,
                        itemsize: int = 2) -> int:
    """Read q, k, v; write o and the float32 row logsumexp."""
    return itemsize * (2 * b * h * t * hd + 2 * b * hkv * t * hd) + 4 * b * h * t


def attention_bwd_bytes(b: int, h: int, hkv: int, t: int, hd: int,
                        itemsize: int = 2) -> int:
    """Read q, k, v, dO and the float32 row statistics (logsumexp and
    rowsum(dO * O)); write dq, dk, dv."""
    return (itemsize * (3 * b * h * t * hd + 4 * b * hkv * t * hd)
            + 2 * 4 * b * h * t)


def train_flops_per_token(spec: Spec, t: int) -> float:
    """Forward and backward operations per trained token: 6 per matmul
    weight, plus causal attention at sequence length t (forward 1x,
    backward 2x)."""
    attn = 3 * attention_fwd_flops(1, spec.heads, t, spec.head_dim) / t
    return 6.0 * matmul_params(spec) + spec.layers * attn


def attention_least_seconds(spec: Spec, traffic: dict, slots: int,
                            peaks: dict, backward: bool) -> float:
    """The least time a chip needs for the causal attention of ``slots``
    training slots (every layer, every worker's rows), forward or backward:
    the larger of FLOPs over peak FLOP/s and bytes over peak bytes/s."""
    t = traffic
    rows = slots * t["subnets"] * t["workers_per_subnet"] \
        * t["batch_per_worker"]
    fl = attention_bwd_flops if backward else attention_fwd_flops
    by = attention_bwd_bytes if backward else attention_fwd_bytes
    f = spec.layers * fl(rows, spec.heads, t["seq_len"], spec.head_dim)
    b = spec.layers * by(rows, spec.heads, spec.kv_heads, t["seq_len"],
                         spec.head_dim)
    return max(f / peaks["bf16_flops_per_s"], b / peaks["hbm_bytes_per_s"])
