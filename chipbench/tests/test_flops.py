"""FLOP and byte counts against hand-computed values."""
import json
import os

import flops
from weights import Spec

from conftest import BENCH


def spec(name):
    with open(os.path.join(BENCH, "configs", name + ".json")) as f:
        return Spec.from_json(json.load(f))


def test_attention_counts():
    # B=1, H=2, T=4, hd=8: 10 causal pairs per head, 2 matmuls of
    # 2 * hd operations per pair
    assert flops.attention_fwd_flops(1, 2, 4, 8) == 2 * 2 * 2 * 8 * 10
    assert flops.attention_bwd_flops(1, 2, 4, 8) == 2 * 640
    # q, o: 2 * (1*2*4*8) bf16; k, v with 1 kv head: 2 * (1*1*4*8); lse
    assert flops.attention_fwd_bytes(1, 2, 1, 4, 8) == 2 * (128 + 64) + 32
    # q, dO, dq: 3 * 64 elements; k, v, dk, dv: 4 * 32; lse and delta
    assert flops.attention_bwd_bytes(1, 2, 1, 4, 8) == 2 * (192 + 128) + 64


def test_qwen2_counts():
    s = spec("qwen2-0.5b")
    per_layer = 2 * 896 * 14 * 64 + 2 * 896 * 2 * 64 + 3 * 896 * 4864
    assert flops.matmul_params(s) == 24 * per_layer + 151936 * 896
    assert flops.matmul_params(s) == 493_961_216
    assert s.param_count() == 494_032_768     # the published 0.49B
    attn = 6 * 24 * 14 * 64 * 257
    assert flops.train_flops_per_token(s, 256) == 6 * 493_961_216 + attn


def test_attention_least_seconds():
    s = spec("qwen2-0.5b")
    t = {"subnets": 2, "workers_per_subnet": 2, "batch_per_worker": 1,
         "seq_len": 256}
    peaks = {"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e9}
    f = 24 * flops.attention_fwd_flops(40, 14, 256, 64)
    b = 24 * flops.attention_fwd_bytes(40, 14, 2, 256, 64)
    assert flops.attention_least_seconds(s, t, 10, peaks, False) == \
        max(f / 1e12, b / 1e9)
