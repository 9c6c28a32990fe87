"""CPU tests of the chip benchmark: tiny sizes, Pallas in interpret mode."""
import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
for p in (BENCH, os.path.join(ROOT, "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

TINY = dict(num_hidden_layers=2, hidden_size=64, intermediate_size=128,
            num_attention_heads=4, num_key_value_heads=2, head_dim=16,
            vocab_size=256)
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
# Limits at this size, set from readings at this size as the chip's are
# set at the cells' own (six seeds, both cells): sound runs read loss1 <=
# 1.25e-3, grad1_median <= 1.0e-3 and exchange 0; the float8 control reads
# loss1 >= 4.2e-3; half of each batch reads loss1 >= 3.4e-2 and
# grad1_median >= 0.15, the exchange left out exchange >= 0.56, a state
# left unchanged grad1_median 1.
TINY_LIMITS = {"loss1": 2.5e-3, "grad1_median": 1e-2, "exchange": 0.05}


def tiny_cell(name: str) -> dict:
    """A cell as `run.load_cell` reads it, cut to a size the CPU runs in
    seconds: every width and the vocabulary shrunk, seq 32, with the
    limits of that size."""
    import run
    cell = run.load_cell(name)
    cell["config"] = dict(cell["config"], **TINY)
    cell["traffic"] = dict(cell["traffic"], seq_len=32, tokens_per_worker=4096)
    cell["limits"] = dict(TINY_LIMITS)
    return cell
