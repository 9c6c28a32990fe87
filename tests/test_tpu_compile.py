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


def _smoke_harness(one_chip, monkeypatch, mixing="dense"):
    """A `TrainHarness` at the smoke config with the Pallas kernels
    compiled for the chip, W = 4 (two sub-networks of two, ring hubs), and
    its train state as shapes on one chip."""
    import dataclasses

    from repro.configs.registry import get_smoke_config
    from repro.core.mllsgd import MLLConfig, build_network, build_state
    from repro.core.protocol import init_train_state
    from repro.kernels import ops
    from repro.launch.harness import TrainHarness
    from repro.launch.train import replicate_params
    from repro.models import model as model_mod

    monkeypatch.setattr(ops, "_interpret_default", lambda: False)
    cfg = get_smoke_config("qwen2-0.5b")
    mll = MLLConfig(tau=2, q=2, eta=0.005, hub_topology="ring",
                    mixing=mixing)
    network = build_network(
        dataclasses.replace(mll, granularity="worker_per_data"), 2, 2)
    st = build_state(mll, network)
    h = TrainHarness(cfg, mll, st, gate_mode="bernoulli", impl="flash")
    state = jax.eval_shape(lambda: init_train_state(replicate_params(
        model_mod.init_model(jax.random.PRNGKey(0), cfg), 4), cfg=mll))
    return h, jax.tree.map(lambda x: _sds(one_chip, x.shape, x.dtype), state)


@pytest.mark.parametrize("entry", ["local_scan", "event_step"])
def test_step_programs_match_the_benchmark_names(one_chip, monkeypatch,
                                                 entry):
    """The trainer's step programs, compiled with the Pallas kernels at the
    smoke config, still carry what `chipbench/names.json` matches in a
    chip trace: the XLA module name of each entry point and the output
    signature of each flash kernel.  The kernels carry their own names
    and sit in the gradient scope."""
    import json
    import re

    from repro.core import protocol
    from repro.launch import spans

    with open(os.path.join(os.path.dirname(__file__), os.pardir,
                           "chipbench", "names.json")) as f:
        names = json.load(f)
    h, state = _smoke_harness(one_chip, monkeypatch)
    w, seq = 4, 128
    tokens = _sds(one_chip, (w, 1, seq), jnp.int32)
    if entry == "local_scan":
        fn, lead = h.local_scan, (1,)
    else:
        fn, lead = h.event_step[protocol.PHASE_HUB], ()
    batch = {"tokens": _sds(one_chip, lead + tokens.shape, jnp.int32),
             "labels": _sds(one_chip, lead + tokens.shape, jnp.int32)}
    active = _sds(one_chip, lead + (w,), jnp.bool_)
    text = fn.lower(state, batch, active).compile().as_text()
    module = re.search(r"^HloModule (\S+?),", text, re.M).group(1)
    assert re.search(names[entry], f"{module}(1234)")
    calls = [ln.strip().removeprefix("ROOT ") for ln in text.splitlines()
             if CUSTOM_CALL in ln]
    kinds = [[bool(re.search(names[k], c)) for k in ("flash_fwd",
                                                      "flash_bwd")]
             for c in calls]
    assert sorted(map(tuple, kinds)) == [(False, True)] * 2 + [(True, False)]
    kernels = sorted(re.match(r"%(\w+)\.\d+ = ", c).group(1) for c in calls)
    assert kernels == ["flash_dkv", "flash_dq", "flash_fwd"]
    assert all(re.search(r'op_name="[^"]*' + re.escape(spans.GRADS), c)
               for c in calls)
    scopes = set(re.findall(r'op_name="[^"]*?(mll\.[a-z.]+)', text))
    mix = {spans.MIX_HUB} if entry == "event_step" else set()
    assert scopes == {spans.GRADS, spans.UPDATE} | mix


@pytest.mark.parametrize("phase", ["subnet", "hub"])
def test_event_programs_update_and_mix_in_one_pass(one_chip, monkeypatch,
                                                   phase):
    """A two_stage event slot at the smoke config, compiled for the chip:
    the update and the mixing run in the row form, so no top-level
    `reduce` is left in the mixing scope, and a top-level `broadcast`
    there (if any) copies one worker row to every worker: under the
    ring's H at D = 2 both hub rows are the same bits, XLA computes that
    hub model once and broadcasts it.  The fused loops that update keep
    the rounding of the updated params."""
    import re

    from repro.core import protocol
    from repro.launch import spans

    h, state = _smoke_harness(one_chip, monkeypatch, "two_stage")
    ph = protocol.PHASE_SUBNET if phase == "subnet" else protocol.PHASE_HUB
    w, seq = 4, 128
    batch = {k: _sds(one_chip, (w, 1, seq), jnp.int32)
             for k in ("tokens", "labels")}
    text = h.event_step[ph].lower(
        state, batch, _sds(one_chip, (w,), jnp.bool_)).compile().as_text()
    assert dict(h.event_forms) == {(ph, "rows"): 1}
    scope = spans.MIX_SUBNET if phase == "subnet" else spans.MIX_HUB
    entry = text[text.index("\nENTRY"):].splitlines()[1:]
    top = [re.match(r"\s*(?:ROOT )?%\S+ = \w+\[([\d,]*)\]\S* "
                    r"([\w-]+)\((.*)", ln) for ln in entry]
    mixing = [m for m, ln in zip(top, entry)
              if m and 'op_name="' in ln and scope in ln]
    assert mixing, "no top-level instruction in the mixing scope"
    assert not [m for m in mixing if m.group(2) == "reduce"]
    for m in mixing:
        if m.group(2) == "broadcast":
            rank = len(m.group(1).split(","))
            dims = re.search(r"dimensions=\{([\d,]*)\}", m.group(3)).group(1)
            assert dims == ",".join(map(str, range(1, rank)))
    # inside each fused loop that updates, the bf16 roundings of the
    # updated params are ops of their own (`protocol._round_to`)
    fused = re.findall(r"\n(%fused_computation[^\n]*\{\n(?:.*\n)*?\})", text)
    updating = [c for c in fused if spans.UPDATE in c and " subtract(" in c]
    assert updating
    assert all("reduce-precision(" in c for c in updating)


def test_update_and_hub_mix_read_and_write_each_weight_once(one_chip):
    """The gated update and a ring hub event (D = 2 sub-networks of 2) in
    the row form, on a stand-in of four qwen2-0.5b-shaped bf16 leaves at
    W = 4 (1.04e9 elements): the compiler's `bytes accessed` is at most
    8 B per element, against 6 B for reading params and gradients and
    writing the mix once (the composed form reads 17 B)."""
    import dataclasses
    import math

    from repro.core import protocol
    from repro.core.mllsgd import MLLConfig, build_network, build_state
    from repro.optim import optimizers

    shapes = [(4, 24, 896, 4864), (4, 24, 896, 896), (4, 151936, 896),
              (4, 24, 896)]
    tree = [_sds(one_chip, s) for s in shapes]
    mll = MLLConfig(hub_topology="ring", mixing="two_stage")
    st = build_state(mll, build_network(
        dataclasses.replace(mll, granularity="worker_per_data"), 2, 2))
    sgd = optimizers.sgd(0.005)
    strategy = protocol.get_mixing("two_stage")

    def event(params, opt_state, grads, theta):
        rows, opt_state = protocol.gated_update_rows(sgd, params, opt_state,
                                                     grads, theta)
        return strategy.mix_rows(rows, st, hub=True), opt_state

    opt_state = {"inner": (), "counts": _sds(one_chip, (4,), jnp.int32)}
    compiled = jax.jit(event, donate_argnums=0).lower(
        tree, opt_state, tree, _sds(one_chip, (4,), jnp.float32)).compile()
    cost = compiled.cost_analysis()
    cost = cost[0] if isinstance(cost, list) else cost
    elements = sum(math.prod(s) for s in shapes)
    assert cost["bytes accessed"] / elements <= 8.0
