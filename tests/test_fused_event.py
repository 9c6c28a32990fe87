"""The row form of an event slot (`protocol.gated_update_rows` into
`TwoStageMixing.mix_rows`), which the single-device trainer runs for
two_stage + sgd events:

* it gives the same bits as `gated_inner_update` followed by
  `TwoStageMixing.subnet` / `hub`, in bf16 and f32, at several (D, Nd)
  and gate patterns, and every member of a sub-network holds one model,
* `train_step.event_form` routes only the events it can compute bit for
  bit, and `TrainHarness` counts the form each event entry was traced in,
* a whole event slot of the smoke transformer ends in the same state in
  either form.
"""
import dataclasses
import itertools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.registry import get_smoke_config
from repro.core import protocol
from repro.core.mllsgd import MLLConfig, build_network, build_state
from repro.core.protocol import init_train_state
from repro.core.timeline import get_policy
from repro.data.pipeline import LMBatcher, make_token_stream
from repro.launch.harness import TrainHarness, run_plan
from repro.launch.train import replicate_params
from repro.models import model as model_mod
from repro.optim import optimizers
from repro.train import train_step

CFG = get_smoke_config("qwen2-0.5b")
SGD = optimizers.sgd(0.05)
GATES = {"on": lambda w: np.ones(w),
         "mixed": lambda w: np.arange(w) % 2,
         "off": lambda w: np.zeros(w)}


def _bits(x):
    return np.atleast_1d(np.asarray(x)).view(np.uint8)


def _grouped(d, nd, mixing="two_stage", **kw):
    mll = MLLConfig(hub_topology="ring", mixing=mixing, **kw)
    net = build_network(
        dataclasses.replace(mll, granularity="worker_per_data"), d, nd)
    return mll, net, build_state(mll, net)


@pytest.mark.parametrize(
    "phase,dtype,dnd,gate",
    list(itertools.product(["subnet", "hub"], ["bfloat16", "float32"],
                           [(2, 2), (3, 2), (2, 3), (4, 5)], sorted(GATES))))
def test_rows_event_equals_the_composed_event(phase, dtype, dnd, gate):
    d, nd = dnd
    _, _, st = _grouped(d, nd)
    w, hub = d * nd, phase == "hub"
    ks = jax.random.split(jax.random.PRNGKey(10 * d + nd), 4)
    shapes = {"a": (w, 7, 33), "b": (w, 129)}
    params = {k: 3 * jax.random.normal(ks[i], s, dtype)
              for i, (k, s) in enumerate(shapes.items())}
    grads = {k: jax.random.normal(ks[2 + i], s, dtype)
             for i, (k, s) in enumerate(shapes.items())}
    theta = jnp.asarray(GATES[gate](w), jnp.float32)
    opt_state = protocol.init_gated_opt_state(SGD, params)
    strategy = protocol.get_mixing("two_stage")
    assert strategy.has_rows()

    @jax.jit
    def composed(p, o, g, t):
        p, o = protocol.gated_inner_update(SGD, p, o, g, t)
        return (strategy.hub if hub else strategy.subnet)(p, st), o

    @jax.jit
    def rows(p, o, g, t):
        r, o = protocol.gated_update_rows(SGD, p, o, g, t)
        return strategy.mix_rows(r, st, hub=hub), o

    want, want_opt = composed(params, opt_state, grads, theta)
    got, got_opt = rows(params, opt_state, grads, theta)
    for k in shapes:
        assert got[k].dtype == want[k].dtype
        np.testing.assert_array_equal(_bits(got[k]), _bits(want[k]))
        # every member of a sub-network holds the same bits
        members = _bits(got[k]).reshape((d, nd, -1))
        assert (members == members[:, :1]).all()
    np.testing.assert_array_equal(got_opt["counts"], want_opt["counts"])


def test_gated_update_rows_refuses_optimizer_state():
    params = {"w": jnp.ones((4, 3))}
    mom = optimizers.momentum(0.1)
    with pytest.raises(ValueError, match="holds no arrays"):
        protocol.gated_update_rows(
            mom, params, protocol.init_gated_opt_state(mom, params), params,
            jnp.ones(4))


ROUTES = {
    # name: (MLLConfig fields, event_form kwargs, form)
    "subnet": ({}, {"phase": protocol.PHASE_SUBNET}, "rows"),
    "hub": ({}, {"phase": protocol.PHASE_HUB}, "rows"),
    "local": ({}, {"phase": protocol.PHASE_LOCAL}, "composed"),
    "dense_op": ({}, {"phase": protocol.PHASE_LOCAL, "op": jnp.eye(4)},
                 "composed"),
    "shard_map": ({}, {"phase": protocol.PHASE_HUB,
                       "spmd": protocol.SpmdAxis("workers", 2, 4)},
                  "composed"),
    "chunked": ({}, {"phase": protocol.PHASE_HUB, "overlap": "chunked"},
                "composed"),
    "mix_dtype": ({"mix_dtype": "bfloat16"}, {"phase": protocol.PHASE_HUB},
                  "composed"),
    "momentum": ({"inner_opt": "momentum"}, {"phase": protocol.PHASE_HUB},
                 "composed"),
    "adamw": ({"inner_opt": "adamw"}, {"phase": protocol.PHASE_SUBNET},
              "composed"),
    "dense": ({"mixing": "dense"}, {"phase": protocol.PHASE_HUB},
              "composed"),
    "ppermute": ({"mixing": "ppermute"}, {"phase": protocol.PHASE_HUB},
                 "composed"),
    "int8_subnet": ({"mixing": "int8"}, {"phase": protocol.PHASE_SUBNET},
                    "composed"),
    "bf16": ({"mixing": "bf16"}, {"phase": protocol.PHASE_HUB}, "composed"),
}


@pytest.mark.parametrize("route", sorted(ROUTES))
def test_event_form_takes_rows_only_where_they_are_exact(route):
    fields, kwargs, form = ROUTES[route]
    mll = MLLConfig(**{"hub_topology": "ring", "mixing": "two_stage",
                       **fields})
    params = {"w": jnp.ones((4, 3))}
    opt_state = protocol.init_gated_opt_state(
        protocol.resolve_inner_optimizer(mll), params)
    assert train_step.event_form(mll, opt_state, **kwargs) == form


def _smoke(mixing="two_stage"):
    mll, net, st = _grouped(2, 2, mixing, tau=2, q=2, eta=0.005,
                            worker_rates=(1.0, 0.5, 1.0, 0.5))
    return mll, net, st


def _state(mll):
    params = model_mod.init_model(jax.random.PRNGKey(0), CFG)
    stacked = replicate_params(params, 4)
    # spread the workers so that mixing has something to average
    stacked = jax.tree.map(
        lambda x: x + (0.01 * jax.random.normal(
            jax.random.PRNGKey(x.size), x.shape)).astype(x.dtype), stacked)
    return init_train_state(stacked, cfg=mll)


@pytest.mark.parametrize("idle", [False, True])
@pytest.mark.parametrize("phase", [protocol.PHASE_SUBNET, protocol.PHASE_HUB])
def test_event_slot_state_is_the_same_in_either_form(monkeypatch, phase,
                                                     idle):
    """One event slot of the smoke transformer (bf16 weights, batch 2),
    and its all-idle twin (mixing alone): the row form and the composed
    form end in the same bits, and the harness counts the form it
    traced."""
    mll, _, st = _smoke()
    stream = make_token_stream(4, 4096, vocab_size=CFG.vocab_size, seed=0)
    batch = LMBatcher(stream, 16, 2).sample(np.random.default_rng(0))
    active = jnp.ones((4,), bool)

    entry = "event_step_idle" if idle else "event_step"

    rows_h = TrainHarness(CFG, mll, st, gate_mode="bernoulli")
    got, _ = getattr(rows_h, entry)[phase](_state(mll), batch, active)
    assert dict(rows_h.event_forms) == {(phase, "rows"): 1}

    monkeypatch.setattr(train_step, "event_form",
                        lambda *a, **k: "composed")
    composed_h = TrainHarness(CFG, mll, st, gate_mode="bernoulli")
    want, _ = getattr(composed_h, entry)[phase](_state(mll), batch, active)
    for x, y in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_array_equal(_bits(x), _bits(y))


@pytest.mark.parametrize("mixing,form", [("two_stage", "rows"),
                                         ("dense", "composed")])
def test_run_plan_logs_the_event_forms(mixing, form):
    """tau 2, q 2, a boundary every 2 slots: the subnet event's line and
    the hub event's line each name the forms traced so far."""
    mll, net, st = _smoke(mixing)
    plan = get_policy("deadline").plan(net, mll.schedule, 8,
                                       np.random.default_rng(0),
                                       rate_model="bernoulli")
    stream = make_token_stream(4, 4096, vocab_size=CFG.vocab_size, seed=0)
    lines = []
    out = run_plan(CFG, mll, net, st, plan, LMBatcher(stream, 16, 1),
                   np.random.default_rng(0), _state(mll), stop_slot=8,
                   eval_every=2, log=lines.append)
    sub, hub = (protocol.PHASE_SUBNET, form), (protocol.PHASE_HUB, form)
    assert f"event forms {{{sub}: 1}}" in lines[0]
    assert f"event forms {{{sub}: 1, {hub}: 1}}" in lines[1]
    assert all("event forms" not in ln for ln in lines[2:])
    assert dict(out.harness.event_forms) == {sub: 1, hub: 1}
