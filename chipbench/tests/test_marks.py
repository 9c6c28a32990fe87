"""The reductions over the trainer's own marks, on a hand-made trace, and
the reading of op names from the HLO a real (CPU) trace holds."""
import glob
import os
import types

import pytest

import run
import spans
from spans import Marks, Span
from traces import Op

READERS = ("grad_slot_ms", "update_slot_ms", "mix_event_ms", "host_slot_ms",
           "dispatch_slot_ms", "collective_exposed_ms")


LOCAL, EVENT = "jit__lambda(1)", "jit__unknown(2)"


def make():
    # window [0, 100).  Device 0: a local scan's loop op over [0, 100) in
    # the gradient part (left out), gradient ops [10, 30), an update [30,
    # 40), a carry copy [40, 45); then an event program's update [45,
    # 48), hub mix [48, 58), an op of no part [58, 60), and a gradient op
    # after the window.  Device 1: gradients [0, 20), an update [20, 26),
    # a subnet mix [26, 36).
    d0 = [(Op("%while.3 = (s32[]) while(s32[] %a)", 0, 100), LOCAL,
           spans.GRADS),
          (Op("%fusion.1 = bf16[8]{0} fusion(%a)", 10, 30), LOCAL,
           spans.GRADS),
          (Op("%fusion.2 = bf16[8]{0} fusion(%b)", 30, 40), LOCAL,
           spans.UPDATE),
          (Op("%copy.7 = bf16[8]{0} copy(%p)", 40, 45), LOCAL, spans.CARRY),
          (Op("%fusion.8 = bf16[8]{0} fusion(%g)", 45, 48), EVENT,
           spans.UPDATE),
          (Op("%fusion.3 = bf16[8]{0} fusion(%c)", 48, 58), EVENT,
           "mll.mix.hub"),
          (Op("%copy.4 = bf16[8]{0} copy(%d)", 58, 60), EVENT, ""),
          (Op("%fusion.5 = bf16[8]{0} fusion(%e)", 150, 160), LOCAL,
           spans.GRADS)]
    d1 = [(Op("%fusion.1 = bf16[8]{0} fusion(%a)", 0, 20), LOCAL,
           spans.GRADS),
          (Op("%fusion.2 = bf16[8]{0} fusion(%b)", 20, 26), LOCAL,
           spans.UPDATE),
          (Op("%fusion.6 = bf16[8]{0} fusion(%f)", 26, 36), EVENT,
           "mll.mix.subnet")]
    host = [Span("run_span", 0, 90, {"lo": 0, "hi": 9}),
            Span("local_scan", 10, 20, {"slots": 4}),
            Span("event_step.1", 30, 35, {"slots": 1, "idle": 0}),
            Span("skip_idle", 40, 41, {"slots": 3}),
            Span("dense_step", 50, 52, {"slots": 1, "idle": 1}),
            # a call that ends after the window does not count
            Span("run_span", 95, 120, {"lo": 9, "hi": 10}),
            Span("local_scan", 96, 110, {"slots": 1})]
    return Marks({0: d0, 1: d1}, host, (0, 100),
                 {LOCAL: spans.LOCAL_SCAN, EVENT: spans.EVENT_STEP})


def test_scope_time_leaves_loop_ops_out():
    m = make()
    assert spans.part_ns(m, spans.GRADS) == pytest.approx((20 + 20) / 2)
    assert spans.part_ns(m, spans.UPDATE) == pytest.approx((10 + 3 + 6) / 2)
    assert spans.part_ns(m, spans.MIX) == pytest.approx((10 + 10) / 2)
    assert spans.part_ns(m, "mll.mix.hub") == pytest.approx(10 / 2)
    assert spans.part_ns(m, spans.CARRY) == pytest.approx(5 / 2)
    # the local scans': the update of the programs that mix nothing
    assert spans.programs(m, spans.LOCAL_SCAN) == {LOCAL}
    assert spans.programs(m, spans.LOCAL_SCAN, spans.EVENT_STEP) \
        == {LOCAL, EVENT}
    assert spans.part_ns(m, spans.UPDATE, {LOCAL}) \
        == pytest.approx((10 + 6) / 2)


def test_slot_counts_from_stats():
    m = make()
    assert spans.model_slots(m) == 4 + 1 + 1
    assert spans.local_slots(m) == 4
    assert spans.event_slots(m) == 2
    assert spans.all_slots(m) == 4 + 1 + 1 + 3


def test_self_time_of_nested_spans():
    m = make()
    assert spans.span_ns(m, spans.DISPATCH) == 10 + 5 + 2
    # the fast-forward is host work of run_span's own, not a launch
    assert spans.self_ns(m, spans.RUN_SPAN, spans.DISPATCH) == 90 - 17


def test_scope_of_an_op_name():
    assert spans.scope_of("jit(_lambda)/while/body/closed_call/mll.grads/"
                          "vmap(transpose(jvp()))/dot_general") == spans.GRADS
    assert spans.scope_of("jit(f)/mll.grads/flash_fwd/pallas_call") \
        == spans.GRADS
    assert spans.scope_of("jit(_unknown)/mll.mix.hub/reduce_sum") \
        == "mll.mix.hub"
    assert spans.scope_of("jit(_lambda)/while/body/dynamic_slice") == ""
    assert spans.scope_of("") == ""
    assert spans.latest({spans.GRADS, "mll.mix.hub", spans.UPDATE}) \
        == "mll.mix.hub"
    assert spans.latest({spans.GRADS, spans.UPDATE}) == spans.UPDATE
    assert spans.latest(set()) == ""


def test_program_kind_from_its_scopes():
    """A step program is told by the trainer scopes its HLO holds, by
    the rules of `names.json`, whatever its module is named."""
    import json

    from conftest import BENCH
    from spans import Instr
    with open(os.path.join(BENCH, "names.json")) as f:
        rules = json.load(f)["programs"]

    def prog(*op_names):
        return [Instr(f"i.{k}", n, k, ()) for k, n in enumerate(op_names)]

    g = "jit(_lambda)/while/body/closed_call/mll.grads/dot_general"
    u = "jit(_lambda)/while/body/closed_call/mll.update/mul"
    assert spans.kind_of(prog(g, u, ""), rules) == spans.LOCAL_SCAN
    for mix in ("mll.mix.subnet", "mll.mix.hub"):
        assert spans.kind_of(prog(g, f"jit(_lambda)/shard_map/{mix}/psum",
                                  u), rules) == spans.EVENT_STEP
    # an all-idle event step runs the forward and the mixing alone
    assert spans.kind_of(prog("jit(_unknown)/mll.mix.hub/add", g), rules) \
        == spans.EVENT_STEP
    assert spans.kind_of(prog(g, u, "jit(_lambda)/mll.mix.dense/dot"),
                         rules) == spans.DENSE_STEP
    # the benchmark's own programs and the feed's hold no trainer scope
    assert spans.kind_of(prog("jit(stack)/concatenate", ""), rules) == ""
    assert spans.kind_of([], rules) == ""


def _collectives():
    """Two chips' event programs, window [0, 100).  Chip 0: a fusion
    [10, 20), an async all-reduce [15, 40) (start at 15, done ends at
    40) of which [20, 40) runs alone, then a collective-permute [50, 60)
    half under a fusion [55, 70).  Chip 1: the same all-reduce [15, 30),
    under a fusion [10, 20) and a fusion [25, 35); a collective in a
    local scan, which does not count."""
    ar_s = "%all-reduce-start.1 = (bf16[8]{0}) all-reduce-start(bf16[8]"
    ar_d = "%all-reduce-done.1 = bf16[8]{0} all-reduce-done((bf16[8]{0})"
    cp = "%collective-permute.2 = bf16[8]{0} collective-permute(bf16[8]"
    fus = "%fusion.3 = bf16[8]{0} fusion(bf16[8]{0} %a)"
    d0 = [(Op(fus, 10, 20), EVENT, ""), (Op(ar_s, 15, 16), EVENT, ""),
          (Op(ar_d, 39, 40), EVENT, ""), (Op(cp, 50, 60), EVENT, ""),
          (Op(fus, 55, 70), EVENT, "")]
    d1 = [(Op(fus, 10, 20), EVENT, ""), (Op(ar_s, 15, 16), EVENT, ""),
          (Op(fus, 25, 35), EVENT, ""), (Op(ar_d, 29, 30), EVENT, ""),
          (Op("%all-gather.4 = bf16[8]{0} all-gather(bf16[2]", 80, 90),
           LOCAL, "")]
    host = [Span("event_step.2", 0, 1, {"slots": 1, "idle": 0}),
            Span("event_step.1", 1, 2, {"slots": 1, "idle": 0})]
    return Marks({0: d0, 1: d1}, host, (0, 100),
                 {LOCAL: spans.LOCAL_SCAN, EVENT: spans.EVENT_STEP})


def test_exposed_collective_time():
    m = _collectives()
    # chip 0: [20, 40) and [50, 55); chip 1: [20, 25); two event slots
    got = run.reader("collective_exposed_ms")(
        types.SimpleNamespace(marks=m, chips=2))
    assert got == pytest.approx((25 + 5) / 2 / 2 / 1e6)
    # one chip: no exchange to read
    assert run.reader("collective_exposed_ms")(
        types.SimpleNamespace(marks=m, chips=1)) is None
    # collectives wholly under compute read 0, not nothing
    hidden = Marks({0: [(Op("%fusion.1 = f(%a)", 0, 50), EVENT, ""),
                        (Op(m.ops[0][3][0].name, 10, 20), EVENT, "")]},
                   m.spans, m.window, m.programs)
    assert run.reader("collective_exposed_ms")(
        types.SimpleNamespace(marks=hidden, chips=2)) == 0.0


def test_readers():
    m = make()
    ctx = types.SimpleNamespace(marks=m, chips=2)
    got = {r: run.reader(r)(ctx) for r in READERS}
    # the hand-made trace holds no collective: that reader finds nothing
    assert got.pop("collective_exposed_ms") is None
    assert got == pytest.approx({
        "grad_slot_ms": 20 / 6 / 1e6, "update_slot_ms": 8 / 4 / 1e6,
        "mix_event_ms": 10 / 2 / 1e6, "host_slot_ms": 73 / 9 / 1e6,
        "dispatch_slot_ms": 17 / 9 / 1e6})
    # a trainer that names nothing: no charged op, no span with stats
    bare = Marks({0: [(op, mod, "") for op, mod, _ in m.ops[0]]}, [],
                 (0, 100))
    ctx = types.SimpleNamespace(marks=bare, chips=2)
    assert all(run.reader(r)(ctx) is None for r in READERS)
    # no device op recorded at all, or no trace
    ctx = types.SimpleNamespace(marks=Marks({}, m.spans, (0, 100)), chips=2)
    assert all(run.reader(r)(ctx) is None for r in READERS)
    assert all(run.reader(r)(types.SimpleNamespace(chips=2)) is None
               for r in READERS)


def test_scope_passes_to_what_xla_adds():
    """A fusion is charged to the latest part it holds; an entry copy of
    an argument or a loop result to the carry; any other instruction
    that names no part to its nearest charged operand, else user, within
    three steps."""
    from spans import Instr
    g = "jit(_lambda)/while/body/closed_call/mll.grads/"
    u = "jit(_unknown)/mll.update/"
    h = "jit(_unknown)/mll.mix.hub/"
    body = "jit(_lambda)/while/body/closed_call"
    instrs = [
        # entry computation 0: arguments copied into a loop and out
        Instr("param.9", "s.params", 9, (), "parameter"),
        Instr("copy.8", "", 8, (9,), "copy"),
        Instr("tuple.20", "", 20, (8,), "tuple"),
        Instr("while.21", "jit(_lambda)/while", 21, (20,), "while",
              (1, 2)),
        Instr("gte.22", "", 22, (21,), "get-tuple-element"),
        Instr("copy.23", "", 23, (22,), "copy"),
        # loop body 1
        Instr("dot.1", g + "dot_general", 1, (), "dot", (), 1),
        Instr("reshape.2", "", 2, (1,), "reshape", (), 1),
        Instr("fusion.3", "", 3, (2,), "fusion", (), 1),
        Instr("constant.5", body, 5, (), "constant", (), 1),
        Instr("broadcast.4", body, 4, (5,), "broadcast", (), 1),
        Instr("tuple.6", "", 6, (4,), "tuple", (), 1),
        Instr("while.7", g + "vmap(transpose(jvp()))/while", 7, (6,),
              "while", (), 1),
        Instr("copy.24", "", 24, (2,), "copy", (), 1),
        # an event program's fusions (computation 3 fused into 30, 4
        # into 31, 5 into 32)
        Instr("mul.10", u + "mul", 10, (), "multiply", (), 3),
        Instr("sub.11", "", 11, (10,), "subtract", (), 3),
        Instr("add.12", h + "add", 12, (11,), "add", (), 3),
        Instr("fusion.30", h + "add", 30, (), "fusion", (3,)),
        Instr("exp.13", g + "exp", 13, (), "exponential", (), 4),
        Instr("mul.14", u + "mul", 14, (13,), "multiply", (), 4),
        Instr("fusion.31", g + "exp", 31, (), "fusion", (4,)),
        Instr("neg.15", g + "neg", 15, (), "negate", (), 5),
        Instr("fusion.32", "", 32, (), "fusion", (5,)),
    ]
    got = spans.parts(0, instrs)
    assert got == {
        "param.9": "", "copy.8": spans.CARRY, "tuple.20": "",
        "while.21": "", "gte.22": "", "copy.23": spans.CARRY,
        "dot.1": spans.GRADS, "reshape.2": spans.GRADS,
        "fusion.3": spans.GRADS, "constant.5": spans.GRADS,
        "broadcast.4": spans.GRADS, "tuple.6": spans.GRADS,
        "while.7": spans.GRADS, "copy.24": spans.GRADS,
        "mul.10": spans.UPDATE, "sub.11": spans.UPDATE,
        "add.12": "mll.mix.hub", "fusion.30": "mll.mix.hub",
        "exp.13": spans.GRADS, "mul.14": spans.UPDATE,
        "fusion.31": spans.UPDATE, "neg.15": spans.GRADS,
        "fusion.32": spans.GRADS}
    assert spans.parts(0, instrs, depth=1)["broadcast.4"] == ""


def test_hlo_read_from_a_trace(tmp_path):
    """Each instruction's op_name and operands as the compiled program's
    HLO text has them, read back from the HLO protos a (CPU) trace holds."""
    import re

    import jax
    import jax.numpy as jnp

    @jax.jit
    def step(w, x):
        with jax.named_scope(spans.GRADS):
            g = jax.grad(lambda w: jnp.sum(jnp.tanh(x @ w) ** 2))(w)
        with jax.named_scope(spans.UPDATE):
            return w - 0.01 * g

    w = jnp.ones((16, 16))
    text = step.lower(w, w).compile().as_text()
    jax.profiler.start_trace(str(tmp_path))
    try:
        jax.block_until_ready(step(w, w))
    finally:
        jax.profiler.stop_trace()
    path = glob.glob(os.path.join(str(tmp_path), "**", "*.xplane.pb"),
                     recursive=True)[0]
    with open(path, "rb") as f:
        data = f.read()
    every = spans.hlo_modules(data)
    (module,) = [k for k in every if k.startswith("jit_step(")]
    assert set(spans.hlo_modules(data, {module})) == {module}
    entry, instrs = every[module]
    names = {i.name: i.op_name for i in instrs}
    by_id = {i.id: i.name for i in instrs}
    lines = {m.group(1): m.group(0) for m in re.finditer(
        r"^\s*(?:ROOT )?%(\S+) = .*$", text, re.M)}
    want = {k: re.search(r'op_name="([^"]*)"', v).group(1)
            for k, v in lines.items() if 'op_name="' in v}
    assert want and all(names[k] == v for k, v in want.items())
    for i in instrs:
        assert all(f"%{by_id[o]}" in lines[i.name] for o in i.operands)
        assert re.search(rf" {re.escape(i.opcode)}\(", lines[i.name])
    assert sum(len(i.operands) for i in instrs) > len(instrs) / 2
    # each computation's instructions, as the text groups them
    comps = {}
    for m in re.finditer(r"^(?:ENTRY )?%(\S+) .*?\{\n(.*?)^\}", text,
                         re.M | re.S):
        comps[m.group(1)] = set(re.findall(r"^\s*(?:ROOT )?%(\S+) = ",
                                           m.group(2), re.M))
    held = {}
    for i in instrs:
        held.setdefault(i.computation, set()).add(i.name)
    assert sorted(map(sorted, held.values())) \
        == sorted(map(sorted, comps.values()))
    (entry_name,) = re.findall(r"^ENTRY %(\S+) ", text, re.M)
    assert held[entry] == comps[entry_name]
    name_of = {cid: next(k for k, v in comps.items() if v == got)
               for cid, got in held.items()}
    fusions = [i for i in instrs if i.opcode == "fusion"]
    assert fusions and all(
        len(i.called) == 1 and f"calls=%{name_of[i.called[0]]}" in
        lines[i.name] for i in fusions)
    assert {spans.GRADS, spans.UPDATE} \
        <= set(spans.parts(entry, instrs).values())
