"""The trace reductions, on a hand-made trace."""
import dataclasses

import pytest

import traces
from traces import Op, Trace


def make():
    # window [0, 100).  Device 0: fusions at [10, 30) and [20, 40)
    # (overlapping), an all-reduce at [50, 70) half covered by a kernel at
    # [60, 80), and a loop op spanning [10, 80) that must not hide the
    # all-reduce.  Device 1: one op [0, 50) and a collective [90, 120).
    d0 = [Op("%while.9 = (s32[]) while(s32[] %a)", 10, 80),
          Op("%fusion.1 = bf16[8]{0} fusion(bf16[8]{0} %a)", 10, 30),
          Op("%fusion.2 = bf16[8]{0} fusion(bf16[8]{0} %b)", 20, 40),
          Op("%all-reduce.3 = bf16[8]{0} all-reduce(bf16[8]{0} %c)", 50, 70),
          Op("%closed_call.4 = (bf16[2,4]{1,0}, f32[2,1]{1,0}) "
             "custom-call(bf16[2,4]{1,0} %d", 60, 80)]
    d1 = [Op("%convolution.7 = bf16[8]{0} convolution(bf16[8]{0} %e)", 0, 50),
          Op("%collective-permute-done.1 = bf16[8]{0} "
             "collective-permute-done(bf16[8]{0} %f)", 90, 120)]
    mods = {0: [Op("jit__lambda(123)", 10, 40), Op("jit__unknown(9)", 50, 80)],
            1: [Op("jit__lambda(123)", 0, 50), Op("jit__unknown(9)", 90, 120)]}
    host = [Op("run_span", 0, 100), Op("local_scan", 35, 45),
            Op("draw_batch", 85, 95)]
    return Trace({0: d0, 1: d1}, mods, host, (0, 100))


def test_merge_and_busy():
    assert traces.merge([(10, 30), (20, 40), (50, 70), (60, 80)], 0, 100) \
        == [(10, 40), (50, 80)]
    assert traces.merge([(-5, 5), (95, 120)], 0, 100) == [(0, 5), (95, 100)]
    assert traces.busy_ns(make()) == {0: 70, 1: 60}


def test_idle_share():
    assert traces.idle_share(make()) == pytest.approx(0.35)


def test_program_and_kernel_time():
    tr = make()
    assert traces.module_ns(tr, {"jit__lambda(123)"}) == 30 + 50
    assert traces.module_ns(tr, {"jit__unknown(9)"}) == 30 + 30
    assert traces.module_ns(tr, {"jit__lambda(123)", "jit__unknown(9)"}) \
        == 30 + 50 + 30 + 30
    assert traces.module_ns(tr, set()) == 0
    assert traces.op_ns(tr, r"\) custom-call\(") == 20


def test_interval_difference():
    assert traces.minus([(0, 10), (20, 30)], [(2, 4), (8, 22), (25, 26)]) \
        == [(0, 2), (4, 8), (22, 25), (26, 30)]
    assert traces.minus([(0, 10)], []) == [(0, 10)]
    assert traces.minus([(0, 10)], [(-5, 15)]) == []
    assert traces.minus([(5, 6)], [(0, 1), (7, 9)]) == [(5, 6)]


def test_name_table_tells_the_kernels_apart():
    import json
    import os
    import re
    from conftest import BENCH
    with open(os.path.join(BENCH, "names.json")) as f:
        names = json.load(f)
    fwd = ("%closed_call.58 = (bf16[4,1,14,256,64]{4,3,2,1,0:T(8,128)(2,1)}, "
           "f32[4,1,14,256,1]{4,3,2,1,0:T(8,128)}) custom-call(bf16[4,1")
    dq = ("%closed_call.57 = bf16[4,1,14,256,64]{4,3,2,1,0:T(8,128)(2,1)} "
          "custom-call(bf16[4")
    dkv = ("%closed_call.58 = (bf16[4,1,2,256,64]{4,3,2,1,0:T(8,128)(2,1)}, "
           "bf16[4,1,2,256,64]{4,3,2,1,0:T(8,128)(2,1)}) custom-call(bf16")
    fus = ("%fusion.245 = (bf16[4,24,896]{2,1,0}, bf16[4,24,896]{2,1,0}) "
           "fusion(bf16[24")
    ar = "%all-reduce.3 = bf16[2,896]{1,0} all-reduce(bf16[2,896]{1,0} %x)"
    got = {k: [bool(re.search(names[k], x)) for x in (fwd, dq, dkv, fus, ar)]
           for k in ("flash_fwd", "flash_bwd")}
    assert got == {"flash_fwd": [1, 0, 0, 0, 0],
                   "flash_bwd": [0, 1, 1, 0, 0]}


def test_breakdown():
    tr = make()
    ops = dict(traces.top_ops(tr))
    assert ops["convolution"] == pytest.approx(50 / 2 / 1e9)
    assert ops["fusion"] == pytest.approx(40 / 2 / 1e9)
    assert "while" not in ops
    # device 0's gaps: [0, 10) and [80, 100), each labelled by the
    # innermost span around its middle
    assert traces.idle_gaps(tr) == [["draw_batch", 20 / 1e9],
                                    ["run_span", 10 / 1e9]]


def test_readers():
    """Each per-layer reader on the hand-made trace: two chips, each with
    its `local_scan` and `event_step` executions, the programs' kinds as
    their scopes give them."""
    import json
    import os
    import types

    import flops
    import run
    import spans
    from conftest import BENCH, PEAKS, tiny_cell
    from weights import Spec
    with open(os.path.join(BENCH, "names.json")) as f:
        names = json.load(f)
    cell = tiny_cell("qwen2-0.5b.train.local")
    spec = Spec.from_json(cell["config"])
    win = {"seconds": 1.0, "slots": 3, "local_slots": 2, "event_slots": 1,
           "tokens": 3 * 4 * 32}
    tr = make()
    marks = spans.Marks({d: [(o, "", "") for o in ops]
                         for d, ops in tr.ops.items()}, [], tr.window,
                        {"jit__lambda(123)": spans.LOCAL_SCAN,
                         "jit__unknown(9)": spans.EVENT_STEP})
    ctx = types.SimpleNamespace(
        trace=tr, traces=traces, flops=flops, spec=spec,
        traffic=cell["traffic"], window=win, chips=2, peaks=PEAKS,
        names=names, marks=marks)
    got = {m: run.reader(m)(ctx) for m in (
        "train_mfu", "local_slot_ms", "event_slot_ms",
        "device_idle_share.train", "flash_fwd_roofline")}
    work = flops.train_flops_per_token(spec, 32) * win["tokens"]
    assert got["train_mfu"] == pytest.approx(
        100 * work / (2 * (80 + 60) / 2 / 1e9 * PEAKS["bf16_flops_per_s"]))
    assert got["local_slot_ms"] == pytest.approx(80 / 2 / 2 / 1e6)
    assert got["event_slot_ms"] == pytest.approx(60 / 2 / 1 / 1e6)
    assert got["device_idle_share.train"] == pytest.approx(35.0)
    # the hand-made kernel has the flash forward's outputs: 20 ns
    assert got["flash_fwd_roofline"] == pytest.approx(
        100 * flops.attention_least_seconds(spec, cell["traffic"], 3, PEAKS,
                                            False) / 20e-9)
    # the same programs of unknown kind: no program time is read
    blind = dataclasses.replace(marks, programs=dict.fromkeys(marks.programs,
                                                              ""))
    unknown = types.SimpleNamespace(**dict(vars(ctx), marks=blind))
    assert all(run.reader(m)(unknown) is None
               for m in ("train_mfu", "local_slot_ms", "event_slot_ms"))
    empty = types.SimpleNamespace(**dict(
        vars(ctx), trace=Trace({}, {}, [], (0, 100)), marks=None))
    assert all(run.reader(m)(empty) is None for m in got)
