"""Unit tests for the HLO text analyzer (trip-count multipliers, byte model,
collective classification) on synthetic HLO and a real compiled module."""
import textwrap

import numpy as np

from repro.launch.hlo_analysis import (HloCosts, analyze_hlo,
                                       compute_multipliers, parse_computations,
                                       roofline_terms, _crosses_pods,
                                       _shape_bytes)

SYNTH = textwrap.dedent("""\
    HloModule test

    %body.1 (p: (s32[], f32[128,256])) -> (s32[], f32[128,256]) {
      %p = (s32[], f32[128,256]{1,0}) parameter(0)
      %i = s32[] get-tuple-element(%p), index=0
      %x = f32[128,256]{1,0} get-tuple-element(%p), index=1
      %w = f32[256,256]{1,0} constant({...})
      %d = f32[128,256]{1,0} dot(%x, %w), lhs_contracting_dims={1}, rhs_contracting_dims={0}
      %ar = f32[128,256]{1,0} all-reduce(%d), replica_groups=[32,16]<=[512], to_apply=%add.2
      ROOT %t = (s32[], f32[128,256]{1,0}) tuple(%i, %ar)
    }

    %cond.1 (p2: (s32[], f32[128,256])) -> pred[] {
      %p2 = (s32[], f32[128,256]{1,0}) parameter(0)
      %i2 = s32[] get-tuple-element(%p2), index=0
      %c = s32[] constant(12)
      ROOT %lt = pred[] compare(%i2, %c), direction=LT
    }

    %add.2 (a: f32[], b: f32[]) -> f32[] {
      %a = f32[] parameter(0)
      %b = f32[] parameter(1)
      ROOT %s = f32[] add(%a, %b)
    }

    %fused_dus.3 (fp0: f32[12,128,256], fp1: f32[128,256], fp2: s32[]) -> f32[12,128,256] {
      %fp0 = f32[12,128,256]{2,1,0} parameter(0)
      %fp1 = f32[128,256]{1,0} parameter(1)
      %fp2 = s32[] parameter(2)
      %r = f32[1,128,256]{2,1,0} reshape(%fp1)
      ROOT %dus = f32[12,128,256]{2,1,0} dynamic-update-slice(%fp0, %r, %fp2, %fp2, %fp2)
    }

    ENTRY %main.9 (arg0: f32[128,256], buf: f32[12,128,256]) -> f32[12,128,256] {
      %arg0 = f32[128,256]{1,0} parameter(0)
      %buf = f32[12,128,256]{1,0} parameter(1)
      %zero = s32[] constant(0)
      %init = (s32[], f32[128,256]{1,0}) tuple(%zero, %arg0)
      %loop = (s32[], f32[128,256]{1,0}) while(%init), condition=%cond.1, body=%body.1, backend_config={"known_trip_count":{"n":"12"}}
      %y = f32[128,256]{1,0} get-tuple-element(%loop), index=1
      ROOT %fus = f32[12,128,256]{2,1,0} fusion(%buf, %y, %zero), kind=kLoop, calls=%fused_dus.3
    }
    """)


def test_parse_and_multipliers():
    comps = parse_computations(SYNTH)
    assert set(comps) >= {"body.1", "cond.1", "add.2", "fused_dus.3", "main.9"}
    mult = compute_multipliers(comps, "main.9")
    assert mult["body.1"] == 12.0
    assert mult["cond.1"] == 12.0
    assert mult["fused_dus.3"] == 1.0
    assert mult["add.2"] == 12.0          # called from the loop's all-reduce


def test_flops_trip_count_corrected():
    costs = analyze_hlo(SYNTH)
    dot_once = 2 * 128 * 256 * 256
    assert costs.dot_flops == 12 * dot_once


def test_collective_bytes_and_counts():
    costs = analyze_hlo(SYNTH, pod_stride=256)
    ar_bytes = 128 * 256 * 4
    assert costs.collective_bytes == 12 * ar_bytes
    assert costs.collective_counts["all-reduce"] == 12
    # iota groups [32,16]<=[512]: contiguous stride-1 groups of 16 — no pod
    # crossing with stride 256
    assert costs.dcn_bytes == 0


ASYNC = textwrap.dedent("""\
    HloModule async

    ENTRY %main.1 (x: bf16[24,14,64]) -> bf16[24,14,64] {
      %x = bf16[24,14,64]{2,1,0} parameter(0)
      %cps = (bf16[24,14,64]{2,1,0}, bf16[24,14,64]{2,1,0}, u32[], u32[]) collective-permute-start(%x), channel_id=1, source_target_pairs={{0,1},{1,0}}
      ROOT %cpd = bf16[24,14,64]{2,1,0} collective-permute-done(%cps)
    }
    """)


def test_async_collective_pairs_count_once():
    """TPU HLO splits a collective into X-start / X-done: one call, and
    the bytes of the done's result."""
    costs = analyze_hlo(ASYNC)
    assert costs.collective_counts == {"collective-permute": 1}
    assert costs.collective_bytes == 24 * 14 * 64 * 2


def test_dus_fusion_in_place_bytes():
    """The DUS-rooted fusion must charge ~2 update slices, not the full
    12x buffer."""
    costs = analyze_hlo(SYNTH)
    update = 128 * 256 * 4
    full_buf = 12 * update
    # total bytes should be far below charging the full buffer per op
    assert costs.bytes < 12 * (2 * full_buf) * 0.5


def test_crosses_pods_iota_and_list():
    # groups of (2 pods x 16): ids 0 and 256 in one group
    line = "x = f32[4] all-reduce(%a), replica_groups=[256,2]<=[2,256]T(1,0)"
    assert _crosses_pods(line, 256)
    line2 = "x = f32[4] all-reduce(%a), replica_groups=[32,16]<=[512]"
    assert not _crosses_pods(line2, 256)
    line3 = "x = f32[4] all-reduce(%a), replica_groups={{0,256},{1,257}}"
    assert _crosses_pods(line3, 256)
    line4 = "x = f32[4] all-reduce(%a), replica_groups={{0,1},{2,3}}"
    assert not _crosses_pods(line4, 256)


def test_shape_bytes_tuples():
    assert _shape_bytes("f32[128,256]{1,0}") == 128 * 256 * 4
    assert _shape_bytes("(s32[], bf16[2,4]{1,0}, pred[8]{0})") == 4 + 16 + 8
    assert _shape_bytes("token[]") == 0


def test_roofline_terms_dominant():
    c = HloCosts(flops=197e12, bytes=819e9 * 3, collective_bytes=50e9)
    rl = roofline_terms(c, 256)
    assert abs(rl.compute_s - 1.0) < 1e-9
    assert abs(rl.memory_s - 3.0) < 1e-9
    assert abs(rl.collective_s - 1.0) < 1e-9
    assert rl.dominant == "memory"
    assert rl.flops == 197e12 * 256       # global scale-up


def test_real_module_scan_correction():
    """End-to-end on a real compiled lax.scan module (1 device)."""
    import jax
    import jax.numpy as jnp

    def f(w, x):
        def body(x, wi):
            return jnp.tanh(x @ wi), None
        y, _ = jax.lax.scan(body, x, w)
        return y.sum()

    L, D = 5, 64
    comp = jax.jit(f).lower(
        jax.ShapeDtypeStruct((L, D, D), jnp.float32),
        jax.ShapeDtypeStruct((32, D), jnp.float32)).compile()
    costs = analyze_hlo(comp.as_text())
    analytic = 2 * 32 * D * D * L
    assert costs.dot_flops == analytic
    assert costs.unknown_trip_whiles == 0
