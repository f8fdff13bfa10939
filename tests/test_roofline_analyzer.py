"""Unit tests for the trip-count-aware HLO cost analyzer — the §Roofline
measurement tool itself (synthetic HLO fixtures + a live compiled module)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.analysis.roofline import (HloCostModel, _parse_shape, _shape_bytes,
                                     model_flops)

SYNTH = """\
HloModule jit_f

%add (a: f32[], b: f32[]) -> f32[] {
  %a = f32[] parameter(0)
  %b = f32[] parameter(1)
  ROOT %r = f32[] add(%a, %b)
}

%body (p: (s32[], f32[8,16])) -> (s32[], f32[8,16]) {
  %p = (s32[], f32[8,16]) parameter(0)
  %i = s32[] get-tuple-element(%p), index=0
  %x = f32[8,16]{1,0} get-tuple-element(%p), index=1
  %w = f32[16,16]{1,0} constant({...})
  %dot.1 = f32[8,16]{1,0} dot(%x, %w), lhs_contracting_dims={1}, rhs_contracting_dims={0}
  %ar = f32[8,16]{1,0} all-reduce(%dot.1), channel_id=1, replica_groups=[4,4]<=[16], use_global_device_ids=true, to_apply=%add
  %one = s32[] constant(1)
  %ni = s32[] add(%i, %one)
  ROOT %t = (s32[], f32[8,16]) tuple(%ni, %ar)
}

%cond (p: (s32[], f32[8,16])) -> pred[] {
  %p = (s32[], f32[8,16]) parameter(0)
  %i = s32[] get-tuple-element(%p), index=0
  %n = s32[] constant(12)
  ROOT %lt = pred[] compare(%i, %n), direction=LT
}

ENTRY %main (x: f32[8,16]) -> f32[8,16] {
  %x = f32[8,16]{1,0} parameter(0)
  %zero = s32[] constant(0)
  %t0 = (s32[], f32[8,16]) tuple(%zero, %x)
  %w2 = f32[16,16]{1,0} constant({...})
  %dot.0 = f32[8,16]{1,0} dot(%x, %w2), lhs_contracting_dims={1}, rhs_contracting_dims={0}
  %wh = (s32[], f32[8,16]) while(%t0), condition=%cond, body=%body
  ROOT %out = f32[8,16]{1,0} get-tuple-element(%wh), index=1
}
"""


class TestSyntheticHlo:
    def setup_method(self, _):
        self.cm = HloCostModel(SYNTH)

    def test_trip_count_extracted(self):
        assert self.cm.trips.get("body") == 12

    def test_dot_flops_multiplied_by_trips(self):
        # dot: 2*8*16*16 = 4096 flops; f32-sourced -> x4 penalty
        per_dot = 2 * 8 * 16 * 16 * self.cm.F32_DOT_PENALTY
        # one dot at top level + one dot x12 in the body
        assert self.cm.dot_flops() == pytest.approx(per_dot * 13)

    def test_collective_ring_model(self):
        wire, by_kind = self.cm.collective_wire_bytes(16)
        # all-reduce of 8*16*4B in groups of 4, ring: 2*S*(g-1)/g, x12 trips
        s = 8 * 16 * 4
        assert by_kind["all-reduce"] == pytest.approx(2 * s * 3 / 4 * 12)

    def test_entry_found(self):
        assert self.cm.entry == "main"


def test_shape_parsing():
    assert _parse_shape("f32[8,16]{1,0}") == ("f32", (8, 16))
    assert _parse_shape("bf16[2,3,4]") == ("bf16", (2, 3, 4))
    assert _parse_shape("pred[]")[1] == ()
    assert _shape_bytes("(f32[8,16]{1,0}, bf16[4]{0})") == 8 * 16 * 4 + 4 * 2


class TestLiveModule:
    """Against a real compiled scan program: the analyzer must out-count
    cost_analysis by ~the trip factor (the while-body undercount)."""

    def test_scan_trip_correction(self):
        L, D = 16, 64

        def f(ws, x):
            def body(c, w):
                return jnp.tanh(c @ w), ()
            y, _ = jax.lax.scan(body, x, ws)
            return y

        ws = jnp.zeros((L, D, D), jnp.float32)
        x = jnp.zeros((8, D), jnp.float32)
        compiled = jax.jit(f).lower(ws, x).compile()
        cm = HloCostModel(compiled.as_text())
        raw = compiled.cost_analysis()["flops"]
        ours = cm.dot_flops()
        per_layer = 2 * 8 * D * D
        # our count must cover all L layers (within the f32 penalty factor)
        assert ours >= per_layer * L
        # XLA's raw count misses the trip multiplication
        assert raw < per_layer * L

    def test_convert_only_fusion_free(self):
        hlo = """\
HloModule m

%fused_convert (p0: bf16[128,128]) -> f32[128,128] {
  %p0 = bf16[128,128]{1,0} parameter(0)
  ROOT %c = f32[128,128]{1,0} convert(%p0)
}

ENTRY %main (x: bf16[128,128]) -> f32[128,128] {
  %x = bf16[128,128]{1,0} parameter(0)
  ROOT %f = f32[128,128]{1,0} fusion(%x), kind=kLoop, calls=%fused_convert
}
"""
        cm = HloCostModel(hlo)
        assert cm.hbm_bytes() == 0.0  # convert-only: fuses into a dot on TPU

    def test_dus_fusion_counts_slice_only(self):
        hlo = """\
HloModule m

%fused_dus (p0: s32[], p1: f32[1,64], p2: f32[16,64]) -> f32[16,64] {
  %p2 = f32[16,64]{1,0} parameter(2)
  %p1 = f32[1,64]{1,0} parameter(1)
  %p0 = s32[] parameter(0)
  %z = s32[] constant(0)
  ROOT %dus = f32[16,64]{1,0} dynamic-update-slice(%p2, %p1, %p0, %z)
}

ENTRY %main (i: s32[], u: f32[1,64], buf: f32[16,64]) -> f32[16,64] {
  %i = s32[] parameter(0)
  %u = f32[1,64]{1,0} parameter(1)
  %buf = f32[16,64]{1,0} parameter(2)
  ROOT %f = f32[16,64]{1,0} fusion(%i, %u, %buf), kind=kLoop, calls=%fused_dus
}
"""
        cm = HloCostModel(hlo)
        # 2x the update slice (read-modify-write) + scalar index,
        # not the full buffer
        assert cm.hbm_bytes() == pytest.approx(2 * 1 * 64 * 4 + 4)


def test_model_flops_formulas():
    from repro.configs.base import SHAPE_CELLS
    from repro.configs.registry import get_arch
    yi = get_arch("yi-9b")
    mf_train = model_flops(yi, SHAPE_CELLS["train_4k"])
    # 6*N*D dominates: N~8.8e9 params, D=256*4096 tokens
    assert mf_train == pytest.approx(6 * 8.3e9 * 256 * 4096, rel=0.25)
    mf_dec = model_flops(yi, SHAPE_CELLS["decode_32k"])
    assert mf_dec < mf_train / 1000  # one token per sequence
    moe = get_arch("moonshot-v1-16b-a3b")
    # MoE uses ACTIVE params only
    assert model_flops(moe, SHAPE_CELLS["train_4k"]) < \
        6 * moe.param_count() * 256 * 4096
