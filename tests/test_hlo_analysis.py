"""Unit tests for the roofline-term extraction (HLO text parsing).

These are pure-text tests — the parser is the §Roofline data source, so
its byte accounting must be exact on synthetic HLO snippets.
"""
import numpy as np

from repro.launch.hlo_analysis import (
    RooflineTerms, _shape_bytes, collective_bytes_by_op,
    total_collective_bytes,
)
from repro.launch.mesh import V5E

HLO = """
HloModule jit_step

ENTRY %main {
  %p0 = bf16[64,128]{1,0} parameter(0)
  %ag = bf16[64,2048]{1,0} all-gather(%p0), dimensions={1}
  %ar = f32[256]{0} all-reduce(%x), to_apply=%sum
  %rs = f32[16,16]{1,0} reduce-scatter(%y), dimensions={0}
  %a2a = bf16[8,32]{1,0} all-to-all(%z), dimensions={0}
  %cp = u8[1024]{0} collective-permute(%w), source_target_pairs={{0,1}}
  %ags = (bf16[64,2048]{1,0}, bf16[64,128]{1,0}) all-gather-start(%p0)
  %agd = bf16[64,2048]{1,0} all-gather-done(%ags)
  %dot = f32[64,64]{1,0} dot(%a, %b)
}
"""


def test_shape_bytes():
    assert _shape_bytes("bf16[64,128]") == 64 * 128 * 2
    assert _shape_bytes("f32[256]") == 1024
    assert _shape_bytes("(bf16[2,2], f32[3])") == 8 + 12
    assert _shape_bytes("pred[8]") == 8
    assert _shape_bytes("u8[]") == 0 or _shape_bytes("u8[]") == 1  # scalar


def test_collective_bytes_by_op():
    d = collective_bytes_by_op(HLO)
    assert d["all-gather"] == 64 * 2048 * 2 + (64 * 2048 * 2 + 64 * 128 * 2)
    assert d["all-reduce"] == 256 * 4
    assert d["reduce-scatter"] == 16 * 16 * 4
    assert d["all-to-all"] == 8 * 32 * 2
    assert d["collective-permute"] == 1024
    counts = d["_counts"]
    assert counts["all-gather"] == 2          # plain + start, done skipped
    total = total_collective_bytes(HLO)
    assert total == sum(v for k, v in d.items() if not k.startswith("_"))


def test_roofline_terms_bottleneck():
    t = RooflineTerms(arch="a", shape="s", mesh="m", n_chips=256,
                      device_kind=V5E,
                      hlo_flops=197e12,          # exactly 1s of compute
                      hlo_bytes=819e9 * 2,       # 2s of memory
                      collective_bytes=int(50e9 * 3),  # 3s of collective
                      collective_detail={}, model_flops=197e12 * 256)
    assert abs(t.t_compute - 1.0) < 1e-9
    assert abs(t.t_memory - 2.0) < 1e-9
    assert abs(t.t_collective - 3.0) < 1e-9
    assert t.bottleneck == "collective"
    np.testing.assert_allclose(t.useful_flops_ratio, 1.0)
    d = t.to_dict()
    assert d["bottleneck"] == "collective"


def test_chip_peaks_unknown_device_is_an_error():
    import pytest
    from repro.launch.mesh import chip_peaks
    assert chip_peaks(V5E).flops_bf16 == 197e12
    with pytest.raises(ValueError, match="no published peaks"):
        chip_peaks("cpu")
    t = RooflineTerms(arch="a", shape="s", mesh="m", n_chips=1,
                      device_kind="cpu", hlo_flops=1.0, hlo_bytes=1.0,
                      collective_bytes=0, collective_detail={})
    with pytest.raises(ValueError, match="no published peaks"):
        t.t_compute
