"""Operations and bytes from shapes, and the peak table."""
import pytest

from bench import reference, registry, work
from bench.peaks import PEAKS, peaks_for


@pytest.fixture(scope="module")
def phi4():
    return reference.dims_of(registry.cell("phi4-mini.chat").config)


def test_gemm():
    w = work.gemm(16, 8192, 3072)
    assert w.flops == 2 * 16 * 8192 * 3072
    assert w.bytes == 2 * (16 * 3072 + 3072 * 8192 + 16 * 8192)
    assert work.gemm(4, 4, 4, extra_mn=2).bytes == 2 * (16 + 16 + 3 * 16)


def test_layer_gemms_count_seven_projections(phi4):
    d = phi4
    w = work.layer_gemms(d, 1)
    weights = (d.d_model * (d.heads + 2 * d.kv_heads) * d.head_dim
               + d.heads * d.head_dim * d.d_model + 3 * d.d_model * d.d_ff)
    assert w.flops == 2 * weights
    assert weights < w.bytes / 2 < weights * 1.01   # weight-read bound


def test_flash_attention_causal_pairs(phi4):
    d = phi4
    w = work.flash_attention(d, 512)
    assert w.flops == 4 * d.heads * d.head_dim * 512 * 513 / 2
    assert w.bytes == 2 * d.head_dim * 512 * (2 * d.heads + 2 * d.kv_heads)


def test_decode_bytes_count_held_positions_not_max_len(phi4):
    d = phi4
    short = work.decode_step(d, [100] * 12)
    long = work.decode_step(d, [1100] * 12)
    kv_row = 2 * d.kv_heads * d.head_dim * 2          # K and V, bf16
    assert long.bytes - short.bytes == d.layers * kv_row * 12 * 1000
    assert long.flops - short.flops == (
        4 * d.layers * d.heads * d.head_dim * 12 * 1000)
    # The weights dominate a short step: about 7.7 GB for phi4-mini.
    assert 7.0e9 < short.bytes < 8.5e9
    # An idle slot costs nothing: only the active slots are counted.
    assert work.decode_step(d, [100] * 4).bytes < short.bytes


def test_prefill_and_model(phi4):
    d = phi4
    w = work.prefill(d, 512)
    per_layer = work.layer_gemms(d, 512) + work.flash_attention(d, 512)
    assert w.flops == pytest.approx(d.layers * per_layer.flops
                                    + 2 * d.vocab * d.d_model)
    # 2 x params x tokens, to within attention and the head.
    assert 2 * 3.0e9 * 512 < w.flops < 2 * 4.5e9 * 512


def test_seconds_and_bound():
    p = peaks_for("TPU v5 lite")
    mem = work.Work(flops=1e9, bytes=819e9)
    assert mem.seconds(p) == pytest.approx(1.0) and mem.bound(p) == "memory"
    comp = work.Work(flops=197e12 * 2, bytes=1.0)
    assert comp.seconds(p) == pytest.approx(2.0)
    assert comp.bound(p) == "compute"


def test_peaks_keyed_by_device_kind():
    p = peaks_for("TPU v5 lite")
    assert (p.bf16_flops, p.hbm_bytes) == (197e12, 819e9)
    with pytest.raises(KeyError, match="no peaks"):
        peaks_for("TPU v9 imaginary")
    assert "cpu" not in PEAKS
