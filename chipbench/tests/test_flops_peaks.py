"""FLOPs per token against hand counts, and the peaks table."""
import pytest

from chipbench import peaks
from chipbench.registry import family
from chipbench.tests.tiny import REPO


def test_dense_flops_by_hand():
    m = {"block_pattern": ["global"], "num_layers": 2, "d_model": 8,
         "num_heads": 2, "num_kv_heads": 1, "head_dim": 4, "d_ff": 16,
         "vocab_size": 10}
    # per layer: q 8x8, k 8x4, v 8x4, o 8x8 = 192; mlp 3 x 8 x 16 = 384;
    # scores and values: 2 x seq(5) x 8 = 80  -> 656 MACs
    # head: 8 x 10 = 80 MACs; total 2 x 656 + 80 = 1392 MACs
    assert family(REPO, "qwen2").flops_per_token(m, 5) == 6 * 1392


def test_ssd_flops_by_hand():
    m = {"block_pattern": ["ssd"], "num_layers": 1, "d_model": 4,
         "ssm_state": 2, "ssm_head_dim": 4, "ssm_expand": 2,
         "ssm_conv_width": 3, "ssm_chunk": 5, "vocab_size": 6}
    # di 8, 2 heads: in_proj 4 x (16 + 4 + 2) = 88, out_proj 8 x 4 = 32;
    # conv 3 x (8 + 4) = 36; chunk terms 5x2 + 5x8 + 2x8x2 = 82
    # -> 238 MACs; head 4 x 6 = 24 MACs; total 262
    assert family(REPO, "mamba2").flops_per_token(m, 7) == 6 * 262


def test_peaks_are_keyed_by_device_kind():
    assert peaks.peak("TPU v5 lite")["bf16_flops_per_s"] == 197e12
    with pytest.raises(KeyError):
        peaks.peak("TPU v9 imaginary")


def test_step_mfu_reads_the_device_busy_time():
    import types

    from chipbench.registry import metric_reader
    read = metric_reader(REPO, "step.mfu")
    trace = types.SimpleNamespace(busy_s=2.0)
    ctx = types.SimpleNamespace(trace=trace, chips=1, device_kind="TPU v5 lite",
                                traced_tokens=1000, flops_per_token=197e9,
                                peaks=peaks)
    # 197e12 FLOP in 2 s of a 197e12 FLOP/s chip
    assert read(ctx) == pytest.approx(50.0)
    assert read(types.SimpleNamespace(**{**vars(ctx), "trace": None})) is None
