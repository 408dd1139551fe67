"""The FLOP and byte functions against hand-worked values for both
configurations, the roofline arithmetic, and the table of peaks."""
import pytest

from benchmarks import manifest, peaks, shapes

OPT13 = manifest.data_file("configs", "opt-1.3b")
OPT67 = manifest.data_file("configs", "opt-6.7b")


def test_matmul_params_by_hand():
    # OPT-1.3B: 4*2048^2 + 2*2048*8192 = 50 331 648 a layer;
    # head 2048*50272 = 102 957 056
    c = dict(OPT13, num_hidden_layers=4)
    assert shapes.lm_matmul_params(c) == 4 * 50_331_648 + 102_957_056
    # OPT-6.7B: 4*4096^2 + 2*4096*16384 = 201 326 592 a layer
    c = dict(OPT67, num_hidden_layers=1)
    assert shapes.lm_matmul_params(c) == 201_326_592 + 4096 * 50272


def test_param_count_by_hand():
    # ISSUE 23: 201 M a layer and 420 M in embedding, positions and the
    # untied head at 6.7B widths; 50.4 M a layer at 1.3B widths
    one = shapes.lm_param_count(dict(OPT67, num_hidden_layers=1))
    zero = shapes.lm_param_count(dict(OPT67, num_hidden_layers=0))
    assert one - zero == 201_326_592 + 4 * 4096 + 16384 + 4096 + 4 * 4096
    assert zero == 2 * 50272 * 4096 + 2048 * 4096 + 50272 + 2 * 4096
    per13 = (shapes.lm_param_count(dict(OPT13, num_hidden_layers=2))
             - shapes.lm_param_count(dict(OPT13, num_hidden_layers=1)))
    assert round(per13 / 1e6, 1) == 50.4


def test_train_step_flops_by_hand():
    """ISSUE 23's own figure: 15.8 TFLOP a step at L=4, batch 4 x 2048
    (14.96 matmul + 0.82 attention)."""
    f = shapes.lm_train_step_flops(dict(OPT13, num_hidden_layers=4), 4,
                                   2048)
    assert f["matmul"] == 6 * (4 * 50_331_648 + 102_957_056) * 8192
    # causal attention: 6 matmul-passes x (2*B*H*S*S*D / 2) x L
    assert f["attention"] == 6 * (2 * 4 * 32 * 2048 * 2048 * 64 / 2) * 4
    assert round(f["total"] / 1e12, 1) == 15.8


def test_flash_cost_by_hand():
    c = shapes.flash_attention_train_cost(
        dict(OPT13, num_hidden_layers=1), 4, 2048)
    one = 2 * 4 * 32 * 2048 * 2048 * 64 / 2
    assert c["flops"] == 7 * one
    assert c["bytes"] == 12 * (4 * 32 * 2048 * 64 * 2)
    least, bound = shapes.roofline_least_seconds(
        c, peaks.peaks_for("TPU v5 lite"))
    assert bound == "compute"
    assert least == pytest.approx(7 * one / 197e12)


def test_paged_cost_by_hand():
    # one row with 1000 live tokens, 16 layers of OPT-6.7B: K and V of
    # 4096 bf16 values a token a layer
    c = shapes.paged_attention_decode_cost(OPT67, 1000, 1)
    assert c["bytes"] == 2 * 1000 * 4096 * 2 * 16 + 2 * 4096 * 2 * 16
    assert c["flops"] == 4 * 1000 * 4096 * 16
    least, bound = shapes.roofline_least_seconds(
        c, peaks.peaks_for("TPU v5 lite"))
    assert bound == "memory"
    assert least == pytest.approx(c["bytes"] / 819e9)


def test_unknown_device_kind_is_an_error():
    assert peaks.peaks_for("TPU v5 lite")["flops_per_s"] == 197e12
    assert peaks.peaks_for("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError, match="not in the benchmark's table"):
        peaks.peaks_for("cpu")
