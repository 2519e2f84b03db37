"""Operation and byte counts against hand counts at a small shape, and the
table of peaks."""
import pytest

from bench import flops, harness

M = {"z_dim": 2, "gen_hidden": [5], "disc_hidden": [6], "pac": 2,
     "batch_size": 4}


def test_train_step_by_hand():
    G, G0 = 2 * 4 * (25 + 40), 2 * 4 * 25          # 520, 200
    # critic: 2 packs of 2 x (4 + 3) = 14 inputs -> 6 -> 1
    C, C0 = 2 * 2 * (14 * 6 + 6), 2 * 2 * 14 * 6   # 360, 336
    critic_update = G + 12 * C - 2 * C0            # 4168
    generator_update = 3 * G - G0 + 2 * C          # 2080
    assert flops.train_step(M, 3, 4) == (critic_update
                                         + generator_update) / 4 == 1562


def test_kernel_bytes_by_hand():
    # 2 rows, a tanh lane and a 3-wide softmax span: logits + outputs
    # (4 lanes each) and 3 uniforms per row, 4 bytes each
    assert flops.segment_activations_bytes(2, [1, 3],
                                           ["tanh", "softmax"]) == 88
    assert flops.weighted_agg_bytes(5, 10) == 4 * 10 * 6
    assert flops.weighted_agg_flops(5, 10) == 100


def test_peaks_by_device_kind():
    v5e = harness.peaks_for("TPU v5 lite")
    assert v5e["flops_bf16"] == 197e12 and v5e["hbm_bytes_per_s"] == 819e9
    with pytest.raises(harness.BenchError):
        harness.peaks_for("TPU v9 imaginary")
