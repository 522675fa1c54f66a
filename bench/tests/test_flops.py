"""The operation count behind ``train_mfu`` and the peak table."""
import importlib.util
import os

import pytest

from bench.peaks import peaks_for

HERE = os.path.dirname(os.path.abspath(__file__))


def _flops():
    path = os.path.join(HERE, "..", "flops", "whisper-base.py")
    spec = importlib.util.spec_from_file_location("whisper_flops", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


TINY = {"d_model": 64, "n_heads": 2, "n_kv_heads": 2, "head_dim": 32,
        "d_ff": 128, "vocab": 300, "n_frontend_tokens": 24,
        "n_encoder_layers": 1, "n_layers": 1}


def test_tiny_config_matches_the_layer_sum_by_hand():
    # d=64, attention width 64, F=24 frames, S=16 tokens, d_ff=128, V=300
    enc = (4 * 2 * 24 * 64 * 64          # Q, K, V, O over the frames
           + 2 * 2 * 24 * 24 * 64        # scores and weighted sum
           + 3 * 2 * 24 * 64 * 128)      # SwiGLU
    dec = (4 * 2 * 16 * 64 * 64          # self-attention projections
           + 2 * 2 * 16 * 16 * 64        # self scores and sum, all pairs
           + 2 * 2 * 16 * 64 * 64        # cross Q and O over the tokens
           + 2 * 2 * 24 * 64 * 64        # cross K and V over the frames
           + 2 * 2 * 16 * 24 * 64        # cross scores and sum
           + 3 * 2 * 16 * 64 * 128)      # SwiGLU
    head = 2 * 16 * 64 * 300
    assert (enc, dec, head) == (2113536, 2129920, 614400)
    f = _flops()
    assert f.forward_flops_per_utterance(TINY, 16) == enc + dec + head
    assert f.train_flops_per_token(TINY, 16) == 3 * (enc + dec + head) / 16


def test_whisper_base_is_about_517_gflop_per_utterance():
    cfg = {"d_model": 512, "n_heads": 8, "n_kv_heads": 8, "head_dim": 64,
           "d_ff": 2048, "vocab": 51865, "n_frontend_tokens": 1500,
           "n_encoder_layers": 6, "n_layers": 6}
    per_utt = 3 * _flops().forward_flops_per_utterance(cfg, 448)
    assert per_utt == pytest.approx(517.3e9, rel=1e-3)


def test_peaks_known_device_and_unknown_raises():
    assert peaks_for("TPU v5 lite")["bf16_flops_per_s"] == 197e12
    with pytest.raises(KeyError):
        peaks_for("TPU v99 imaginary")
