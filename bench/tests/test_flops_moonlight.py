"""The operation count behind ``train_mfu.moonlight``."""
import importlib.util
import json
import os

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))


def _load(rel, name):
    spec = importlib.util.spec_from_file_location(
        name, os.path.join(HERE, "..", rel))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


FLOPS = _load("flops/moonlight-16b-a3b.py", "moonlight_flops")


def _config(name):
    with open(os.path.join(HERE, "..", name)) as f:
        return json.load(f)


def test_tiny_config_matches_the_layer_sum_by_hand():
    cfg = _config("tests/data/tiny-moonlight.json")
    # d=64, 4 heads, latent 32, nope 16, rope 8, v 16, 3 layers (1 dense),
    # dense width 96, 16 routed experts of width 32, 2 shared, vocab 300
    attn = (2 * (64 * 4 * 24 + 64 * 40 + 32 * 4 * 32 + 4 * 16 * 64)
            + (16 + 1) / 2 * 2 * 4 * (24 + 16))      # S = 16
    dense = 3 * 2 * 64 * 96
    moe = 2 * 64 * 16 + 3 * 2 * 64 * 2 * 32
    head = 2 * 64 * 300
    per_token = 3 * attn + dense + 2 * moe + head
    assert FLOPS.dense_forward_flops_per_token(cfg, 16) == per_token
    assert FLOPS.expert_forward_flops_per_assignment(cfg) == 3 * 2 * 64 * 32
    assert FLOPS.train_flops(cfg, 16, 10, 7) == 3 * (
        10 * per_token + 7 * 3 * 2 * 64 * 32)


def test_cut_is_about_2_3_gflop_a_token():
    cfg = _config("configs/moonlight-16b-a3b.json")
    dense = FLOPS.dense_forward_flops_per_token(cfg, 8192)
    # an even router sends 6 x 8/64 = 0.75 assignments a token to each
    # MoE layer's held experts, 4 MoE layers
    per_token = 3 * (dense + 4 * 0.75
                     * FLOPS.expert_forward_flops_per_assignment(cfg))
    assert dense == pytest.approx(709.4e6, rel=1e-3)
    assert per_token == pytest.approx(2.28e9, rel=1e-2)
