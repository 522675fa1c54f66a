"""Operations of one whisper-base training step, counted from the shapes of
the model as the repo implements it (a multiply-add is 2 operations):

* encoder, per layer over F frames: the Q, K, V and output projections
  (4 x 2 F d^2), attention scores and their weighted sum (2 x 2 F^2 d), and
  the SwiGLU MLP (3 x 2 F d d_ff);
* decoder, per layer over S tokens: self-attention projections
  (4 x 2 S d^2) and its scores and sum over all S^2 pairs (the blocked
  kernel computes the masked half too: 2 x 2 S^2 d); cross-attention with
  Q and output projections over the tokens (2 x 2 S d^2), K and V
  projections over the frames (2 x 2 F d^2), scores and sum (2 x 2 S F d);
  the SwiGLU MLP (3 x 2 S d d_ff);
* the output head over the vocabulary (2 S d V).

The backward pass costs twice the forward, so a step is 3x the forward.
Recomputation under rematerialisation is not counted. Attention widths are
n_heads x head_dim.
"""
from __future__ import annotations


def forward_flops_per_utterance(cfg: dict, seq_len: int) -> float:
    d, ff = cfg["d_model"], cfg["d_ff"]
    a = cfg["n_heads"] * cfg["head_dim"]
    kv = cfg["n_kv_heads"] * cfg["head_dim"]
    F, S, V = cfg["n_frontend_tokens"], seq_len, cfg["vocab"]

    def proj(n, kv_n):
        # Q and output over n rows, K and V over kv_n rows
        return 2 * n * d * a * 2 + 2 * kv_n * d * kv * 2

    enc = proj(F, F) + 2 * 2 * F * F * a + 3 * 2 * F * d * ff
    dec = (proj(S, S) + 2 * 2 * S * S * a
           + proj(S, F) + 2 * 2 * S * F * a
           + 3 * 2 * S * d * ff)
    head = 2 * S * d * V
    return float(cfg["n_encoder_layers"] * enc + cfg["n_layers"] * dec + head)


def train_flops_per_token(cfg: dict, seq_len: int) -> float:
    """Forward and backward operations per decoder token."""
    return 3.0 * forward_flops_per_utterance(cfg, seq_len) / seq_len
