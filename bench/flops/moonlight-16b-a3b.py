"""Operations of one Moonlight-16B-A3B training step at the configuration's
cut, counted from the shapes of the model as the repo implements it (a
multiply-add is 2 operations), in two parts:

* per token, whatever every token goes through: in each layer the latent
  attention's projections (queries d x H(dn + dr), the kv down-projection
  d x (r + dr), its up-projection r x H(dn + dv), the output H dv x d) and
  its scores and weighted sum over the pairs causal attention needs, an
  average of (S + 1) / 2 keys per query (H(dn + dr) and H dv per pair);
  the leading dense layers' SwiGLU (3 x d x d_ff); in each MoE layer the
  router over all routed experts (d x E) and the shared experts' SwiGLU
  (3 x d x n_shared de); the output head over the vocabulary (d x V);
* per assignment to a held expert, its SwiGLU (3 x d x de), counted from
  the program's per-round counter of assignments.

The backward pass costs twice the forward, so a step is 3x the forward.
Recomputation under rematerialisation is not counted.
"""
from __future__ import annotations


def dense_forward_flops_per_token(cfg: dict, seq_len: int) -> float:
    d, H = cfg["hidden_size"], cfg["num_attention_heads"]
    r, dn, dr, dv = (cfg["kv_lora_rank"], cfg["qk_nope_head_dim"],
                     cfg["qk_rope_head_dim"], cfg["v_head_dim"])
    n_layers, n_dense = cfg["num_hidden_layers"], cfg["first_k_dense_replace"]
    routed = cfg["n_routed_experts"] * cfg["deployment"]["expert_parallel"]
    de = cfg["moe_intermediate_size"]
    attn = 2 * (d * H * (dn + dr) + d * (r + dr) + r * H * (dn + dv)
                + H * dv * d) + (seq_len + 1) / 2 * 2 * H * (dn + dr + dv)
    dense_mlp = 3 * 2 * d * cfg["intermediate_size"]
    moe = 2 * d * routed + 3 * 2 * d * cfg["n_shared_experts"] * de
    head = 2 * d * cfg["vocab_size"]
    return float(n_layers * attn + n_dense * dense_mlp
                 + (n_layers - n_dense) * moe + head)


def expert_forward_flops_per_assignment(cfg: dict) -> float:
    return float(3 * 2 * cfg["hidden_size"] * cfg["moe_intermediate_size"])


def train_flops(cfg: dict, seq_len: int, tokens: int,
                held_assignments: int) -> float:
    """Forward and backward operations of ``tokens`` tokens of which
    ``held_assignments`` (token, expert) pairs went to the held experts."""
    return 3.0 * (tokens * dense_forward_flops_per_token(cfg, seq_len)
                  + held_assignments
                  * expert_forward_flops_per_assignment(cfg))
