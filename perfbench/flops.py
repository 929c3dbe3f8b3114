"""Operations and bytes the work needs, from shapes alone.

``lm_request_flops`` is the model FLOPs of one served request of the
dense decoder: 2 x matmul parameters per token processed, plus causal
attention (QK^T and PV, 4 x d_model FLOPs per key per layer) at the
request's real lengths. The embedding lookup is a gather and counts
nothing; the output head counts for every token, as the usual model
FLOPs convention has it.
"""
from __future__ import annotations


def lm_matmul_params(model: dict) -> int:
    """Parameters that take part in a matmul per token."""
    L, D = model["num_hidden_layers"], model["hidden_size"]
    H, K = model["num_attention_heads"], model["num_key_value_heads"]
    F, V = model["intermediate_size"], model["vocab_size"]
    hd = D // H
    per_layer = D * H * hd + 2 * D * K * hd + H * hd * D + 3 * D * F
    return L * per_layer + D * V


def lm_request_flops(model: dict, n_prompt: int, n_out: int) -> float:
    """FLOPs of prefilling ``n_prompt`` tokens, then ``n_out`` decode steps
    (the first at the last prompt position, as the engine decodes)."""
    L, D = model["num_hidden_layers"], model["hidden_size"]
    per_token = 2 * lm_matmul_params(model)
    attn = 4 * L * D
    prefill = n_prompt * per_token + attn * n_prompt * (n_prompt + 1) / 2
    decode = sum(per_token + attn * (n_prompt + j) for j in range(n_out))
    return prefill + decode
