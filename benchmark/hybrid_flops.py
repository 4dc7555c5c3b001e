"""Operations and bytes of the hybrid decoder's served forward pass
(Ling-3.0-flash, `bailing_hybrid`), from shapes and from what the window's
batches and routing really held.

The yardstick of the `ling-3.0-flash-ep4` cell, beside `lm_flops.py`
(GLM-4.7-Flash's, fixed). `c` is the configuration as the cell runs it
(`drivers/lm_serve.reference_sizes`): `n_routed_experts` the router's
width, `experts_held` this chip's share. A matrix product counts 2
operations per multiply-add. What is counted is what the mathematics
NEEDS for the documents at hand, never what an algorithm spends:

- every product with a weight matrix and every convolution tap, over the
  REAL tokens (a span's tail past its document is not one);
- the KDA recurrence as the token recurrence states it, per real token
  and head: the decay of the state (d_k d_v), k^T S, the rank-one update
  and S^T q (2 d_k d_v each), 7 d_k d_v in all; the chunked form's
  products (A, B, the triangular inverse) are the algorithm's, not
  counted;
- the latent layers' attention core over the (query, key) pairs that are
  causal AND in one document: a document of n tokens has n (n + 1) / 2;
- the routed experts over the assignments that really fell on the held
  experts (the batches' own counter).
"""

from __future__ import annotations

RECURRENCE_OPS = 7      # per token, head and element of the state


def layer_counts(c: dict):
    """(KDA layers, latent layers, dense layers, expert layers) held."""
    kinds = [(c["first_layer_index"] + j + 1) % c["layer_group_size"] == 0
             for j in range(c["num_hidden_layers"])]
    dense = c["first_k_dense_replace"]
    return (len(kinds) - sum(kinds), sum(kinds), dense,
            c["num_hidden_layers"] - dense)


def kda_params(c: dict) -> int:
    """Matrices, taps, A_log, dt_bias and the head norm of a KDA mixer."""
    D, H, dk = c["hidden_size"], c["num_attention_heads"], c["kda_head_dim"]
    W = H * dk
    return (5 * D * W + 2 * D * H + 3 * c["short_conv_kernel_size"] * W
            + H + W + dk)


def latent_params(c: dict) -> int:
    D, H = c["hidden_size"], c["num_attention_heads"]
    nope, rope, dv = c["qk_nope_head_dim"], c["qk_rope_head_dim"], c["v_head_dim"]
    return (D * H * (nope + rope) + D * (c["kv_lora_rank"] + rope)
            + c["kv_lora_rank"] + c["kv_lora_rank"] * H * (nope + dv)
            + H * dv * D + D * H)


def expert_params(c: dict) -> int:
    return 3 * c["hidden_size"] * c["moe_intermediate_size"]


def param_count(c: dict) -> int:
    """Parameters of the served share (norms included, the router's bias,
    the output head and the prediction module not)."""
    D = c["hidden_size"]
    kda, latent, dense, moe = layer_counts(c)
    return (c["vocab_size"] * D + D + (kda + latent) * 2 * D
            + kda * kda_params(c) + latent * latent_params(c)
            + dense * 3 * D * c["intermediate_size"]
            + moe * (D * c["n_routed_experts"]
                     + (c["experts_held"] + c["n_shared_experts"]) * expert_params(c)))


def kda_core_flops(c: dict, real_tokens: float) -> float:
    """The recurrence of ONE KDA layer over these tokens."""
    return (RECURRENCE_OPS * real_tokens * c["num_attention_heads"]
            * c["kda_head_dim"] ** 2)


def kda_core_min_bytes(c: dict, positions: float) -> float:
    """The least HBM traffic of one KDA layer's core: q, k, v and the log
    decay (float32, as the configuration states them) and beta read once,
    o written once, over the positions of the batch."""
    H, dk = c["num_attention_heads"], c["kda_head_dim"]
    return 4.0 * positions * H * (5 * dk + 1)


def forward_flops(c: dict, real_tokens: float, pairs: float,
                  assignments_held: float) -> float:
    """One forward pass of `embed`. `pairs`: (query, key) pairs causal
    and in one document, summed over the documents; `assignments_held`:
    (token, slot) assignments to held experts, summed over the expert
    layers."""
    D, H = c["hidden_size"], c["num_attention_heads"]
    kda, latent, dense, moe = layer_counts(c)
    W, K = H * c["kda_head_dim"], c["short_conv_kernel_size"]
    per_token = (
        kda * (5 * D * W + 2 * D * H + 3 * K * W)
        + latent * (latent_params(c) - c["kv_lora_rank"])
        + dense * 3 * D * c["intermediate_size"]
        + moe * (D * c["n_routed_experts"]
                 + c["n_shared_experts"] * expert_params(c)))
    core = H * (c["qk_nope_head_dim"] + c["qk_rope_head_dim"] + c["v_head_dim"])
    return (2.0 * (real_tokens * per_token + latent * pairs * core
                   + assignments_held * expert_params(c))
            + kda * kda_core_flops(c, real_tokens))

