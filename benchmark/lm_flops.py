"""Operations and bytes of the causal expert decoder's step, from shapes
and from what the step's batch and routing really held.

The yardstick of the `glm-4.7-flash-ep8` cells, kept here (not in
`flops.py`, which is ProteinBERT's and fixed). `c` is the configuration
as the cell runs it: published key names, `n_routed_experts` the router's
width, `experts_held` this chip's share. Every matrix product counts 2
operations per multiply-add; training is three forward passes;
recomputation is not counted. What is counted is what the mathematics
NEEDS for the batch at hand:

- every product with a weight matrix, over the REAL (non-pad) tokens;
- the attention core over the (query, key) pairs that are causal AND in
  one segment: a document of n tokens has n (n + 1) / 2 of them;
- the routed experts over the assignments that really fell on the held
  experts (the step's own counter), not over their mean share.
"""

from __future__ import annotations


def attention_params(c: dict) -> int:
    D, H = c["hidden_size"], c["num_attention_heads"]
    nope, rope, dv = c["qk_nope_head_dim"], c["qk_rope_head_dim"], c["v_head_dim"]
    return (D * c["q_lora_rank"] + c["q_lora_rank"] * H * (nope + rope)
            + D * (c["kv_lora_rank"] + rope)
            + c["kv_lora_rank"] * H * (nope + dv) + H * dv * D)


def expert_params(c: dict) -> int:
    return 3 * c["hidden_size"] * c["moe_intermediate_size"]


def layer_counts(c: dict):
    """(dense layers, expert layers, prediction modules)."""
    dense = c["first_k_dense_replace"]
    return dense, c["num_hidden_layers"] - dense, c["num_nextn_predict_layers"]


def param_count(c: dict) -> int:
    """Trained parameters of this chip's share (norms included, the
    balance bias not)."""
    D, V = c["hidden_size"], c["vocab_size"]
    dense, moe, mtp = layer_counts(c)
    attn = attention_params(c) + c["q_lora_rank"] + c["kv_lora_rank"] + 2 * D
    expert_layer = (attn + D * c["n_routed_experts"]
                    + (c["experts_held"] + c["n_shared_experts"]) * expert_params(c))
    return (2 * V * D + D + dense * (attn + 3 * D * c["intermediate_size"])
            + moe * expert_layer + mtp * (expert_layer + 2 * D * D + 2 * D))


def forward_flops(c: dict, real_tokens: float, pairs: float,
                  assignments_held: float) -> float:
    """One forward pass. `pairs`: (query, key) pairs causal and in one
    segment, summed over the batch's rows; `assignments_held`: (token,
    slot) assignments to held experts, summed over every expert layer
    (the prediction module's with them)."""
    D, H = c["hidden_size"], c["num_attention_heads"]
    dense, moe, mtp = layer_counts(c)
    per_token = (
        (dense + moe + mtp) * attention_params(c)
        + dense * 3 * D * c["intermediate_size"]
        + (moe + mtp) * (D * c["n_routed_experts"]
                         + c["n_shared_experts"] * expert_params(c))
        + mtp * 2 * D * D + (1 + mtp) * D * c["vocab_size"])
    core = H * (c["qk_nope_head_dim"] + c["qk_rope_head_dim"] + c["v_head_dim"])
    return 2.0 * (real_tokens * per_token
                  + (dense + moe + mtp) * pairs * core
                  + assignments_held * expert_params(c))


def train_flops(c: dict, real_tokens: float, pairs: float,
                assignments_held: float) -> float:
    return 3.0 * forward_flops(c, real_tokens, pairs, assignments_held)


def train_min_bytes(c: dict, positions: int) -> float:
    """The least HBM traffic of one optimizer step: float32 parameters
    and both Adam moments read and written once, tokens and segment ids
    read once."""
    return 24.0 * param_count(c) + 8.0 * positions


def expected_assignments(c: dict, real_tokens: float) -> float:
    """The held experts' MEAN share of a step's assignments, over every
    expert layer: what a balanced router sends here. Used only where no
    step's counter has been fetched."""
    _, moe, mtp = layer_counts(c)
    return ((moe + mtp) * real_tokens * c["num_experts_per_tok"]
            * c["experts_held"] / c["n_routed_experts"])


def moe_experts_flops(c: dict, assignments_held: float) -> float:
    """The grouped products of one step, forward and backward (three
    forward passes), over the assignments that fell on the held experts."""
    return 3.0 * 2.0 * assignments_held * expert_params(c)


def moe_experts_min_bytes(c: dict) -> float:
    """The least traffic of the grouped products of one step: the held
    experts' float32 matrices read once forward and once backward, their
    gradients written once."""
    _, moe, mtp = layer_counts(c)
    return 3.0 * 4.0 * (moe + mtp) * c["experts_held"] * expert_params(c)
