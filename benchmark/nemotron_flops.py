"""Operations and bytes of Nemotron 3's served forward pass (`nemotron_h`:
Mamba-2, attention over grouped keys and LatentMoE layers, one sublayer a
layer by a published pattern), from shapes and from what the window's
batches and routing really held.

The yardstick of the `nemotron-3-super-ep4` cell, beside `hybrid_flops.py`
(Ling-3.0-flash's) and `cca_flops.py` (ZAYA1's), both fixed. `c` is the
configuration as the cell runs it (`drivers/nemotron_serve.reference_sizes`,
published key names; `n_routed_experts` the experts HELD, `router_width`
the router's). A matrix product counts 2 operations per multiply-add. What
is counted is what the mathematics NEEDS for the documents at hand, never
what an algorithm spends:

- every product with a weight matrix and every convolution tap, over the
  REAL tokens (a span's tail past its document is not one);
- the state-space recurrence TOKEN BY TOKEN: per token and head 5 x
  head_dim x state operations (the state's decay 1, the rank-one update
  2, S C 2, as `hybrid_flops` counts KDA's 7); what the chunked form
  spends on its masked Q x Q products is the algorithm's;
- the attention core over the (query, key) pairs that are causal AND in
  one document, 2 x 2 x head_dim operations a pair and QUERY head;
- the routed experts over the assignments the batches' own counter
  reports (the ones that fell on the experts held), two matrices each.
So no share of a peak read from these can pass 100.
"""

from __future__ import annotations

from collections import Counter

from benchmark.reference.nemotron_h_f32 import held_kinds


def layer_counts(c: dict) -> Counter:
    """kind -> layers of it held (`mamba`, `gqa`, `latent_moe`)."""
    return Counter(kind for _, kind in held_kinds(c))


def mamba_sizes(c: dict):
    """(heads, inner width, conv channels, in_proj width)."""
    H = c["mamba_num_heads"]
    inner = H * c["mamba_head_dim"]
    conv = inner + 2 * c["n_groups"] * c["ssm_state_size"]
    return H, inner, conv, inner + conv + H


def mamba_params(c: dict) -> int:
    """The layer's norm, W_in, the convolution and its bias, A_log,
    dt_bias and D, the gated norm's scale, W_out."""
    D = c["hidden_size"]
    H, inner, conv, wide = mamba_sizes(c)
    return (D + D * wide + c["conv_kernel"] * conv + conv + 3 * H + inner
            + inner * D)


def gqa_params(c: dict) -> int:
    D, H, G, d = (c["hidden_size"], c["num_attention_heads"],
                  c["num_key_value_heads"], c["head_dim"])
    return D + D * H * d + 2 * D * G * d + H * d * D


def expert_params(c: dict) -> int:
    """One routed expert: two matrices in the latent."""
    return 2 * c["moe_latent_size"] * c["moe_intermediate_size"]


def latent_moe_params(c: dict) -> int:
    """The layer's norm, the router, the way down and up, the experts
    held, the shared expert (the balance bias is no parameter)."""
    D, U = c["hidden_size"], c["moe_latent_size"]
    return (D + D * c["router_width"] + 2 * D * U
            + c["n_routed_experts"] * expert_params(c)
            + 2 * D * c["moe_shared_expert_intermediate_size"])


def param_count(c: dict) -> int:
    """Parameters of the served share (no output head, no prediction
    module)."""
    n = layer_counts(c)
    D = c["hidden_size"]
    return (c["vocab_size"] * D + D + n["mamba"] * mamba_params(c)
            + n["gqa"] * gqa_params(c) + n["latent_moe"] * latent_moe_params(c))


def published_param_count(published: dict) -> int:
    """The whole model by the same shapes: every layer, every expert, the
    whole vocabulary and the untied output head (the prediction module
    shares the model's weights but for its own layers, which the card's
    count leaves out and so does this)."""
    c = dict(published, first_layer_index=0, router_width=published["n_routed_experts"])
    return param_count(c) + c["vocab_size"] * c["hidden_size"]


def ssd_core_flops(c: dict, tokens: float) -> float:
    """ONE Mamba layer's recurrence over these tokens."""
    return (5.0 * tokens * c["mamba_num_heads"] * c["mamba_head_dim"]
            * c["ssm_state_size"])


def ssd_core_min_bytes(c: dict, tokens: float) -> float:
    """The least HBM traffic of one layer's recurrence: x, B and C read
    and y written once in bfloat16, dt read once in float32."""
    H, inner, conv, _ = mamba_sizes(c)
    return tokens * (2.0 * (conv + inner) + 4.0 * H)


def gqa_core_flops(c: dict, pairs: float) -> float:
    """ONE attention layer's core over these (query, key) pairs."""
    return 2.0 * 2.0 * pairs * c["num_attention_heads"] * c["head_dim"]


def experts_flops(c: dict, assignments: float) -> float:
    """The grouped products over these (token, expert) assignments."""
    return 2.0 * assignments * expert_params(c)


def experts_min_bytes(c: dict, layers: int) -> float:
    """Every held expert's two matrices read once a layer, bfloat16."""
    return 2.0 * layers * c["n_routed_experts"] * expert_params(c)


def forward_flops(c: dict, real_tokens: float, pairs: float,
                  assignments: float) -> float:
    """One forward pass of `embed`. `pairs`: (query, key) pairs causal
    and in one document, summed over the documents; `assignments`:
    (token, expert) assignments to the experts held, summed over the
    expert layers."""
    n = layer_counts(c)
    D, U = c["hidden_size"], c["moe_latent_size"]
    H, G, d = c["num_attention_heads"], c["num_key_value_heads"], c["head_dim"]
    _, inner, conv, wide = mamba_sizes(c)
    mamba = 2.0 * (D * wide + c["conv_kernel"] * conv + inner * D)
    gqa = 2.0 * (D * H * d + 2 * D * G * d + H * d * D)
    moe = 2.0 * (D * c["router_width"] + 2 * D * U
                 + 2 * D * c["moe_shared_expert_intermediate_size"])
    return (real_tokens * (n["mamba"] * mamba + n["gqa"] * gqa + n["latent_moe"] * moe)
            + n["mamba"] * ssd_core_flops(c, real_tokens)
            + n["gqa"] * gqa_core_flops(c, pairs)
            + experts_flops(c, assignments))
