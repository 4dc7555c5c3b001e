"""Operations and bytes of ZAYA1's served forward pass (`zaya`: CCA and
top-1 routed experts in every layer), from shapes and from what the
window's batches and routing really held.

The yardstick of the `zaya1-8b-pp2` cell, beside `hybrid_flops.py`
(Ling-3.0-flash's, fixed). `c` is the configuration as the cell runs it
(`drivers/cca_serve.reference_sizes`, published key names). A matrix
product counts 2 operations per multiply-add. What is counted is what the
mathematics NEEDS for the documents at hand, never what an algorithm
spends:

- every product with a weight matrix and every convolution tap, over the
  REAL tokens (a span's tail past its document is not one);
- the attention core over the (query, key) pairs that are causal AND in
  one document: a document of n tokens has n (n + 1) / 2, each pair
  2 x 2 x head_dim operations a QUERY head (scores and the weighted sum
  of values); a tile the kernel walks past the pairs is the kernel's;
- the routed experts over the assignments the batches' own counter
  reports (one a real token and layer: top 1, every expert held).
So no share of a peak read from these can pass 100.
"""

from __future__ import annotations


def mixer_params(c: dict) -> int:
    """Matrices, both convolutions with their biases, and tau."""
    D, H, G, d = (c["hidden_size"], c["num_attention_heads"],
                  c["num_key_value_heads"], c["head_dim"])
    C = (H + G) * d
    return (D * H * d + 2 * D * G * d + H * d * D
            + c["cca_time0"] * C + C + c["cca_time1"] * (H + G) * d * d + C + G)


def router_params(c: dict) -> int:
    """Down-projection and its bias, the carried state's scales, the
    norm, the three MLP layers (the balance bias is no parameter)."""
    D, R = c["hidden_size"], c["router_hidden_size"]
    return D * R + R + R + R + 2 * (R * R + R) + R * c["num_experts"]


def expert_params(c: dict) -> int:
    return 3 * c["hidden_size"] * c["moe_intermediate_size"]


def layer_params(c: dict) -> int:
    """Two norms and eight residual vectors beside the three parts."""
    return (10 * c["hidden_size"] + mixer_params(c) + router_params(c)
            + c["num_experts"] * expert_params(c))


def param_count(c: dict) -> int:
    """Parameters of the served stage (the tied head adds none)."""
    D = c["hidden_size"]
    return c["vocab_size"] * D + D + c["num_hidden_layers"] * layer_params(c)


def core_flops(c: dict, pairs: float) -> float:
    """ONE layer's attention core over these (query, key) pairs."""
    return 2.0 * 2.0 * pairs * c["num_attention_heads"] * c["head_dim"]


def core_min_bytes(c: dict, tokens: float) -> float:
    """The least HBM traffic of one layer's core: q, k and v read once
    and o written once in bfloat16, k and v at their own two heads."""
    H, G, d = c["num_attention_heads"], c["num_key_value_heads"], c["head_dim"]
    return 2.0 * tokens * (2 * H + 2 * G) * d


def experts_flops(c: dict, assignments: float) -> float:
    """The grouped products over these (token, expert) assignments."""
    return 2.0 * assignments * expert_params(c)


def experts_min_bytes(c: dict, layers: int) -> float:
    """Every expert's three matrices read once a layer, bfloat16."""
    return 2.0 * layers * c["num_experts"] * expert_params(c)


def forward_flops(c: dict, real_tokens: float, pairs: float,
                  assignments: float) -> float:
    """One forward pass of `embed`. `pairs`: (query, key) pairs causal
    and in one document, summed over the documents; `assignments`:
    (token, expert) assignments summed over the layers."""
    D, R = c["hidden_size"], c["router_hidden_size"]
    H, G, d = c["num_attention_heads"], c["num_key_value_heads"], c["head_dim"]
    C = (H + G) * d
    per_token = (D * H * d + 2 * D * G * d + H * d * D        # q, k, v1 + v2, o
                 + c["cca_time0"] * C + c["cca_time1"] * (H + G) * d * d
                 + D * R + 2 * R * R + R * c["num_experts"])
    L = c["num_hidden_layers"]
    return (2.0 * L * real_tokens * per_token + L * core_flops(c, pairs)
            + experts_flops(c, assignments))
