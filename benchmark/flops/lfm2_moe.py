"""Operations of one example of the family ``lfm2_moe`` (LFM2-MoE: gated
short convolutions, grouped-query attention, a leading dense SwiGLU layer,
sigmoid-routed experts), under ``harness/flops.py``'s conventions: a
multiply-accumulate is two operations, norms, activations, RoPE and softmax
are left out, the backward pass counts twice the forward, and nothing
recomputed counts.  An example is one sequence of ``tokens`` tokens.

Also here, because a kernel's count is kept with the benchmark: the
operations and bytes of the grouped expert matmul from the rows that were
counted (``moe_gmm_*``) and of causal attention (``attention_*``), which
the roofline shares under ``layer_metrics/`` divide by traced device time.
"""

from __future__ import annotations


def _mixer_macs(p: dict, kind: str) -> float:
    d = p["hidden_size"]
    if kind == "conv":  # in_proj d x 3d, the depthwise taps, out_proj d x d
        return d * 3 * d + p["conv_L_cache"] * d + d * d
    kv = d // p["num_attention_heads"] * p["num_key_value_heads"]
    # q and o d x d, k and v d x kv; scores and their product with the
    # values over the causal mask's lower triangle: (tokens + 1) / 2 keys a
    # query on average, d multiply-accumulates a key for each of the two
    return 2 * d * d + 2 * d * kv + 2 * d * (p["tokens"] + 1) / 2


def expert_macs_per_row(p: dict) -> float:
    """One (token, expert) pair through one expert: three d x f products."""
    return 3 * p["hidden_size"] * p["moe_intermediate_size"]


def forward_flops(p: dict) -> float:
    """One sequence's forward pass on this chip's share: every mixer, norm
    and dense layer whole, the router at its published width, the expected
    ``top_k * held / experts`` of one expert a token (uniform routing), the
    tied head over the held vocabulary rows once a token."""
    d = p["hidden_size"]
    per_token = d * p["vocab_rows"]
    for i, kind in enumerate(p["layer_types"]):
        per_token += _mixer_macs(p, kind)
        if i < p["num_dense_layers"]:
            per_token += 3 * d * p["intermediate_size"]
        else:
            pairs = p["num_experts_per_tok"] * p["num_experts_held"] / p["num_experts"]
            per_token += d * p["num_experts"] + pairs * expert_macs_per_row(p)
    return 2.0 * per_token * p["tokens"]


# ------------------------------------------------------------ kernel counts


def moe_gmm_flops(rows: float, p: dict) -> float:
    """Forward and backward of the three grouped matmuls over ``rows``
    counted (token, expert) pairs: three forwards' worth."""
    return 3.0 * 2.0 * rows * expert_macs_per_row(p)


def moe_gmm_bytes(rows: float, layer_steps: float, p: dict,
                  itemsize: int = 2) -> float:
    """The least the three grouped matmuls move over ``layer_steps``
    executions of one expert layer: each held expert's three matrices read
    in the forward, read again for the gradient with respect to the rows
    and written once as their own gradient; each row's input, its two
    hidden activations and its output read or written once in each
    direction.  In the compute dtype (bf16: 2 bytes)."""
    weights = 3 * p["num_experts_held"] * expert_macs_per_row(p)
    per_row = 2 * (p["hidden_size"] + 3 * p["moe_intermediate_size"])
    return itemsize * (layer_steps * weights + 2 * rows * per_row)


def attention_flops(sequences: float, p: dict) -> float:
    """Forward and backward of causal attention over ``sequences``
    sequences in every attention layer: q k^T and p v over the lower
    triangle, three forwards' worth."""
    layers = sum(kind == "full_attention" for kind in p["layer_types"])
    t, d = p["tokens"], p["hidden_size"]
    return 3.0 * 2.0 * layers * sequences * 2 * d * t * (t + 1) / 2


def attention_bytes(sequences: float, p: dict, itemsize: int = 2) -> float:
    """q, k, v (each key-value head repeated for its queries, as the kernel
    is handed them) and o once forward; those, dO and dq, dk, dv backward."""
    layers = sum(kind == "full_attention" for kind in p["layer_types"])
    return itemsize * layers * sequences * 12 * p["tokens"] * p["hidden_size"]
