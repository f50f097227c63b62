"""Operations of one example of the family ``qwen3_next`` (Qwen3-Next: Gated
DeltaNet layers and gated grouped-query attention layers, every layer's MLP
softmax-routed experts beside a gated shared expert, an untied head), under
``harness/flops.py``'s conventions: a multiply-accumulate is two operations,
norms, activations, gates, RoPE, softmax, the decays and the four-tap
depthwise convolution (33 k multiply-accumulates a token and layer of 33.7 M)
are left out, the backward pass counts twice the forward, and nothing
recomputed counts.  An example is one sequence of ``tokens`` tokens.

Also here, because a kernel's count is kept with the benchmark: the
operations and bytes of the grouped expert matmul from the rows that were
counted (``moe_gmm_*``), of causal attention (``attention_*``) and of the
gated delta rule's scan (``gdn_scan_*``), which the roofline shares under
``layer_metrics/`` divide by traced device time.

The scan is counted in its chunked form at ``gdn_chunk`` tokens a chunk —
**the work, not an implementation**: per chunk of ``C`` tokens and value
head, with ``dk`` / ``dv`` the key / value head size, the products ``K K^T``,
``Q K^T`` and ``T (beta e^gamma K)`` (``C^2 dk`` each), ``T (beta V)`` and
``(Q K^T * Gamma) D`` (``C^2 dv`` each), and the three that meet the state,
``W S``, ``Q S`` and ``K^T D`` (``C dk dv`` each): ``C (3 dk + 2 dv) + 3 dk
dv`` multiply-accumulates a token and head.  Whole squares, as an MXU does
them at ``C`` = 64; the triangular solve for ``T`` itself (``C^2 / 6`` a
token, under 1 %) is left out.  The token-by-token recurrence would be ``3 dk
dv`` a token of vector work and is not what a chip should do.
"""

from __future__ import annotations


def _kinds(p: dict, kind: str) -> int:
    return sum(k == kind for k in p["layer_types"])


def gdn_scan_macs_per_token(p: dict) -> float:
    """One token through one Gated DeltaNet layer's scan, every value head."""
    c, dk, dv = p["gdn_chunk"], p["linear_key_head_dim"], p["linear_value_head_dim"]
    return p["linear_num_value_heads"] * (c * (3 * dk + 2 * dv) + 3 * dk * dv)


def _gdn_macs(p: dict) -> float:
    """One token's Gated DeltaNet mixer: the projection to q | k | v | z and
    to b | alpha, the scan, the output projection."""
    d = p["hidden_size"]
    keys = p["linear_num_key_heads"] * p["linear_key_head_dim"]
    values = p["linear_num_value_heads"] * p["linear_value_head_dim"]
    return (
        d * (2 * keys + 2 * values + 2 * p["linear_num_value_heads"])
        + gdn_scan_macs_per_token(p) + values * d
    )


def _attention_macs(p: dict) -> float:
    """One token's attention layer: q with its gate (twice the heads'
    width), k, v and o; scores and their product with the values over the
    ``(tokens + 1) / 2`` keys a query sees on average."""
    d = p["hidden_size"]
    wide = p["num_attention_heads"] * p["head_dim"]
    kv = p["num_key_value_heads"] * p["head_dim"]
    return 3 * d * wide + 2 * d * kv + 2 * wide * (p["tokens"] + 1) / 2


def expert_macs_per_row(p: dict) -> float:
    """One (token, expert) pair through one expert: three d x f products."""
    return 3 * p["hidden_size"] * p["moe_intermediate_size"]


def forward_flops(p: dict) -> float:
    """One sequence's forward pass on this chip's share: every mixer, norm
    and shared expert (with its gate) whole, the router at its published
    width, the expected ``top_k * held / experts`` of one routed expert a
    token (uniform routing), the untied head over the held vocabulary rows
    once a token."""
    d = p["hidden_size"]
    pairs = p["num_experts_per_tok"] * p["num_experts_held"] / p["num_experts"]
    expert_layer = (
        d * p["num_experts"] + d
        + 3 * d * p["shared_expert_intermediate_size"]
        + pairs * expert_macs_per_row(p)
    )
    per_token = (
        d * p["vocab_rows"]
        + _kinds(p, "linear_attention") * _gdn_macs(p)
        + _kinds(p, "full_attention") * _attention_macs(p)
        + len(p["layer_types"]) * expert_layer
    )
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
    """Forward and backward of q k^T and p v over the lower triangle in the
    full-attention layers: three forwards' worth."""
    wide = p["num_attention_heads"] * p["head_dim"]
    t = p["tokens"]
    return (
        3.0 * 2.0 * _kinds(p, "full_attention") * sequences
        * 2 * wide * t * (t + 1) / 2
    )


def attention_bytes(sequences: float, p: dict, itemsize: int = 2) -> float:
    """q and o at the query heads' width and k, v at the key-value heads'
    (the least: no head repeated) once forward; those, dO and dq, dk, dv
    backward."""
    wide = p["num_attention_heads"] * p["head_dim"]
    kv = p["num_key_value_heads"] * p["head_dim"]
    return (
        itemsize * _kinds(p, "full_attention") * sequences * p["tokens"]
        * 6 * (wide + kv)
    )


def gdn_scan_flops(sequences: float, p: dict) -> float:
    """Forward and backward of the scan in the Gated DeltaNet layers, in
    the chunked form (module docstring): three forwards' worth."""
    return (
        3.0 * 2.0 * _kinds(p, "linear_attention") * sequences * p["tokens"]
        * gdn_scan_macs_per_token(p)
    )


def gdn_scan_bytes(sequences: float, p: dict, itemsize: int = 2) -> float:
    """The least the scan moves: q and k at the key heads' width (no head
    repeated), v, the log-decay g and beta (float32, a value head each) read
    and o written forward; q, k, v, g, beta and dO read and the five
    gradients written backward."""
    keys = p["linear_num_key_heads"] * p["linear_key_head_dim"]
    values = p["linear_num_value_heads"] * p["linear_value_head_dim"]
    gates = 2 * p["linear_num_value_heads"] * 4  # g and beta
    forward = itemsize * (2 * keys + 2 * values) + gates
    backward = itemsize * (2 * (2 * keys + values) + values) + 2 * gates
    return (
        _kinds(p, "linear_attention") * sequences * p["tokens"]
        * (forward + backward)
    )
