"""Operations of one example of the family ``afmoe`` (Arcee AFMoE: gated
grouped-query attention on sliding-window and full layers, a leading dense
SwiGLU layer, a shared expert beside sigmoid-routed experts, an untied
head), under ``harness/flops.py``'s conventions: a multiply-accumulate is
two operations, norms, activations, gates, RoPE and softmax are left out,
the backward pass counts twice the forward, and nothing recomputed counts.
An example is one sequence of ``tokens`` tokens.

Also here, because a kernel's count is kept with the benchmark: the
operations and bytes of the grouped expert matmul from the rows that were
counted (``moe_gmm_*``), of attention over both kinds of layer
(``attention_*``) and over the sliding layers alone (``window_attention_*``),
which the roofline shares under ``layer_metrics/`` divide by traced device
time.
"""

from __future__ import annotations


def visible_keys(p: dict, kind: str) -> float:
    """Keys all the queries of one sequence see, summed: ``sum_i min(i + 1,
    W)`` on a sliding layer, the lower triangle on a full one."""
    t = p["tokens"]
    if kind != "sliding_attention" or p["sliding_window"] >= t:
        return t * (t + 1) / 2
    w = p["sliding_window"]
    return w * (w + 1) / 2 + (t - w) * w


def _attention_macs(p: dict, kind: str) -> float:
    """One token's attention layer: q, the gate and o at ``heads x
    head_dim``, k and v at ``kv heads x head_dim``; scores and their product
    with the values over the keys a query sees on average."""
    d = p["hidden_size"]
    wide = p["num_attention_heads"] * p["head_dim"]
    kv = p["num_key_value_heads"] * p["head_dim"]
    return 3 * d * wide + 2 * d * kv + 2 * wide * visible_keys(p, kind) / p["tokens"]


def expert_macs_per_row(p: dict) -> float:
    """One (token, expert) pair through one expert: three d x f products."""
    return 3 * p["hidden_size"] * p["moe_intermediate_size"]


def forward_flops(p: dict) -> float:
    """One sequence's forward pass on this chip's share: every attention
    layer, norm, dense layer and shared expert whole, the router at its
    published width, the expected ``top_k * held / experts`` of one routed
    expert a token (uniform routing), the untied head over the held
    vocabulary rows once a token."""
    d = p["hidden_size"]
    per_token = d * p["vocab_rows"]
    for i, kind in enumerate(p["layer_types"]):
        per_token += _attention_macs(p, kind)
        if i < p["num_dense_layers"]:
            per_token += 3 * d * p["intermediate_size"]
        else:
            pairs = p["num_experts_per_tok"] * p["num_experts_held"] / p["num_experts"]
            per_token += d * p["num_experts"] + (
                p["num_shared_experts"] + pairs
            ) * expert_macs_per_row(p)
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


def _score_flops(sequences: float, p: dict, kinds: tuple) -> float:
    """Forward and backward of q k^T and p v over the keys each query sees,
    in the layers of ``kinds``: three forwards' worth."""
    wide = p["num_attention_heads"] * p["head_dim"]
    keys = sum(visible_keys(p, k) for k in p["layer_types"] if k in kinds)
    return 3.0 * 2.0 * sequences * 2 * wide * keys


def _qkv_bytes(sequences: float, p: dict, kinds: tuple, itemsize: int) -> float:
    """q and o at the query heads' width and k, v at the key-value heads'
    (the least: no head repeated) once forward; those, dO and dq, dk, dv
    backward."""
    wide = p["num_attention_heads"] * p["head_dim"]
    kv = p["num_key_value_heads"] * p["head_dim"]
    layers = sum(k in kinds for k in p["layer_types"])
    return itemsize * layers * sequences * p["tokens"] * 6 * (wide + kv)


_BOTH = ("sliding_attention", "full_attention")


def attention_flops(sequences: float, p: dict) -> float:
    return _score_flops(sequences, p, _BOTH)


def attention_bytes(sequences: float, p: dict, itemsize: int = 2) -> float:
    return _qkv_bytes(sequences, p, _BOTH, itemsize)


def window_attention_flops(sequences: float, p: dict) -> float:
    return _score_flops(sequences, p, _BOTH[:1])


def window_attention_bytes(sequences: float, p: dict, itemsize: int = 2) -> float:
    return _qkv_bytes(sequences, p, _BOTH[:1], itemsize)
