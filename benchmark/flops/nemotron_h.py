"""Operations of one example of the family ``nemotron_h`` (Nemotron-H: every
layer one mixer alone — a Mamba-2 state-space mixer, an expert layer of
squared-ReLU experts beside a shared expert, or grouped-query attention —
and an untied head), under ``harness/flops.py``'s conventions: a
multiply-accumulate is two operations, norms, activations, softmax, the
decays and the four-tap depthwise convolution (25 k multiply-accumulates a
token and layer of 38.7 M) are left out, the backward pass counts twice the
forward, and nothing recomputed counts.  An example is one sequence of
``tokens`` tokens.

Also here, because a kernel's count is kept with the benchmark: the
operations and bytes of the grouped expert matmul from the rows that were
counted (``moe_gmm_*``), of causal attention (``attention_*``) and of the
state-space scan (``ssd_scan_*``), which the roofline shares under
``layer_metrics/`` divide by traced device time.

The scan is counted in its chunked form at ``ssd_chunk`` tokens a chunk —
**the work, not an implementation**: per chunk of ``L`` tokens, ``C B^T``
once a group (``L^2 N``), its masked product with the written values a head
(``L^2 P``), and the two products that meet the state a head, ``C S`` and
``X^T B`` (``L N P`` each): ``G L N + H L P + 2 H N P`` multiply-accumulates
a token.  Whole squares, as an MXU does them at ``L`` = 128.  The
token-by-token recurrence would be ``2 H N P`` a token of vector work and
is not what a chip should do.
"""

from __future__ import annotations


def _kinds(p: dict, kind: str) -> int:
    return sum(k == kind for k in p["layer_types"])


def ssd_scan_macs_per_token(p: dict) -> float:
    """One token through one Mamba-2 layer's scan, every head."""
    l, h, hp = p["ssd_chunk"], p["mamba_num_heads"], p["mamba_head_dim"]
    g, n = p["n_groups"], p["ssm_state_size"]
    return g * l * n + h * l * hp + 2 * h * n * hp


def _mamba_macs(p: dict) -> float:
    """One token's Mamba-2 mixer: the projection to z | xBC | dt, the scan,
    the output projection."""
    d = p["hidden_size"]
    inner = p["mamba_num_heads"] * p["mamba_head_dim"]
    wide = 2 * inner + 2 * p["n_groups"] * p["ssm_state_size"] + p["mamba_num_heads"]
    return d * wide + ssd_scan_macs_per_token(p) + inner * d


def _attention_macs(p: dict) -> float:
    """One token's attention layer: q, k, v and o; scores and their product
    with the values over the ``(tokens + 1) / 2`` keys a query sees on
    average."""
    d = p["hidden_size"]
    wide = p["num_attention_heads"] * p["head_dim"]
    kv = p["num_key_value_heads"] * p["head_dim"]
    return 2 * d * wide + 2 * d * kv + 2 * wide * (p["tokens"] + 1) / 2


def expert_macs_per_row(p: dict) -> float:
    """One (token, expert) pair through one expert: two d x f products."""
    return 2 * p["hidden_size"] * p["moe_intermediate_size"]


def forward_flops(p: dict) -> float:
    """One sequence's forward pass on this chip's share: every mixer, norm
    and shared expert whole, the router at its published width, the
    expected ``top_k * held / experts`` of one routed expert a token
    (uniform routing), the untied head over the held vocabulary rows once a
    token."""
    d = p["hidden_size"]
    pairs = p["num_experts_per_tok"] * p["num_experts_held"] / p["num_experts"]
    expert_layer = (
        d * p["num_experts"]
        + 2 * d * p["moe_shared_expert_intermediate_size"]
        + pairs * expert_macs_per_row(p)
    )
    per_token = (
        d * p["vocab_rows"]
        + _kinds(p, "mamba") * _mamba_macs(p)
        + _kinds(p, "attention") * _attention_macs(p)
        + _kinds(p, "moe") * expert_layer
    )
    return 2.0 * per_token * p["tokens"]


# ------------------------------------------------------------ kernel counts


def moe_gmm_flops(rows: float, p: dict) -> float:
    """Forward and backward of the two grouped matmuls over ``rows``
    counted (token, expert) pairs: three forwards' worth."""
    return 3.0 * 2.0 * rows * expert_macs_per_row(p)


def moe_gmm_bytes(rows: float, layer_steps: float, p: dict,
                  itemsize: int = 2) -> float:
    """The least the two grouped matmuls move: each held expert's two
    matrices read in the forward, read again for the gradient with respect
    to the rows and written once as their own gradient, an expert layer's
    call; each row's input, hidden activation and output read or written
    once in each direction.  In the compute dtype (bf16: 2 bytes).
    ``layer_steps`` is what ``layer_metrics/moe_gmm_roofline_pct.py`` hands
    every family — the traced steps times the layers that are not leading
    dense ones — and here only the ``moe`` layers of ``layer_types`` are
    expert layers, so their share of it is taken."""
    not_dense = len(p["layer_types"]) - p["num_dense_layers"]
    calls = layer_steps * _kinds(p, "moe") / not_dense
    weights = 3 * p["num_experts_held"] * expert_macs_per_row(p)
    per_row = 2 * (p["hidden_size"] + 2 * p["moe_intermediate_size"])
    return itemsize * (calls * weights + 2 * rows * per_row)


def attention_flops(sequences: float, p: dict) -> float:
    """Forward and backward of q k^T and p v over the lower triangle in the
    attention layers: three forwards' worth."""
    wide = p["num_attention_heads"] * p["head_dim"]
    t = p["tokens"]
    return (
        3.0 * 2.0 * _kinds(p, "attention") * sequences
        * 2 * wide * t * (t + 1) / 2
    )


def attention_bytes(sequences: float, p: dict, itemsize: int = 2) -> float:
    """q and o at the query heads' width and k, v at the key-value heads'
    (the least: no head repeated) once forward; those, dO and dq, dk, dv
    backward."""
    wide = p["num_attention_heads"] * p["head_dim"]
    kv = p["num_key_value_heads"] * p["head_dim"]
    return (
        itemsize * _kinds(p, "attention") * sequences * p["tokens"]
        * 6 * (wide + kv)
    )


def ssd_scan_flops(sequences: float, p: dict) -> float:
    """Forward and backward of the scan in the Mamba-2 layers, in the
    chunked form (module docstring): three forwards' worth."""
    return (
        3.0 * 2.0 * _kinds(p, "mamba") * sequences * p["tokens"]
        * ssd_scan_macs_per_token(p)
    )


def ssd_scan_bytes(sequences: float, p: dict, itemsize: int = 2) -> float:
    """The least the scan moves: x at the heads' width, B and C at the
    groups' (no group repeated) and dt (float32, a head) read and y written
    forward; x, B, C, dt and dO read and the four gradients written
    backward (A's and D's are a head's scalars)."""
    inner = p["mamba_num_heads"] * p["mamba_head_dim"]
    groups = 2 * p["n_groups"] * p["ssm_state_size"]
    dt = p["mamba_num_heads"] * 4
    forward = itemsize * (2 * inner + groups) + dt
    backward = itemsize * (2 * (inner + groups) + inner) + 2 * dt
    return _kinds(p, "mamba") * sequences * p["tokens"] * (forward + backward)
