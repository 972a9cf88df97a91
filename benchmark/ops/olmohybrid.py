"""Operations a hybrid decoder of Gated DeltaNet and full attention
(Olmo-Hybrid-7B) requires, from the sizes in its configuration file
(``model``: the keys of the published config.json, and ``layers_held``).
2 a multiply-add of every matmul a token uses; the full layers' pairs as
``ops/gpt.py`` counts them, at 2 x head_dim a pair; the delta rule by its
recurrence, whatever computes it.  Batches are described as in
``ops/gpt.py`` (``units``, ``sum_sq``).
"""

from __future__ import annotations

from typing import Any, Dict, Tuple

from ..families.olmohybrid import layer_kinds
from .gpt import attention_pairs

# per head and token of the recurrence, each d_k x d_v: the decay (1), k^T S
# (2), the rank-one update (2), q^T S (2)
RECURRENCE_OPS = 7


def _heads_and_widths(model: Dict[str, Any]) -> Tuple[int, int, int]:
    return (model["linear_num_key_heads"], model["linear_key_head_dim"],
            model["linear_value_head_dim"])


def gdn_matmul_params(model: Dict[str, Any]) -> int:
    """W_q, W_k (keys), W_v, the gate W_z and W_o (values), and the two a
    head (W_a, W_b).  Not the convolutions' taps (4 multiply-adds a
    channel), no norm."""
    d = model["hidden_size"]
    h, dk, dv = _heads_and_widths(model)
    return 2 * d * h * dk + 3 * d * h * dv + 2 * d * h


def full_matmul_params(model: Dict[str, Any]) -> int:
    """W_q, W_k, W_v, W_o of heads that fill the hidden size."""
    return 4 * model["hidden_size"] ** 2


def matmul_params(model: Dict[str, Any]) -> float:
    """Matmul parameters a token uses: every held layer's mixer and SwiGLU
    and the untied head over the held vocabulary.  The input embedding is
    a gather."""
    d = model["hidden_size"]
    total = float(model["vocab_size"] * d)
    for mixer in layer_kinds(model):
        total += (gdn_matmul_params(model) if mixer == "gdn"
                  else full_matmul_params(model))
        total += 3.0 * d * model["intermediate_size"]
    return total


def gdn_core_ops(model: Dict[str, Any], units: float) -> float:
    """One layer's delta rule, forward, by the recurrence's count."""
    h, dk, dv = _heads_and_widths(model)
    return RECURRENCE_OPS * h * dk * dv * units


def gdn_core_bytes(model: Dict[str, Any], positions: float,
                   itemsize: int = 2) -> float:
    """One layer's delta rule, forward: q, k, v read and o written once in
    the activation type, the decay's logarithm and beta (one a head each)
    in float32."""
    h, dk, dv = _heads_and_widths(model)
    return positions * h * ((2.0 * dk + 2.0 * dv) * itemsize + 8.0)


def gdn_core_step(model: Dict[str, Any], units: float, positions: float
                  ) -> Tuple[float, float]:
    """(operations, bytes) of a training step's delta rules over every GDN
    layer held: the forward's, and for the backward twice its operations
    and its bytes once more with the gradients' beside them."""
    layers = sum(mixer == "gdn" for mixer in layer_kinds(model))
    return (layers * 3.0 * gdn_core_ops(model, units),
            layers * 3.0 * gdn_core_bytes(model, positions))


def forward_flops(model: Dict[str, Any], units: float, sum_sq: float,
                  causal: bool = True) -> float:
    kinds = layer_kinds(model)
    full = sum(mixer == "full" for mixer in kinds)
    head = model["hidden_size"] // model["num_attention_heads"]
    attention = (4.0 * full * model["num_attention_heads"] * head
                 * attention_pairs(units, sum_sq, causal))
    return (2.0 * matmul_params(model) * units + attention
            + (len(kinds) - full) * gdn_core_ops(model, units))


def train_flops(model: Dict[str, Any], units: float, sum_sq: float,
                causal: bool = True) -> float:
    """Forward and backward: the backward pass of a matmul is two matmuls
    of the forward's size, and the recurrence's transpose twice the
    recurrence.  What a step recomputes is not counted."""
    return 3.0 * forward_flops(model, units, sum_sq, causal)
