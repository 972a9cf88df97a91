"""Operations a looped decoder (Ouro / LoopLM) requires, from the sizes in
its configuration file (``model``: the keys of the published config.json
and ``total_ut_steps``).  The stack of layers, the head and the exit gate
are applied ``total_ut_steps`` times a forward pass, so each counts that
often; their weights are counted once nowhere.  Batches are described as
in ``ops/gpt.py`` (``units``, ``sum_sq``).
"""

from __future__ import annotations

from typing import Any, Dict

from .gpt import attention_pairs


def layer_matmul_params(model: Dict[str, Any]) -> int:
    """Parameters of one layer that sit in a matrix multiplication: q, k,
    v (grouped heads would make k and v narrower) and o, and the gate, up
    and down projections of the SwiGLU MLP.  No norm."""
    d, f = model["hidden_size"], model["intermediate_size"]
    q = model["num_attention_heads"] * model["head_dim"]
    kv = model["num_key_value_heads"] * model["head_dim"]
    return d * q + 2 * d * kv + q * d + 3 * d * f


def pass_matmul_params(model: Dict[str, Any]) -> int:
    """Matmul parameters one pass over the stack applies: every layer, the
    untied head over the held vocabulary, the exit gate's one column.  The
    input embedding is a gather."""
    d = model["hidden_size"]
    return (model["num_hidden_layers"] * layer_matmul_params(model)
            + model["vocab_size"] * d + d)


def forward_flops(model: Dict[str, Any], units: float, sum_sq: float,
                  causal: bool = True) -> float:
    """Forward pass over a batch: per pass 2 operations per matmul
    parameter per token, and per layer and head 2*head_dim for QK^T and
    2*head_dim for AV per allowed pair."""
    steps = model["total_ut_steps"]
    attention = (4.0 * model["num_hidden_layers"]
                 * model["num_attention_heads"] * model["head_dim"]
                 * attention_pairs(units, sum_sq, causal))
    return steps * (2.0 * pass_matmul_params(model) * units + attention)


def train_flops(model: Dict[str, Any], units: float, sum_sq: float,
                causal: bool = True) -> float:
    """Forward and backward: the backward pass of a matmul is two matmuls
    of the forward's size.  What a step recomputes is not required and is
    not counted."""
    return 3.0 * forward_flops(model, units, sum_sq, causal)
