"""Operations a GPT-2-style decoder requires, from the sizes in its
configuration file (``model``: the keys of the published config.json).

A *batch* is described by what the mask allows: ``units`` is the sum of
its documents' lengths (its non-padding tokens) and ``sum_sq`` the sum of
their squares.  A dense batch of R rows of T tokens is R documents of
length T.
"""

from __future__ import annotations

from typing import Any, Dict


def head_dim(model: Dict[str, Any]) -> int:
    return model["n_embd"] // model["n_head"]


def ff_dim(model: Dict[str, Any]) -> int:
    return model.get("n_inner") or 4 * model["n_embd"]


def matmul_params(model: Dict[str, Any]) -> int:
    """Parameters that sit in a matrix multiplication: every Dense kernel
    (fused QKV, attention projection, the two of the MLP) and ``wte`` once
    as the tied output head.  Not ``wpe`` and not ``wte`` as the input
    embedding (both are gathers), no bias, no LayerNorm."""
    d, f = model["n_embd"], ff_dim(model)
    per_layer = d * 3 * d + d * d + d * f + f * d
    return model["n_layer"] * per_layer + model["vocab_size"] * d


def attention_pairs(units: float, sum_sq: float, causal: bool) -> float:
    """(query, key) pairs the mask allows: a document of n tokens has
    n(n+1)/2 under a causal mask (the diagonal included), n*n without."""
    return (sum_sq + units) / 2.0 if causal else float(sum_sq)


def forward_flops(model: Dict[str, Any], units: float, sum_sq: float,
                  causal: bool = True) -> float:
    """Forward pass over a batch: 2 operations per matmul parameter per
    token, and per layer and head 2*head_dim for QK^T and 2*head_dim for AV
    per allowed pair."""
    attention = (4.0 * model["n_layer"] * model["n_head"] * head_dim(model)
                 * attention_pairs(units, sum_sq, causal))
    return 2.0 * matmul_params(model) * units + attention


def train_flops(model: Dict[str, Any], units: float, sum_sq: float,
                causal: bool = True) -> float:
    """Forward and backward: the backward pass of a matmul is two matmuls
    of the forward's size."""
    return 3.0 * forward_flops(model, units, sum_sq, causal)
