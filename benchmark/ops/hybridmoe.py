"""Operations a hybrid decoder with routed experts (Ling-3.0-flash)
requires, from the sizes in its configuration file (``model``: the keys of
the published config.json, and ``layers_held``, ``experts_held``,
``router_width``).  2 a multiply-add of every matmul a token *uses*; the
softmax layers' pairs as ``ops/gpt.py`` counts them, at qk_head_dim +
v_head_dim a pair; the delta rule by its recurrence, whatever computes it.
Batches are described as in ``ops/gpt.py`` (``units``, ``sum_sq``).
"""

from __future__ import annotations

from typing import Any, Dict, Tuple

from ..families.hybridmoe import layer_kinds
from .gpt import attention_pairs

# per head and token of the recurrence, each d_k x d_v: the decay (1), k^T S
# (2), the rank-one update (2), q^T S (2)
RECURRENCE_OPS = 7


def kda_matmul_params(model: Dict[str, Any]) -> int:
    """W_q, W_k, W_v, W_f and W_o, and the two a head (beta, the gate).
    Not the convolutions' taps (4 multiply-adds a channel), no norm."""
    d = model["hidden_size"]
    h = model["num_attention_heads"]
    return 5 * d * h * model["head_dim"] + 2 * d * h


def mla_matmul_params(model: Dict[str, Any]) -> int:
    """W_q, W_dkv, W_ukv, W_o and the gate a head."""
    d, h = model["hidden_size"], model["num_attention_heads"]
    rank, turn = model["kv_lora_rank"], model["qk_rope_head_dim"]
    return (d * h * model["qk_head_dim"] + d * (rank + turn)
            + rank * h * (model["qk_nope_head_dim"] + model["v_head_dim"])
            + h * model["v_head_dim"] * d + d * h)


def pairs_per_token(model: Dict[str, Any]) -> float:
    """(token, expert) pairs a token brings to the experts held here, in
    expectation under a router that spreads its choices evenly: the batch's
    own count is the program's gauge ``model.moe.pairs_per_step``."""
    first, past = model["experts_held"]
    return (model["num_experts_per_tok"] * (past - first)
            / model["router_width"])


def ffn_matmul_params(model: Dict[str, Any], ffn: str) -> float:
    """What a token uses of a layer's FFN: the dense SwiGLU, or the router
    (its published width), the shared expert and its pairs' experts."""
    d = model["hidden_size"]
    if ffn == "dense":
        return 3.0 * d * model["intermediate_size"]
    expert = 3.0 * d * model["moe_intermediate_size"]
    return (d * model["router_width"] + model["num_shared_experts"] * expert
            + pairs_per_token(model) * expert)


def matmul_params(model: Dict[str, Any]) -> float:
    """Matmul parameters a token uses: every held layer and the untied
    head over the held vocabulary.  The input embedding is a gather."""
    total = float(model["vocab_size"] * model["hidden_size"])
    for mixer, ffn in layer_kinds(model):
        total += (kda_matmul_params(model) if mixer == "kda"
                  else mla_matmul_params(model))
        total += ffn_matmul_params(model, ffn)
    return total


def kda_core_ops(model: Dict[str, Any], units: float) -> float:
    """One layer's delta rule, forward, by the recurrence's count."""
    return (RECURRENCE_OPS * model["num_attention_heads"]
            * model["head_dim"] ** 2 * units)


def kda_core_bytes(model: Dict[str, Any], positions: float,
                   itemsize: int = 2) -> float:
    """One layer's delta rule, forward: q, k, v read and o written once in
    the activation type, the decays' logarithms (a channel) and beta (a
    head) in float32."""
    h, d = model["num_attention_heads"], model["head_dim"]
    return positions * h * (4.0 * d * itemsize + 4.0 * d + 4.0)


def kda_core_step(model: Dict[str, Any], units: float, positions: float
                  ) -> Tuple[float, float]:
    """(operations, bytes) of a training step's delta rules over every KDA
    layer held: the forward's, and for the backward twice its operations
    and its bytes once more with the gradients' beside them."""
    layers = sum(mixer == "kda" for mixer, _ in layer_kinds(model))
    return (layers * 3.0 * kda_core_ops(model, units),
            layers * 3.0 * kda_core_bytes(model, positions))


def forward_flops(model: Dict[str, Any], units: float, sum_sq: float,
                  causal: bool = True) -> float:
    kinds = layer_kinds(model)
    softmax = sum(mixer == "mla" for mixer, _ in kinds)
    linear = len(kinds) - softmax
    attention = (2.0 * softmax * model["num_attention_heads"]
                 * (model["qk_head_dim"] + model["v_head_dim"])
                 * attention_pairs(units, sum_sq, causal))
    return (2.0 * matmul_params(model) * units + attention
            + linear * kda_core_ops(model, units))


def train_flops(model: Dict[str, Any], units: float, sum_sq: float,
                causal: bool = True) -> float:
    """Forward and backward: the backward pass of a matmul is two matmuls
    of the forward's size, and the recurrence's transpose twice the
    recurrence.  What a step recomputes is not counted."""
    return 3.0 * forward_flops(model, units, sum_sq, causal)


def mla_flash_ops_and_bytes(model: Dict[str, Any], positions: float,
                            units: float, sum_sq: float,
                            itemsize: int = 2) -> Tuple[float, float]:
    """One latent-attention layer's flash forward call: QK^T over
    qk_head_dim and AV over v_head_dim, 2 each per allowed pair and head;
    q and k read at qk_head_dim, v read and o written at v_head_dim in the
    activation type, one float32 logsumexp a position and head."""
    h = model["num_attention_heads"]
    wide, narrow = model["qk_head_dim"], model["v_head_dim"]
    ops = 2.0 * h * (wide + narrow) * attention_pairs(units, sum_sq, True)
    return ops, positions * h * (2.0 * (wide + narrow) * itemsize + 4.0)


def mla_flash_roofline(run, kernel: str, what: str, ops_factor: float):
    """Share of its roofline, in percent, of the attention kernel under
    ``kernel`` in a hybrid cell (``trace/calls.py``'s calls a step and
    their device time); None where the trace shows no such call."""
    from benchmark import peaks
    from benchmark.harness import say
    from benchmark.trace import calls as trace_calls

    reduced = run.reduced()
    found = None if reduced is None else trace_calls.per_step(reduced, kernel)
    if found is None:
        return None
    calls, seconds = found
    work, steps = run.work(), len(run.completions)
    layers = sum(mixer == "mla" for mixer, _ in layer_kinds(run.model))
    ops, nbytes = mla_flash_ops_and_bytes(
        run.model, work.positions / steps / run.chips,
        work.units / steps / run.chips, work.sum_sq / steps / run.chips)
    peak = peaks.for_kind(run.device_kind)
    by_ops = ops_factor * ops / peak["bf16_flops_per_s"]
    by_bytes = nbytes / peak["hbm_bytes_per_s"]
    say(f"{what}.bound_by", "operations" if by_ops >= by_bytes else "bytes")
    say(f"{what}.calls_per_step", calls)
    return 100.0 * calls / layers * max(by_ops, by_bytes) / seconds
