"""Operations a hybrid decoder of Gated DeltaNet and gated attention with
routed experts (Qwen3-Next) requires, from the sizes in its configuration
file (``model``: the keys of the published config.json, and
``layers_held``, ``experts_held``, ``router_width``).  2 a multiply-add of
every matmul a token *uses* (the routed experts by the expected pairs a
token brings to the experts held here); the full layers' pairs as
``ops/gpt.py`` counts them, at 2 x head_dim a pair and query head; the delta
rule by its recurrence, 7 d_k d_v a value head and token, whatever computes
it.  Batches are described as in ``ops/gpt.py`` (``units``, ``sum_sq``).
"""

from __future__ import annotations

from typing import Any, Dict, Tuple

from ..families.qwen3next import layer_kinds
from .gpt import attention_pairs

# per value head and token of the recurrence, each d_k x d_v: the decay
# (1), k^T S (2), the rank-one update (2), q^T S (2)
RECURRENCE_OPS = 7


def gdn_matmul_params(model: Dict[str, Any]) -> int:
    """W_q, W_k at the key heads; W_v, the gate W_z and W_o at the value
    heads; W_a and W_b, one column a value head.  Not the convolutions'
    taps (4 multiply-adds a channel), no norm."""
    d = model["hidden_size"]
    hk, h = model["linear_num_key_heads"], model["linear_num_value_heads"]
    dk, dv = model["linear_key_head_dim"], model["linear_value_head_dim"]
    return 2 * d * hk * dk + 3 * d * h * dv + 2 * d * h


def attn_matmul_params(model: Dict[str, Any]) -> int:
    """W_q, the gate W_gate (one column a channel) and W_o at the query
    heads, W_k and W_v at the K/V heads."""
    d, width = model["hidden_size"], model["head_dim"]
    return (3 * d * model["num_attention_heads"] * width
            + 2 * d * model["num_key_value_heads"] * width)


def pairs_per_token(model: Dict[str, Any]) -> float:
    """(token, expert) pairs a token brings to the experts held here, in
    expectation under a router that spreads its choices evenly (10 x 32 /
    512 = 0.625 in the cell): the batch's own count is the program's gauge
    ``model.moe.pairs_per_step``."""
    first, past = model["experts_held"]
    return (model["num_experts_per_tok"] * (past - first)
            / model["router_width"])


def ffn_matmul_params(model: Dict[str, Any]) -> float:
    """What a token uses of a layer's MoE: the router (its published
    width), the shared expert and its gate, and its pairs' experts."""
    d = model["hidden_size"]
    return (d * model["router_width"]
            + 3.0 * d * model["shared_expert_intermediate_size"] + d
            + pairs_per_token(model) * 3.0 * d * model["moe_intermediate_size"])


def matmul_params(model: Dict[str, Any]) -> float:
    """Matmul parameters a token uses: every held layer's mixer and MoE and
    the untied head over the held vocabulary.  The input embedding is a
    gather."""
    total = float(model["vocab_size"] * model["hidden_size"])
    for mixer in layer_kinds(model):
        total += (gdn_matmul_params(model) if mixer == "gdn"
                  else attn_matmul_params(model))
        total += ffn_matmul_params(model)
    return total


def gdn_core_ops(model: Dict[str, Any], units: float) -> float:
    """One layer's delta rule, forward, by the recurrence's count."""
    return (RECURRENCE_OPS * model["linear_num_value_heads"]
            * model["linear_key_head_dim"] * model["linear_value_head_dim"]
            * units)


def gdn_core_bytes(model: Dict[str, Any], positions: float,
                   itemsize: int = 2) -> float:
    """One layer's delta rule, forward: q and k read at the key heads, v
    read and o written at the value heads, once each in the activation
    type; the decay's logarithm and beta (one a value head each) in
    float32."""
    hk, h = model["linear_num_key_heads"], model["linear_num_value_heads"]
    dk, dv = model["linear_key_head_dim"], model["linear_value_head_dim"]
    return positions * ((2.0 * hk * dk + 2.0 * h * dv) * itemsize + 8.0 * h)


def gdn_core_step(model: Dict[str, Any], units: float, positions: float
                  ) -> Tuple[float, float]:
    """(operations, bytes) of a training step's delta rules over every GDN
    layer held: the forward's, and for the backward twice its operations
    and its bytes once more with the gradients' beside them."""
    layers = sum(mixer == "gdn" for mixer in layer_kinds(model))
    return (layers * 3.0 * gdn_core_ops(model, units),
            layers * 3.0 * gdn_core_bytes(model, positions))


def attention_flops(model: Dict[str, Any], units: float, sum_sq: float
                    ) -> float:
    """QK^T and AV of every full layer held, forward: 2 x head_dim a
    required causal pair, each, at every query head."""
    full = sum(mixer == "full" for mixer in layer_kinds(model))
    return (4.0 * full * model["num_attention_heads"] * model["head_dim"]
            * attention_pairs(units, sum_sq, True))


def forward_flops(model: Dict[str, Any], units: float, sum_sq: float,
                  causal: bool = True) -> float:
    gdn = sum(mixer == "gdn" for mixer in layer_kinds(model))
    return (2.0 * matmul_params(model) * units
            + attention_flops(model, units, sum_sq)
            + gdn * gdn_core_ops(model, units))


def train_flops(model: Dict[str, Any], units: float, sum_sq: float,
                causal: bool = True) -> float:
    """Forward and backward: the backward pass of a matmul is two matmuls
    of the forward's size, and the recurrence's transpose twice the
    recurrence.  What a step recomputes is not counted."""
    return 3.0 * forward_flops(model, units, sum_sq, causal)


def flash_step(model: Dict[str, Any], positions: float, units: float,
               sum_sq: float, backward: bool, itemsize: int = 2
               ) -> Tuple[float, float]:
    """(operations, bytes) of one call of a flash kernel in a full layer.
    Forward: QK^T and AV, 2 x head_dim each per required causal pair and
    query head; q read and o written at the query heads, k and v read once
    at the K/V heads, in the activation type, and one float32 logsumexp a
    position and query head.  Backward: twice the operations (dq, dk, dv
    and dp are four matmuls over the pairs; the scores it computes again
    are not required), and with q, k, v, o and the logsumexp it reads o's
    cotangent and writes dq at the query heads, dk and dv at the K/V
    heads."""
    h, g, d = (model["num_attention_heads"], model["num_key_value_heads"],
               model["head_dim"])
    ops = 4.0 * h * d * attention_pairs(units, sum_sq, True)
    nbytes = positions * (2.0 * (h + g) * d * itemsize + 4.0 * h)
    if backward:
        return 2.0 * ops, nbytes + positions * 2.0 * (h + g) * d * itemsize
    return ops, nbytes


def flash_roofline(run, backward: bool, what: str):
    """Share of its roofline, in percent, of a flash kernel's calls in the
    full layers: one call's bound (``flash_step``) times the calls a step
    the trace shows (``trace/calls.py``: a recomputed call counts as a
    call), over those calls' device time.  The forward's calls lie at
    ``attn/pallas_call``, the backward's under ``attn/flash_bwd``.  None
    where the trace shows no such call or the family has no full layer."""
    from benchmark import peaks
    from benchmark.harness import say
    from benchmark.trace import calls as trace_calls

    reduced = run.reduced()
    if reduced is None or "full" not in layer_kinds(run.model):
        return None
    found = trace_calls.per_step(
        reduced, "attn/" + ("flash_bwd" if backward else "pallas_call"))
    if found is None:
        return None
    calls, seconds = found
    work, steps = run.work(), len(run.completions)
    ops, nbytes = flash_step(
        run.model, work.positions / steps / run.chips,
        work.units / steps / run.chips, work.sum_sq / steps / run.chips,
        backward)
    peak = peaks.for_kind(run.device_kind)
    # ``ops`` and ``nbytes`` are a batch's over one layer: one call's
    by_ops = ops / peak["bf16_flops_per_s"]
    by_bytes = nbytes / peak["hbm_bytes_per_s"]
    say(f"{what}.bound_by", "operations" if by_ops >= by_bytes else "bytes")
    say(f"{what}.calls_per_step", calls)
    return 100.0 * calls * max(by_ops, by_bytes) / seconds
