"""Operations a decoder of mixed full and sliding-window attention with
routed experts (Laguna-XS.2) requires, from the sizes in its configuration
file (``model``: the keys of the published config.json, and ``layers_held``,
``experts_held``, ``router_width``).  2 a multiply-add of every matmul a
token *uses*, and attention's pairs as each layer type *requires* them: the
causal triangle on full layers, the window's band on sliding layers, at the
layer's own number of query heads, whatever tiles the program visits, so
that ``mfu`` cannot rise by computing the whole triangle.  Batches are
described as in ``ops/gpt.py`` (``units``, ``sum_sq``).
"""

from __future__ import annotations

from typing import Any, Dict, Tuple

from ..families.swamoe import layer_kinds
from .gpt import attention_pairs


def window_pairs(units: float, sum_sq: float, window: int) -> float:
    """(query, key) pairs a causal window of ``window`` allows (a query
    sees itself and the ``window - 1`` before it): a document of n tokens
    has n(n+1)/2 up to n = window and W(W+1)/2 + (n - W) W beyond.  Exact
    for documents of one length (``units``^2 / ``sum_sq`` of them, as dense
    rows are); for a mix of lengths it is the count at their
    length-weighted mean."""
    if not units:
        return 0.0
    n = sum_sq / units
    documents = units / n
    if n <= window:
        return documents * n * (n + 1) / 2.0
    return documents * (window * (window + 1) / 2.0 + (n - window) * window)


def pairs(model: Dict[str, Any], mixer: str, units: float, sum_sq: float
          ) -> float:
    """Pairs one head of a layer of kind ``mixer`` requires."""
    if mixer == "window":
        return window_pairs(units, sum_sq, model["sliding_window"])
    return attention_pairs(units, sum_sq, True)


def mixer_matmul_params(model: Dict[str, Any], heads: int) -> int:
    """W_q and W_o at the layer's query heads, W_k and W_v at the
    key/value heads, and the gate a head."""
    d, width = model["hidden_size"], model["head_dim"]
    return (2 * d * heads * width
            + 2 * d * model["num_key_value_heads"] * width + d * heads)


def pairs_per_token(model: Dict[str, Any]) -> float:
    """(token, expert) pairs a token brings to the experts held here, in
    expectation under a router that spreads its choices evenly (8 x 32 /
    256 = 1 in the cell): the batch's own count is the program's gauge
    ``model.moe.pairs_per_step``."""
    first, past = model["experts_held"]
    return (model["num_experts_per_tok"] * (past - first)
            / model["router_width"])


def ffn_matmul_params(model: Dict[str, Any], ffn: str) -> float:
    """What a token uses of a layer's FFN: the dense SwiGLU, or the router
    (its published width), the shared expert and its pairs' experts."""
    d = model["hidden_size"]
    if ffn == "dense":
        return 3.0 * d * model["intermediate_size"]
    return (d * model["router_width"]
            + 3.0 * d * model["shared_expert_intermediate_size"]
            + pairs_per_token(model) * 3.0 * d * model["moe_intermediate_size"])


def matmul_params(model: Dict[str, Any]) -> float:
    """Matmul parameters a token uses: every held layer and the untied
    head over the held vocabulary.  The input embedding is a gather."""
    total = float(model["vocab_size"] * model["hidden_size"])
    for _, ffn, heads in layer_kinds(model):
        total += mixer_matmul_params(model, heads)
        total += ffn_matmul_params(model, ffn)
    return total


def forward_flops(model: Dict[str, Any], units: float, sum_sq: float,
                  causal: bool = True) -> float:
    attention = sum(
        4.0 * heads * model["head_dim"] * pairs(model, mixer, units, sum_sq)
        for mixer, _, heads in layer_kinds(model))
    return 2.0 * matmul_params(model) * units + attention


def train_flops(model: Dict[str, Any], units: float, sum_sq: float,
                causal: bool = True) -> float:
    """Forward and backward: the backward pass of a matmul is two matmuls
    of the forward's size.  What a step recomputes is not counted."""
    return 3.0 * forward_flops(model, units, sum_sq, causal)


def kind_heads(model: Dict[str, Any], mixer: str) -> int:
    """Query heads of the held layers of kind ``mixer`` (one number a
    kind), 0 where none is held."""
    found = {heads for kind, _, heads in layer_kinds(model) if kind == mixer}
    if len(found) > 1:
        raise ValueError(f"{mixer} layers of {sorted(found)} query heads")
    return found.pop() if found else 0


def flash_ops_and_bytes(model: Dict[str, Any], mixer: str, positions: float,
                        units: float, sum_sq: float, backward: bool,
                        itemsize: int = 2) -> Tuple[float, float]:
    """One call of a flash kernel in a layer of kind ``mixer``.  Forward:
    QK^T and AV, 2 * head_dim each per required pair and query head; q
    read and o written at the query heads, k and v read once at the
    key/value heads, in the activation type, and one float32 logsumexp a
    position and query head.  Backward: twice the operations (dq, dk, dv
    and dp are four matmuls over the pairs; the scores it computes again
    are not required), and with q, k, v, o and the logsumexp it reads o's
    cotangent and writes dq at the query heads, dk and dv at the key/value
    heads."""
    h, g, d = kind_heads(model, mixer), model["num_key_value_heads"], \
        model["head_dim"]
    ops = 4.0 * h * d * pairs(model, mixer, units, sum_sq)
    nbytes = positions * (2.0 * (h + g) * d * itemsize + 4.0 * h)
    if backward:
        return 2.0 * ops, nbytes + positions * 2.0 * (h + g) * d * itemsize
    return ops, nbytes


def flash_roofline(run, mixer: str, backward: bool, what: str):
    """Share of its roofline, in percent, of a flash kernel's calls in the
    layers of kind ``mixer``: one call's bound times the calls a step the
    trace shows (``trace/calls.py``: a recomputed call counts as a call),
    over those calls' device time.  The calls are found by the program's
    scopes: a window layer's lie under ``attn/window``, the forward's at
    ``pallas_call`` and the backward's under ``flash_bwd``.  None where
    the trace shows no such call."""
    from benchmark import peaks
    from benchmark.harness import say
    from benchmark.trace import calls as trace_calls

    anchor = ("attn/window/" if mixer == "window" else "attn/") + (
        "flash_bwd" if backward else "pallas_call")
    reduced = run.reduced()
    found = None if reduced is None else trace_calls.per_step(reduced, anchor)
    if found is None or not kind_heads(run.model, mixer):
        return None
    calls, seconds = found
    work, steps = run.work(), len(run.completions)
    ops, nbytes = flash_ops_and_bytes(
        run.model, mixer, work.positions / steps / run.chips,
        work.units / steps / run.chips, work.sum_sq / steps / run.chips,
        backward)
    peak = peaks.for_kind(run.device_kind)
    # ``ops`` and ``nbytes`` are a batch's over one layer: one call's
    by_ops = ops / peak["bf16_flops_per_s"]
    by_bytes = nbytes / peak["hbm_bytes_per_s"]
    say(f"{what}.bound_by", "operations" if by_ops >= by_bytes else "bytes")
    say(f"{what}.calls_per_step", calls)
    return 100.0 * calls * max(by_ops, by_bytes) / seconds
