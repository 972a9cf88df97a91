"""Decoders that mix full and sliding-window attention over grouped
key/value heads, with routed experts (Laguna-XS.2, ``laguna``) through
``models/transformer.py``: a mixer, a head count, a rotary rule and an FFN
for every held layer as settings of ``TransformerConfig``, the experts this
chip holds of a router as wide as published, the mean next-token
cross-entropy."""

from __future__ import annotations

from typing import Any, Dict, List, Tuple

from . import System, dtype_from
from .hybridmoe import optimizer_from

MIXERS = {"full_attention": "full", "sliding_attention": "window"}
FFNS = {"dense": "dense", "sparse": "experts"}


def layer_kinds(m: Dict[str, Any]) -> List[Tuple[str, str, int]]:
    """(mixer, FFN, query heads) of the held layers, read from the
    published lists at their published index."""
    return [(MIXERS[m["layer_types"][i]], FFNS[m["mlp_layer_types"][i]],
             m["num_attention_heads_per_layer"][i])
            for i in m["layers_held"]]


def rope_rule(m: Dict[str, Any], layer_type: str):
    """The ``RopeRule`` of ``rope_parameters[layer_type]``."""
    from horovod_tpu.models.transformer import RopeRule

    p = m["rope_parameters"][layer_type]
    turning = int(round(p["partial_rotary_factor"] * m["head_dim"]))
    if p["rope_type"] == "default":
        return RopeRule(theta=float(p["rope_theta"]), dim=turning)
    if p["rope_type"] != "yarn":
        raise ValueError(
            f"families/swamoe.py does not build rope_type {p['rope_type']!r}")
    return RopeRule(
        theta=float(p["rope_theta"]), dim=turning, factor=float(p["factor"]),
        original_max_len=p["original_max_position_embeddings"],
        beta_fast=float(p["beta_fast"]), beta_slow=float(p["beta_slow"]),
        attention_factor=float(p["attention_factor"]))


def transformer_config(config: Dict[str, Any], traffic: Dict[str, Any]):
    """The ``TransformerConfig`` a configuration file describes."""
    from horovod_tpu.models.transformer import TransformerConfig

    m = config["model"]
    held = m["layers_held"]
    first, past = m["experts_held"]
    published = len(m["layer_types"])
    unsupported = {
        "biases (attention_bias)": m["attention_bias"],
        "a tied head": m["tie_word_embeddings"],
        "attention without its output gate, or a gate that is not the "
        "boolean `gating`": m["gating"] is not True,
        "a shared expert of another width than the experts'":
            m["shared_expert_intermediate_size"]
            != m["moe_intermediate_size"],
        "the router's weight on the expert's input":
            m["moe_apply_router_weight_on_input"],
        "a layer or FFN type it does not know": not (
            set(m["layer_types"]) <= set(MIXERS)
            and set(m["mlp_layer_types"]) <= set(FFNS)),
        "per-layer lists of different lengths": not (
            published == len(m["mlp_layer_types"])
            == len(m["num_attention_heads_per_layer"])),
        "layers_held that are not num_hidden_layers published layers":
            len(held) != m["num_hidden_layers"]
            or not all(0 <= i < published for i in held),
        "experts_held that are not num_experts experts":
            past - first != m["num_experts"],
        "a rotary rule for other layer types than the two":
            not {"full_attention", "sliding_attention"}
            <= set(m["rope_parameters"]),
    }
    for what, present in unsupported.items():
        if present:
            raise ValueError(f"families/swamoe.py does not build {what}")
    if m["max_position_embeddings"] < traffic["seq_len"]:
        raise ValueError(
            f"{traffic['seq_len']} tokens a row exceed the model's "
            f"{m['max_position_embeddings']} positions")
    kinds = layer_kinds(m)
    return TransformerConfig(
        vocab_size=m["vocab_size"], num_layers=len(held),
        model_dim=m["hidden_size"], num_heads=m["num_attention_heads"],
        num_kv_heads=m["num_key_value_heads"], head_dim=m["head_dim"],
        ff_dim=m["intermediate_size"], max_len=m["max_position_embeddings"],
        dtype=dtype_from(config["activation_dtype"]),
        attn_impl=config["attn_impl"],
        remat=config["remat"], remat_save=tuple(config["remat_save"]),
        norm="rmsnorm", norm_eps=m["rms_norm_eps"], positions="rope",
        use_bias=False, fused_qkv=False, mlp="gated_silu", tie_head=False,
        layer_kinds=tuple(mixer for mixer, _, _ in kinds),
        ffn_kinds=tuple(ffn for _, ffn, _ in kinds),
        layer_heads=tuple(heads for _, _, heads in kinds),
        window=m["sliding_window"], attn_gate=True,
        rope_rules=tuple(
            (MIXERS[layer_type], rope_rule(m, layer_type))
            for layer_type in ("full_attention", "sliding_attention")),
        num_experts=m["router_width"], experts_held=(first, past),
        expert_ff_dim=m["moe_intermediate_size"],
        experts_per_token=m["num_experts_per_tok"], n_group=1, topk_group=1,
        routed_scaling=float(m["moe_routed_scaling_factor"]),
    )


def build(config: Dict[str, Any], traffic: Dict[str, Any]) -> System:
    import jax.numpy as jnp

    from horovod_tpu.models.transformer import (
        Transformer,
        packed_token_cross_entropy,
        token_cross_entropy,
    )

    model = Transformer(transformer_config(config, traffic))

    def init(key):
        return model.init(key, jnp.zeros((1, 8), jnp.int32)), None

    if "documents" in traffic:
        def loss_fn(params, batch):
            tokens, segment_ids = batch
            logits, _ = model.apply(params, tokens, segment_ids)
            return packed_token_cross_entropy(logits, tokens, segment_ids)
    else:
        def loss_fn(params, batch):
            logits, _ = model.apply(params, batch)
            return token_cross_entropy(logits, jnp.roll(batch, -1, axis=-1))

    return System(
        init=init, loss_fn=loss_fn,
        optimizer=optimizer_from(config["optimizer"]),
        compression=config["compression"], stateful=False,
        element={"kind": "tokens",
                 "vocab_size": config["model"]["vocab_size"]},
    )
