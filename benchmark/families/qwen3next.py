"""Hybrid decoders of Gated DeltaNet and gated softmax attention with routed
experts beside a gated shared expert (Qwen3-Next, ``qwen3_next``) through
``models/transformer.py``: a mixer for every held layer as settings of
``TransformerConfig`` (``full`` where (i + 1) % full_attention_interval
== 0, ``gdn`` elsewhere), the experts this chip holds of a router as wide
as published, zero-centred RMSNorms, the mean next-token cross-entropy."""

from __future__ import annotations

from typing import Any, Dict, List

from . import System, dtype_from
from .hybridmoe import optimizer_from


def layer_kinds(m: Dict[str, Any]) -> List[str]:
    """The mixer of each held layer, by its published index i."""
    return ["full" if (i + 1) % m["full_attention_interval"] == 0 else "gdn"
            for i in m["layers_held"]]


def _sparse(m: Dict[str, Any], i: int) -> bool:
    """Whether published layer i has experts (the modelling code's rule)."""
    return (i not in m["mlp_only_layers"] and m["router_width"] > 0
            and (i + 1) % m["decoder_sparse_step"] == 0)


def transformer_config(config: Dict[str, Any], traffic: Dict[str, Any]):
    """The ``TransformerConfig`` a configuration file describes."""
    from horovod_tpu.models.transformer import RopeRule, TransformerConfig

    m = config["model"]
    held = m["layers_held"]
    first, past = m["experts_held"]
    unsupported = {
        "biases (attention_bias)": m.get("attention_bias", False),
        "a tied head": m["tie_word_embeddings"],
        f"hidden_act {m['hidden_act']!r}": m["hidden_act"] != "silu",
        "a sliding window": m["use_sliding_window"],
        "rope_scaling": m["rope_scaling"] is not None,
        "weights not renormalised (norm_topk_prob false)":
            not m["norm_topk_prob"],
        "a shared expert of another width than the experts'":
            m["shared_expert_intermediate_size"]
            != m["moe_intermediate_size"],
        "key heads that do not divide the value heads":
            m["linear_num_value_heads"] % m["linear_num_key_heads"],
        "query heads that are not whole groups of K/V heads":
            m["num_attention_heads"] % m["num_key_value_heads"],
        "a dense MLP in a held layer (mlp_only_layers, decoder_sparse_step)":
            not all(_sparse(m, i) for i in held),
        "layers_held that are not num_hidden_layers layers":
            len(held) != m["num_hidden_layers"],
        "experts_held that are not num_experts experts":
            past - first != m["num_experts"],
    }
    for what, present in unsupported.items():
        if present:
            raise ValueError(f"families/qwen3next.py does not build {what}")
    if m["max_position_embeddings"] < traffic["seq_len"]:
        raise ValueError(
            f"{traffic['seq_len']} tokens a row exceed the model's "
            f"{m['max_position_embeddings']} positions")
    kinds = layer_kinds(m)
    value_heads = m["linear_num_value_heads"]
    return TransformerConfig(
        vocab_size=m["vocab_size"], num_layers=len(held),
        model_dim=m["hidden_size"], num_heads=value_heads,
        num_kv_heads=m["num_key_value_heads"], head_dim=m["head_dim"],
        ff_dim=m["intermediate_size"], max_len=m["max_position_embeddings"],
        dtype=dtype_from(config["activation_dtype"]),
        attn_impl=config["attn_impl"],
        remat=config["remat"], remat_save=tuple(config["remat_save"]),
        norm="zero_centred_rmsnorm", norm_eps=m["rms_norm_eps"],
        positions="rope", use_bias=False, fused_qkv=False, mlp="gated_silu",
        tie_head=False, qk_head_norm=True, attn_channel_gate=True,
        layer_kinds=tuple(kinds), ffn_kinds=("experts",) * len(held),
        # the attention's query heads; a GDN layer's heads are num_heads
        layer_heads=tuple(m["num_attention_heads"] if kind == "full"
                          else value_heads for kind in kinds),
        rope_rules=(("full", RopeRule(
            theta=float(m["rope_theta"]),
            dim=int(round(m["partial_rotary_factor"] * m["head_dim"])))),),
        kda_conv=m["linear_conv_kernel_dim"],
        gdn_key_dim=m["linear_key_head_dim"],
        gdn_value_dim=m["linear_value_head_dim"],
        gdn_key_heads=m["linear_num_key_heads"], gdn_allow_neg_eigval=False,
        num_experts=m["router_width"], experts_held=(first, past),
        expert_ff_dim=m["moe_intermediate_size"],
        experts_per_token=m["num_experts_per_tok"],
        router_scoring="softmax", shared_expert_gate=True,
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
