"""Hybrid linear-attention / latent-attention decoders with routed experts
(Ling-3.0-flash, ``bailing_hybrid``) through ``models/transformer.py``: a
mixer and an FFN for every held layer as settings of ``TransformerConfig``,
the experts this chip holds of a router as wide as published, the mean
next-token cross-entropy."""

from __future__ import annotations

from typing import Any, Dict, List, Tuple

from . import System, dtype_from


def layer_kinds(m: Dict[str, Any]) -> List[Tuple[str, str]]:
    """(mixer, FFN) of the held layers by their published index i: latent
    attention where (i + 1) % layer_group_size == 0, the delta rule
    elsewhere; a dense MLP below first_k_dense_replace, experts from there."""
    return [
        ("mla" if (i + 1) % m["layer_group_size"] == 0 else "kda",
         "dense" if i < m["first_k_dense_replace"] else "experts")
        for i in m["layers_held"]]


def transformer_config(config: Dict[str, Any], traffic: Dict[str, Any]):
    """The ``TransformerConfig`` a configuration file describes."""
    from horovod_tpu.models.transformer import TransformerConfig

    m = config["model"]
    held = m["layers_held"]
    first, past = m["experts_held"]
    unsupported = {
        "a low-rank q (q_lora_rank)": m["q_lora_rank"] is not None,
        "grouped key/value heads":
            m["num_key_value_heads"] != m["num_attention_heads"]
            or m["num_kv_heads_for_linear_attn"] not in (
                0, m["num_attention_heads"]),
        f"hidden_act {m['hidden_act']!r}": m["hidden_act"] != "silu",
        "rope_scaling": m.get("rope_scaling") is not None,
        "a tied head": m["tie_word_embeddings"],
        "biases": m["use_bias"] or m["use_qkv_bias"],
        f"score_function {m['score_function']!r}":
            m["score_function"] != "sigmoid" or not m["norm_topk_prob"]
            or m["topk_method"] != "noaux_tc",
        "other than one shared expert of the experts' width":
            m["num_shared_experts"] != 1
            or m["moe_shared_expert_intermediate_size"]
            != m["moe_intermediate_size"],
        "a clamped SwiGLU in a held layer": any(
            m["expert_swiglu_limit_list"][i]
            or m["share_expert_swiglu_limit_list"][i] for i in held),
        "a gate that is not one a head":
            m["gated_attention_proj_granularity_type"] != "head_wise",
        "a group norm over several heads": m["group_norm_size"] != 1,
        "KDA without its bounded gate, its q/k norm or its full-rank f":
            not (m["kda_safe_gate"] and m["use_qk_norm"]
                 and m["no_kda_lora"]) or m["use_kda_lora"]
            or not m["linear_silu"],
        "v_head_dim other than head_dim": m["v_head_dim"] != m["head_dim"],
        "use_mla_nope, value_norm, up_proj_norm, scale_router_input, "
        "use_nGPT": any(m[k] for k in (
            "use_mla_nope", "value_norm", "up_proj_norm",
            "scale_router_input", "use_nGPT")),
        "multi-token prediction": m["num_nextn_predict_layers"] != 0,
        "layers_held that are not num_hidden_layers layers":
            len(held) != m["num_hidden_layers"],
        "experts_held that are not num_experts experts":
            past - first != m["num_experts"],
    }
    for what, present in unsupported.items():
        if present:
            raise ValueError(f"families/hybridmoe.py does not build {what}")
    if m["max_position_embeddings"] < traffic["seq_len"]:
        raise ValueError(
            f"{traffic['seq_len']} tokens a row exceed the model's "
            f"{m['max_position_embeddings']} positions")
    kinds = layer_kinds(m)
    return TransformerConfig(
        vocab_size=m["vocab_size"], num_layers=len(held),
        model_dim=m["hidden_size"], num_heads=m["num_attention_heads"],
        head_dim=m["head_dim"], ff_dim=m["intermediate_size"],
        max_len=m["max_position_embeddings"],
        dtype=dtype_from(config["activation_dtype"]),
        attn_impl=config["attn_impl"],
        remat=config["remat"], remat_save=tuple(config["remat_save"]),
        norm="rmsnorm", norm_eps=m["rms_norm_eps"],
        positions="rope", rope_theta=float(m["rope_theta"]),
        use_bias=False, fused_qkv=False, mlp="gated_silu", tie_head=False,
        layer_kinds=tuple(mixer for mixer, _ in kinds),
        ffn_kinds=tuple(ffn for _, ffn in kinds),
        kv_lora_rank=m["kv_lora_rank"], qk_nope_dim=m["qk_nope_head_dim"],
        rope_dim=m["qk_rope_head_dim"], rope_interleave=m["rope_interleave"],
        kda_conv=m["short_conv_kernel_size"],
        kda_lower_bound=float(m["kda_lower_bound"]),
        num_experts=m["router_width"], experts_held=(first, past),
        expert_ff_dim=m["moe_intermediate_size"],
        experts_per_token=m["num_experts_per_tok"],
        n_group=m["n_group"], topk_group=m["topk_group"],
        routed_scaling=float(m["routed_scaling_factor"]),
    )


def optimizer_from(spec: Dict[str, Any]):
    """AdamW with a linear warm-up (``families/looplm.py``'s), its weight
    decay kept off the routers' selection bias, which no gradient reaches
    either: the buffer stays at its seeded value.  The plain optax
    transformation, for the system and the reference alike."""
    import jax
    import optax

    if spec["name"] != "adamw":
        raise ValueError(f"unknown optimizer {spec['name']!r}")
    schedule = optax.linear_schedule(
        spec["warmup_from"], spec["learning_rate"], spec["warmup_steps"])

    def decayed(params):
        return jax.tree_util.tree_map_with_path(
            lambda path, _: "router_bias" not in jax.tree_util.keystr(path),
            params)

    return optax.adamw(schedule, b2=spec["b2"],
                       weight_decay=spec["weight_decay"], mask=decayed)


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
