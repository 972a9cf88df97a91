"""Looped decoders (Ouro / LoopLM) through ``models/transformer.py``: the
current block as settings of ``TransformerConfig``, the stack applied
``total_ut_steps`` times with one set of weights, the expected loss over
the exit distribution."""

from __future__ import annotations

from typing import Any, Dict

from . import System, dtype_from


def transformer_config(config: Dict[str, Any], traffic: Dict[str, Any]):
    """The ``TransformerConfig`` a configuration file describes."""
    from horovod_tpu.models.transformer import TransformerConfig

    m = config["model"]
    unsupported = {
        "grouped key/value heads":
            m["num_key_value_heads"] != m["num_attention_heads"],
        f"hidden_act {m['hidden_act']!r}": m["hidden_act"] != "silu",
        "rope_scaling": m.get("rope_scaling") is not None,
        "a sliding window": bool(m.get("use_sliding_window")),
        "a tied head": m["tie_word_embeddings"],
    }
    for what, present in unsupported.items():
        if present:
            raise ValueError(f"families/looplm.py does not build {what}")
    if m["max_position_embeddings"] < traffic["seq_len"]:
        raise ValueError(
            f"{traffic['seq_len']} tokens a row exceed the model's "
            f"{m['max_position_embeddings']} positions")
    return TransformerConfig(
        vocab_size=m["vocab_size"], num_layers=m["num_hidden_layers"],
        model_dim=m["hidden_size"], num_heads=m["num_attention_heads"],
        head_dim=m["head_dim"], ff_dim=m["intermediate_size"],
        max_len=m["max_position_embeddings"],
        dtype=dtype_from(config["activation_dtype"]),
        attn_impl=config["attn_impl"],
        remat=config["remat"], remat_save=tuple(config["remat_save"]),
        norm="rmsnorm", norm_eps=m["rms_norm_eps"],
        positions="rope", rope_theta=float(m["rope_theta"]),
        use_bias=False, fused_qkv=False, mlp="gated_silu", post_norm=True,
        tie_head=False, ut_steps=m["total_ut_steps"], exit_gate=True,
    )


def optimizer_from(spec: Dict[str, Any]):
    """AdamW with a linear warm-up from ``warmup_from`` to the peak
    ``learning_rate`` over ``warmup_steps`` steps: the plain optax
    transformation, for the system and the reference alike."""
    import optax

    if spec["name"] != "adamw":
        raise ValueError(f"unknown optimizer {spec['name']!r}")
    schedule = optax.linear_schedule(
        spec["warmup_from"], spec["learning_rate"], spec["warmup_steps"])
    return optax.adamw(schedule, b2=spec["b2"],
                       weight_decay=spec["weight_decay"])


def build(config: Dict[str, Any], traffic: Dict[str, Any]) -> System:
    import jax.numpy as jnp

    from horovod_tpu.models.transformer import (
        Transformer,
        looped_token_cross_entropy,
        packed_looped_token_cross_entropy,
    )

    m = config["model"]
    model = Transformer(transformer_config(config, traffic))
    beta = m["entropy_beta"]

    def init(key):
        return model.init(key, jnp.zeros((1, 8), jnp.int32)), None

    if "documents" in traffic:
        def loss_fn(params, batch):
            tokens, segment_ids = batch
            logits, exits, _ = model.apply(params, tokens, segment_ids)
            return packed_looped_token_cross_entropy(
                logits, exits, tokens, segment_ids, beta)
    else:
        def loss_fn(params, batch):
            logits, exits, _ = model.apply(params, batch)
            return looped_token_cross_entropy(
                logits, exits, jnp.roll(batch, -1, axis=-1), beta)

    return System(
        init=init, loss_fn=loss_fn,
        optimizer=optimizer_from(config["optimizer"]),
        compression=config["compression"], stateful=False,
        element={"kind": "tokens", "vocab_size": m["vocab_size"]},
    )
