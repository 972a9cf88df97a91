"""GPT-2-style decoders through ``models/transformer.py``, trained the way
``chip_smoke.py`` and ``bench.py`` train them."""

from __future__ import annotations

from typing import Any, Dict

from . import System, dtype_from, optimizer_from
from ..ops import gpt as ops


def build(config: Dict[str, Any], traffic: Dict[str, Any]) -> System:
    import jax.numpy as jnp

    from horovod_tpu.models.transformer import (
        Transformer,
        TransformerConfig,
        packed_token_cross_entropy,
        token_cross_entropy,
    )

    m = config["model"]
    if m["layer_norm_epsilon"] != 1e-6:
        raise ValueError(
            "families/gpt.py builds GPT-2 with LayerNorm's epsilon left at "
            "flax's 1e-6 (TransformerConfig.norm_eps is not passed); the "
            f"configuration says {m['layer_norm_epsilon']}")
    if m["n_positions"] < traffic["seq_len"]:
        raise ValueError(
            f"{traffic['seq_len']} tokens a row exceed the model's "
            f"{m['n_positions']} positions")
    model = Transformer(TransformerConfig(
        vocab_size=m["vocab_size"], num_layers=m["n_layer"],
        model_dim=m["n_embd"], num_heads=m["n_head"],
        head_dim=ops.head_dim(m), ff_dim=ops.ff_dim(m),
        max_len=m["n_positions"],
        dtype=dtype_from(config["activation_dtype"]),
        attn_impl=config["attn_impl"],
    ))
    packed = "documents" in traffic

    def init(key):
        return model.init(key, jnp.zeros((1, 8), jnp.int32)), None

    if packed:
        def loss_fn(params, batch):
            tokens, segment_ids = batch
            logits, aux = model.apply(params, tokens, segment_ids)
            return packed_token_cross_entropy(
                logits, tokens, segment_ids) + 0.01 * aux
    else:
        def loss_fn(params, batch):
            logits, aux = model.apply(params, batch)
            return token_cross_entropy(
                logits, jnp.roll(batch, -1, axis=-1)) + 0.01 * aux

    return System(
        init=init, loss_fn=loss_fn,
        optimizer=optimizer_from(config["optimizer"]),
        compression=config["compression"], stateful=False,
        element={"kind": "tokens", "vocab_size": m["vocab_size"]},
    )
