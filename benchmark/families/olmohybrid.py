"""Hybrid decoders of Gated DeltaNet and full attention under OLMo 2's
norms (Olmo-Hybrid-7B, ``olmo_hybrid``) through ``models/transformer.py``:
a mixer for every held layer as settings of ``TransformerConfig`` (``gdn``
where the published layer is ``linear_attention``, ``full`` where it is
``full_attention``), a dense SwiGLU everywhere, the mean next-token
cross-entropy."""

from __future__ import annotations

from typing import Any, Dict, List

from . import System, dtype_from
from .hybridmoe import optimizer_from

MIXERS = {"linear_attention": "gdn", "full_attention": "full"}


def layer_kinds(m: Dict[str, Any]) -> List[str]:
    """The mixer of each held layer, read from the published
    ``layer_types`` at its published index."""
    return [MIXERS[m["layer_types"][i]] for i in m["layers_held"]]


def positions(m: Dict[str, Any]) -> str:
    """"none" where ``rope_parameters.rope_theta`` is null (the published
    file's reading: no rotary positions), "rope" where it is set: the
    one place a reader with the modelling code would change."""
    return "none" if m["rope_parameters"]["rope_theta"] is None else "rope"


def transformer_config(config: Dict[str, Any], traffic: Dict[str, Any]):
    """The ``TransformerConfig`` a configuration file describes."""
    from horovod_tpu.models.transformer import TransformerConfig

    m = config["model"]
    held = m["layers_held"]
    heads = m["num_attention_heads"]
    unsupported = {
        "biases (attention_bias)": m["attention_bias"],
        "a tied head": m["tie_word_embeddings"],
        f"hidden_act {m['hidden_act']!r}": m["hidden_act"] != "silu",
        "grouped key/value heads":
            m["num_key_value_heads"] != heads,
        "linear-attention heads other than the attention's":
            not m["linear_num_key_heads"] == m["linear_num_value_heads"]
            == heads,
        "a hidden size that is not whole heads": m["hidden_size"] % heads,
        "beta in (0, 1) (linear_allow_neg_eigval false)":
            not m["linear_allow_neg_eigval"],
        "a layer type it does not know":
            not set(m["layer_types"]) <= set(MIXERS),
        "layers_held that are not num_hidden_layers published layers":
            len(held) != m["num_hidden_layers"]
            or not all(0 <= i < len(m["layer_types"]) for i in held),
    }
    for what, present in unsupported.items():
        if present:
            raise ValueError(f"families/olmohybrid.py does not build {what}")
    if m["max_position_embeddings"] < traffic["seq_len"]:
        raise ValueError(
            f"{traffic['seq_len']} tokens a row exceed the model's "
            f"{m['max_position_embeddings']} positions")
    theta = m["rope_parameters"]["rope_theta"]
    return TransformerConfig(
        vocab_size=m["vocab_size"], num_layers=len(held),
        model_dim=m["hidden_size"], num_heads=heads,
        head_dim=m["hidden_size"] // heads, ff_dim=m["intermediate_size"],
        max_len=m["max_position_embeddings"],
        dtype=dtype_from(config["activation_dtype"]),
        attn_impl=config["attn_impl"],
        remat=config["remat"], remat_save=tuple(config["remat_save"]),
        norm="rmsnorm", norm_eps=m["rms_norm_eps"],
        positions=positions(m),
        rope_theta=10000.0 if theta is None else float(theta),
        use_bias=False, fused_qkv=False, mlp="gated_silu", tie_head=False,
        pre_norm=False, post_norm=True, qk_norm=True,
        layer_kinds=tuple(layer_kinds(m)),
        kda_conv=m["linear_conv_kernel_dim"],
        gdn_key_dim=m["linear_key_head_dim"],
        gdn_value_dim=m["linear_value_head_dim"],
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
