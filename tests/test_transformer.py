"""Transformer model family: single-device semantics, and equality of
the sp (ring/Ulysses) and tp sharded paths against the unsharded model
with identical weights."""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import shard_map
from jax.sharding import PartitionSpec as P

from horovod_tpu.models import Transformer, TransformerConfig, gpt_tiny
from horovod_tpu.models.transformer import Attention
from horovod_tpu.parallel import make_mesh


def _tokens(b=2, t=32, vocab=256, seed=0):
    return jax.random.randint(jax.random.PRNGKey(seed), (b, t), 0, vocab)


def test_forward_single_device():
    model = gpt_tiny()
    toks = _tokens(t=16)
    params = model.init(jax.random.PRNGKey(1), toks)
    logits, aux = model.apply(params, toks)
    assert logits.shape == (2, 16, 256)
    assert np.isfinite(np.asarray(logits)).all()
    assert float(aux) == 0.0


def test_grads_flow():
    model = gpt_tiny()
    toks = _tokens(t=16)
    params = model.init(jax.random.PRNGKey(1), toks)

    def loss(p):
        logits, aux = model.apply(p, toks)
        onehot = jax.nn.one_hot(toks, 256)
        return -jnp.mean(jnp.sum(jax.nn.log_softmax(logits) * onehot, -1)) + aux

    g = jax.jit(jax.grad(loss))(params)
    leaves = jax.tree.leaves(g)
    assert all(np.isfinite(np.asarray(l)).all() for l in leaves)
    assert any(float(jnp.abs(l).sum()) > 0 for l in leaves)


@pytest.mark.parametrize("impl,heads,head_dim", [
    ("ring", 4, 16),
    ("ulysses", 8, 8),
])
def test_sequence_parallel_matches_single_device(impl, heads, head_dim):
    """sp-sharded transformer (ring / Ulysses) == unsharded transformer
    with the same weights: sequence parallelism is numerically
    transparent."""
    toks = _tokens(b=2, t=32)
    # attn_impl="full": the reference must be *exact* attention, not the
    # flash kernel, so shared flash numerics can't cancel out.
    ref_model = gpt_tiny(num_heads=heads, head_dim=head_dim, attn_impl="full")
    params = ref_model.init(jax.random.PRNGKey(2), toks)
    ref_logits, _ = jax.jit(ref_model.apply)(params, toks)

    sp_model = gpt_tiny(num_heads=heads, head_dim=head_dim, attn_impl=impl)
    mesh = make_mesh(sp=8)
    f = shard_map(
        lambda p, tk: sp_model.apply(p, tk)[0],
        mesh=mesh,
        in_specs=(P(), P(None, "sp")),
        out_specs=P(None, "sp"),
        check_vma=False,  # pallas_call has no replication rule pre-0.5
    )
    logits = jax.jit(f)(params, toks)
    np.testing.assert_allclose(
        np.asarray(logits), np.asarray(ref_logits), atol=2e-4
    )


def test_tp_attention_matches_single_device():
    """tp-sharded attention == unsharded attention when the local QKV /
    proj kernels are the per-head shards of the global kernels."""
    d, heads, head_dim, b, t = 32, 8, 8, 2, 16
    cfg = TransformerConfig(
        vocab_size=64, num_layers=1, model_dim=d, num_heads=heads,
        head_dim=head_dim, ff_dim=64, max_len=t, dtype=jnp.float32,
    )
    x = jax.random.normal(jax.random.PRNGKey(3), (b, t, d))
    attn = Attention(cfg)
    params = attn.init(jax.random.PRNGKey(4), x)["params"]
    ref = jax.jit(lambda p, x: attn.apply({"params": p}, x))(params, x)

    n = 8
    qkv_k = params["qkv"]["Dense_0"]["kernel"].reshape(d, 3, heads, head_dim)
    qkv_b = params["qkv"]["Dense_0"]["bias"].reshape(3, heads, head_dim)
    proj_k = params["proj"]["Dense_0"]["kernel"].reshape(heads, head_dim, d)
    flat = {
        # per-device leading dim: head h of q/k/v goes to device h
        "qkv_k": qkv_k.transpose(2, 0, 1, 3).reshape(n, d, 3 * head_dim),
        "qkv_b": qkv_b.transpose(1, 0, 2).reshape(n, 3 * head_dim),
        "proj_k": proj_k,
        "proj_b": params["proj"]["bias"],
    }

    mesh = make_mesh(tp=8)

    def fn(flat, x):
        local = {
            "qkv": {"Dense_0": {"kernel": flat["qkv_k"][0],
                                "bias": flat["qkv_b"][0]}},
            "proj": {"Dense_0": {"kernel": flat["proj_k"][0]},
                     "bias": flat["proj_b"]},
        }
        return attn.apply({"params": local}, x)

    f = shard_map(
        fn, mesh=mesh,
        in_specs=(
            {"qkv_k": P("tp"), "qkv_b": P("tp"), "proj_k": P("tp"),
             "proj_b": P()},
            P(),
        ),
        out_specs=P(),
        check_vma=False,  # pallas_call has no replication rule pre-0.5
    )
    out = jax.jit(f)(flat, x)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-5)


def test_moe_transformer_forward():
    model = gpt_tiny(moe_every=1, num_experts_local=4)
    toks = _tokens(t=16)
    params = model.init(jax.random.PRNGKey(5), toks)
    logits, aux = model.apply(params, toks)
    assert logits.shape == (2, 16, 256)
    assert np.isfinite(np.asarray(logits)).all()
    assert float(aux) > 0.0


def test_tp_transformer_runs_sharded():
    toks = _tokens(b=2, t=16)
    model = gpt_tiny(num_heads=8, head_dim=8)
    mesh = make_mesh(tp=8)

    def init_and_apply(toks):
        params = model.init(jax.random.PRNGKey(6), toks)
        logits, _ = model.apply(params, toks)
        return logits

    f = shard_map(
        init_and_apply, mesh=mesh, in_specs=(P(),), out_specs=P(),
        check_vma=False,  # rng-based init is replicated but uninferable
    )
    logits = jax.jit(f)(toks)
    assert logits.shape == (2, 16, 256)
    assert np.isfinite(np.asarray(logits)).all()


def test_shard_local_attention_on_sp_mesh_raises():
    """flash/full on a sequence-sharded mesh must refuse (they would
    silently drop cross-shard attention)."""
    toks = _tokens(b=2, t=32)
    model = gpt_tiny(attn_impl="flash")
    params = model.init(jax.random.PRNGKey(0), toks)
    mesh = make_mesh(sp=8)
    f = shard_map(
        lambda p, tk: model.apply(p, tk)[0],
        mesh=mesh, in_specs=(P(), P(None, "sp")), out_specs=P(None, "sp"),
    )
    with pytest.raises(ValueError, match="shard-local"):
        jax.jit(f)(params, toks)


@pytest.mark.slow
def test_remat_matches_no_remat():
    """cfg.remat must change memory behavior only — identical logits
    and gradients (jax.checkpoint semantics)."""
    toks = jnp.asarray(np.random.RandomState(0).randint(0, 256, (2, 16)))
    plain = gpt_tiny()
    remat = gpt_tiny(remat=True)
    params = plain.init(jax.random.PRNGKey(0), toks)

    def loss(model, p):
        logits, aux = model.apply(p, toks)
        return jnp.mean(logits ** 2) + aux

    l1, g1 = jax.value_and_grad(lambda p: loss(plain, p))(params)
    l2, g2 = jax.value_and_grad(lambda p: loss(remat, p))(params)
    np.testing.assert_allclose(float(l1), float(l2), rtol=1e-6)
    for a, b in zip(jax.tree.leaves(g1), jax.tree.leaves(g2)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-5)


def test_token_cross_entropy_matches_one_hot_form():
    """Gather-form LM loss == one-hot log-softmax form (value + grad)
    without materializing a (B, T, vocab) temporary."""
    import jax

    rng = np.random.RandomState(3)
    logits = jnp.asarray(rng.randn(2, 7, 131), jnp.float32)
    tgt = jnp.asarray(rng.randint(0, 131, (2, 7)), jnp.int32)

    from horovod_tpu.models.transformer import token_cross_entropy

    onehot = jax.nn.one_hot(tgt, 131)

    def ref_loss(l):
        return -jnp.mean(jnp.sum(jax.nn.log_softmax(l) * onehot, -1))

    np.testing.assert_allclose(
        float(token_cross_entropy(logits, tgt)), float(ref_loss(logits)),
        rtol=1e-6,
    )
    g_ref = jax.grad(ref_loss)(logits)
    g_new = jax.grad(lambda l: token_cross_entropy(l, tgt))(logits)
    np.testing.assert_allclose(
        np.asarray(g_new), np.asarray(g_ref), rtol=1e-5, atol=1e-7
    )
    # bf16 logits: loss still accumulates in fp32
    lb = logits.astype(jnp.bfloat16)
    assert token_cross_entropy(lb, tgt).dtype == jnp.float32


# ---- sequence packing (VERDICT r4 item 3) -------------------------------

class TestSequencePacking:
    """Packed rows must compute exactly what the same documents would
    compute unpacked: per-document logits equal, loss equal."""

    @staticmethod
    def _docs_and_packed(seq_len=32, impl="full"):
        from horovod_tpu.data.packing import pack_documents

        rng = np.random.RandomState(0)
        docs = [
            rng.randint(1, 256, n).astype(np.int32) for n in (12, 9, 7, 20)
        ]
        toks, segs = pack_documents(docs, seq_len)
        model = gpt_tiny(attn_impl=impl, max_len=seq_len)
        params = model.init(
            jax.random.PRNGKey(1), jnp.asarray(toks), jnp.asarray(segs)
        )
        return docs, toks, segs, model, params

    @pytest.mark.parametrize("impl", ["full", "flash"])
    def test_packed_logits_match_unpacked_per_document(self, impl):
        docs, toks, segs, model, params = self._docs_and_packed(impl=impl)
        apply = jax.jit(model.apply)
        packed_logits, _ = apply(
            params, jnp.asarray(toks), jnp.asarray(segs)
        )
        packed_logits = np.asarray(packed_logits)
        for d in docs:
            # locate this doc's span in the packed rows
            found = False
            for r in range(toks.shape[0]):
                for s in range(1, segs[r].max() + 1):
                    idx = np.where(segs[r] == s)[0]
                    if len(idx) == len(d) and (toks[r, idx] == d).all():
                        solo, _ = apply(params, jnp.asarray(d)[None])
                        np.testing.assert_allclose(
                            packed_logits[r, idx], np.asarray(solo)[0],
                            rtol=2e-4, atol=2e-4,
                        )
                        found = True
                        break
                if found:
                    break
            assert found, f"doc of len {len(d)} not located in packed rows"

    def test_packed_loss_matches_unpacked_mean(self):
        from horovod_tpu.models.transformer import (
            packed_token_cross_entropy,
            token_cross_entropy,
        )

        docs, toks, segs, model, params = self._docs_and_packed()
        logits, _ = model.apply(params, jnp.asarray(toks), jnp.asarray(segs))
        packed_loss = float(packed_token_cross_entropy(
            logits, jnp.asarray(toks), jnp.asarray(segs)
        ))
        # unpacked: token-weighted mean of per-document next-token CE
        tot, cnt = 0.0, 0
        for d in docs:
            solo, _ = model.apply(params, jnp.asarray(d)[None])
            per_tok = float(token_cross_entropy(
                solo[:, :-1], jnp.asarray(d)[None, 1:]
            ))
            tot += per_tok * (len(d) - 1)
            cnt += len(d) - 1
        np.testing.assert_allclose(packed_loss, tot / cnt, rtol=1e-4)

    def test_packed_grads_flow(self):
        from horovod_tpu.models.transformer import packed_token_cross_entropy

        _, toks, segs, model, params = self._docs_and_packed(impl="flash")

        def loss_fn(p):
            logits, _ = model.apply(p, jnp.asarray(toks), jnp.asarray(segs))
            return packed_token_cross_entropy(
                logits, jnp.asarray(toks), jnp.asarray(segs)
            )

        grads = jax.jit(jax.grad(loss_fn))(params)
        total = sum(
            float(jnp.abs(g).sum()) for g in jax.tree.leaves(grads)
        )
        assert np.isfinite(total) and total > 0

    def test_packing_utility_first_fit(self):
        from horovod_tpu.data.packing import (
            pack_documents,
            packing_efficiency,
        )

        docs = [np.arange(1, n + 1, dtype=np.int32) for n in (30, 20, 10, 2)]
        toks, segs = pack_documents(docs, 32)
        # first-fit decreasing: [30, 2] and [20, 10] -> exactly 2 rows
        assert toks.shape == (2, 32)
        assert packing_efficiency(segs) > 0.9
        # every document fully present exactly once
        flat = []
        for r in range(toks.shape[0]):
            for s in range(1, segs[r].max() + 1):
                idx = np.where(segs[r] == s)[0]
                flat.append(tuple(toks[r, idx]))
        assert sorted(len(f) for f in flat) == [2, 10, 20, 30]

    def test_long_document_splits_into_chunks(self):
        from horovod_tpu.data.packing import pack_documents

        toks, segs = pack_documents(
            [np.arange(1, 71, dtype=np.int32)], 32
        )
        got = np.concatenate(
            [toks[r][segs[r] > 0] for r in range(toks.shape[0])]
        )
        assert sorted(got.tolist()) == list(range(1, 71))

    def test_packed_rejects_sequence_parallel(self):
        from horovod_tpu.parallel import make_mesh

        model = gpt_tiny(attn_impl="ring")
        mesh = make_mesh(sp=8)
        toks = jnp.zeros((1, 32), jnp.int32)
        segs = jnp.ones((1, 32), jnp.int32)

        def run(t, s):
            return model.init(jax.random.PRNGKey(0), t, s)

        with pytest.raises(ValueError, match="pack"):
            jax.jit(shard_map(
                run, mesh=mesh, in_specs=(P(None, "sp"), P(None, "sp")),
                out_specs=P(), check_vma=False,
            ))(toks, segs)

    def test_pack_batches_streaming(self):
        from horovod_tpu.data.packing import pack_batches

        rng = np.random.RandomState(1)
        docs = [
            rng.randint(1, 99, rng.randint(5, 30)).astype(np.int32)
            for _ in range(120)
        ]
        batches = list(pack_batches(iter(docs), seq_len=32, batch_size=4))
        assert len(batches) >= 5
        seen = []
        for toks, segs in batches:
            assert toks.shape == (4, 32) and segs.shape == (4, 32)
            for r in range(4):
                for s in range(1, int(segs[r].max()) + 1):
                    idx = np.where(segs[r] == s)[0]
                    if len(idx):
                        seen.append(tuple(toks[r, idx]))
        # every emitted span is one of the source docs (or a chunk of
        # one), and most of the stream was emitted
        doc_set = {tuple(d) for d in docs}
        assert sum(s in doc_set for s in seen) >= len(seen) * 0.9
        assert len(seen) >= 100

    def test_pack_batches_remainder_padding(self):
        from horovod_tpu.data.packing import pack_batches

        docs = [np.arange(1, 11, dtype=np.int32) for _ in range(3)]
        out = list(pack_batches(iter(docs), seq_len=16, batch_size=4,
                                drop_remainder=False))
        assert len(out) == 1
        toks, segs = out[0]
        assert toks.shape == (4, 16)
        # padded rows carry segment 0 everywhere
        assert (segs[(segs > 0).any(axis=1) == False] == 0).all()  # noqa: E712


# ---------------------------------------------------------------------------
# The current block and the loop as settings of TransformerConfig
# ---------------------------------------------------------------------------

def _tree_shapes(params):
    return {jax.tree_util.keystr(path): leaf.shape for path, leaf in
            jax.tree_util.tree_flatten_with_path(params)[0]}


def test_defaults_keep_gpt2s_parameter_tree():
    """The settings the looped decoder added leave GPT-2's parameters as
    they were, names and shapes."""
    model = gpt_tiny()
    shapes = _tree_shapes(model.init(jax.random.PRNGKey(1), _tokens(t=16)))
    block = {
        "['attn']['proj']['Dense_0']['kernel']": (64, 64),
        "['attn']['proj']['bias']": (64,),
        "['attn']['qkv']['Dense_0']['bias']": (192,),
        "['attn']['qkv']['Dense_0']['kernel']": (64, 192),
        "['ln_attn']['bias']": (64,), "['ln_attn']['scale']": (64,),
        "['ln_mlp']['bias']": (64,), "['ln_mlp']['scale']": (64,),
        "['mlp']['wi']['Dense_0']['bias']": (128,),
        "['mlp']['wi']['Dense_0']['kernel']": (64, 128),
        "['mlp']['wo']['Dense_0']['kernel']": (128, 64),
        "['mlp']['wo']['bias']": (64,),
    }
    want = {f"['params']['block_{i}']{k}": v
            for i in range(2) for k, v in block.items()}
    want.update({
        "['params']['ln_f']['bias']": (64,),
        "['params']['ln_f']['scale']": (64,),
        "['params']['wpe']": (256, 64),
        "['params']['wte']['embedding']": (256, 64),
    })
    assert shapes == want


def _looped(**overrides):
    cfg = dict(
        vocab_size=96, num_layers=2, model_dim=32, num_heads=2, head_dim=16,
        ff_dim=48, max_len=64, dtype=jnp.float32, norm="rmsnorm",
        positions="rope", rope_theta=1e4, use_bias=False, fused_qkv=False,
        mlp="gated_silu", post_norm=True, tie_head=False, ut_steps=3,
        exit_gate=True)
    cfg.update(overrides)
    return Transformer(TransformerConfig(**cfg))


def test_looped_model_shares_one_set_of_weights_over_its_passes():
    from horovod_tpu import metrics

    toks = _tokens(t=16, vocab=96)
    model = _looped()
    params = jax.jit(model.init)(jax.random.PRNGKey(1), toks)
    names = set(params["params"])
    assert names == {"block_0", "block_1", "ln_f", "wte", "head",
                     "exit_gate"}  # no wpe, and no block per pass
    assert set(params["params"]["block_0"]) == {
        "attn", "mlp", "ln_attn", "ln_attn_post", "ln_mlp", "ln_mlp_post"}
    assert set(params["params"]["block_0"]["attn"]) == {
        "q", "k", "v", "proj"}
    assert set(params["params"]["block_0"]["mlp"]) == {"wg", "wi", "wo"}
    assert all("bias" not in k for k in _tree_shapes(params)
               if "exit_gate" not in k)
    logits, exits, aux = jax.jit(model.apply)(params, toks)
    assert logits.shape == (3, 2, 16, 96) and exits.shape == (3, 2, 16)
    assert metrics.get_gauge("model.layer_applications") == 6
    assert metrics.get_gauge("model.ut_steps") == 3
    # the first pass of the loop is the one-pass model with these weights
    once, once_exits, _ = jax.jit(_looped(ut_steps=1).apply)(params, toks)
    np.testing.assert_allclose(once[0], logits[0], atol=1e-6)
    np.testing.assert_allclose(once_exits[0], exits[0], atol=1e-6)
    assert metrics.get_gauge("model.layer_applications") == 2
    # without a gate only the last pass has a head
    gateless = {"params": {k: v for k, v in params["params"].items()
                           if k != "exit_gate"}}
    last, _ = jax.jit(_looped(exit_gate=False).apply)(gateless, toks)
    np.testing.assert_allclose(last, logits[-1], atol=1e-6)


@pytest.mark.parametrize("save", [(), ("flash_out",),
                                  ("flash_out", "flash_qkv")])
def test_what_a_rematerialised_block_keeps_changes_no_value(save):
    toks = _tokens(t=16, vocab=96)
    plain = _looped()
    remat = _looped(remat=True, remat_save=save)
    params = jax.jit(plain.init)(jax.random.PRNGKey(0), toks)

    def loss(model, p):
        logits, exits, _ = model.apply(p, toks)
        return jnp.mean(logits ** 2) + jnp.mean(exits ** 2)

    l1, g1 = jax.value_and_grad(lambda p: loss(plain, p))(params)
    l2, g2 = jax.value_and_grad(lambda p: loss(remat, p))(params)
    np.testing.assert_allclose(float(l1), float(l2), rtol=1e-6)
    for a, b in zip(jax.tree.leaves(g1), jax.tree.leaves(g2)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-7)
    # the names reach jax.checkpoint's policy: with the kernel's output
    # kept, the backward runs the forward kernel once a call and not twice
    # (the backward kernel and the rotary kernel are jitted wrappers: the
    # jaxpr holds their calls by name and prints each kernel once)
    jaxpr = str(jax.make_jaxpr(jax.grad(lambda p: loss(remat, p)))(params))
    forward_calls = len(re.findall(r"pallas_call\[", jaxpr)) - len(
        re.findall(r"name=(flash_bwd_dq_dkv|rope)\n", jaxpr))
    assert forward_calls == (12 if not save else 6)
    # q and k are turned once forward and their gradients once backward, 12
    # each; with q and k kept as the kernel read them, turned already, the
    # backward does not turn them again
    rope_calls = len(re.findall(r"name=_rope_call\b", jaxpr))
    assert rope_calls == (24 if "flash_qkv" in save else 36)


def test_rope_scores_depend_on_the_offset_only():
    from horovod_tpu.models.transformer import apply_rope, rope_tables

    q, k = jax.random.normal(jax.random.PRNGKey(2), (2, 1, 1, 1, 16))
    def score(i, j):
        rot = lambda x, p: apply_rope(  # noqa: E731
            x, rope_tables(jnp.array([p]), 16, 1e4))
        return float(jnp.sum(rot(q, i) * rot(k, j)))

    assert score(7, 3) == pytest.approx(score(40, 36), rel=1e-4)
    assert score(7, 3) != pytest.approx(score(7, 4), rel=1e-3)
    # position 0 is the identity
    np.testing.assert_allclose(
        apply_rope(q, rope_tables(jnp.array([0]), 16, 1e4)), q)


def test_packed_rope_positions_restart_at_each_document():
    """A document gives the same logits alone in a row and packed behind
    another."""
    model = _looped(attn_impl="full")
    doc = _tokens(b=1, t=10, vocab=96, seed=4)
    other = _tokens(b=1, t=6, vocab=96, seed=5)
    params = jax.jit(model.init)(jax.random.PRNGKey(1), doc)
    alone, _, _ = jax.jit(model.apply)(
        params, doc, jnp.ones((1, 10), jnp.int32))
    row = jnp.concatenate([other, doc], axis=1)
    seg = jnp.asarray([[1] * 6 + [2] * 10])
    packed, _, _ = jax.jit(model.apply)(params, row, seg)
    np.testing.assert_allclose(packed[:, :, 6:], alone, atol=2e-5)


def test_looped_loss_is_finite_with_saturated_gates():
    from horovod_tpu.models.transformer import looped_token_cross_entropy

    logits = jax.random.normal(jax.random.PRNGKey(0), (3, 2, 5, 7))
    targets = jnp.zeros((2, 5), jnp.int32)
    for z in (-200.0, 200.0):
        exits = jnp.full((3, 2, 5), z)
        loss, grad = jax.value_and_grad(
            lambda e: looped_token_cross_entropy(logits, e, targets, 0.1)
        )(exits)
        assert np.isfinite(float(loss)) and np.isfinite(grad).all()


def test_param_shard_axes_know_the_separate_projections():
    from horovod_tpu.models.transformer import param_shard_axes

    model = _looped()
    params = model.init(jax.random.PRNGKey(1), _tokens(t=16, vocab=96))
    axes = param_shard_axes(params, model.cfg)["params"]["block_0"]
    for name in ("q", "k", "v", "proj"):
        assert axes["attn"][name]["Dense_0"]["kernel"] == "tp", name
    for name in ("wg", "wi", "wo"):
        assert axes["mlp"][name]["Dense_0"]["kernel"] == "tp", name
    assert axes["ln_attn_post"]["scale"] == ""


def test_unknown_settings_are_errors():
    toks = _tokens(t=16, vocab=96)
    for bad in (dict(norm="batchnorm"), dict(positions="alibi"),
                dict(mlp="relu")):
        with pytest.raises(ValueError, match="unknown"):
            _looped(**bad).init(jax.random.PRNGKey(0), toks)


# ------------------------------------------------ per-layer attention shapes
def _laguna_full_rule():
    from horovod_tpu.models.transformer import RopeRule

    return RopeRule(theta=500000.0, dim=64, factor=64.0,
                    original_max_len=4096, beta_fast=64.0, beta_slow=1.0,
                    attention_factor=1.4158883083359672)


def test_yarn_tables_against_hand_computed_values():
    """Laguna-XS.2's full-attention rule: 64 channels turn, theta 500 000,
    factor 64 over 4096 positions, beta_fast 64, beta_slow 1.  By hand:
    low = floor(64 ln(4096 / (64 2 pi)) / (2 ln 500000)) = floor(5.66) = 5,
    high = ceil(64 ln(4096 / (2 pi)) / (2 ln 500000)) = ceil(15.80) = 16;
    pairs up to 5 keep their frequency, pairs from 16 on have it divided
    by 64, pair 10 lies 5/11 of the way; cos and sin carry the factor."""
    import math

    from horovod_tpu.models.transformer import rope_tables, yarn_ramp

    rule = _laguna_full_rule()
    assert yarn_ramp(64, 500000.0, rule) == (5.0, 16.0)
    assert rule.attention_factor == pytest.approx(0.1 * math.log(64) + 1)
    pos = jnp.asarray([0, 1, 100, 8191])
    cos, sin = rope_tables(pos, 64, 500000.0, rule)
    assert cos.shape == sin.shape == (4, 32)
    plain = [500000.0 ** (-2 * j / 64) for j in range(32)]
    want = []
    for j, f in enumerate(plain):
        ramp = min(max((j - 5) / 11, 0.0), 1.0)
        want.append(f / 64 * ramp + f * (1 - ramp))
    assert want[5] == plain[5] and want[16] == plain[16] / 64
    assert want[10] == pytest.approx(
        plain[10] * (6 / 11 + 5 / 11 / 64), rel=1e-12)
    angles = np.asarray(pos, np.float64)[:, None] * np.asarray(want)
    np.testing.assert_allclose(
        cos, rule.attention_factor * np.cos(angles), atol=2e-3)
    np.testing.assert_allclose(  # float32 angles: 1e-3 at 8191 radians
        sin, rule.attention_factor * np.sin(angles), atol=2e-3)
    # position 0: cos is the factor itself, so a score's turning part
    # carries its square
    np.testing.assert_allclose(cos[0], 1.4158883, rtol=1e-6)
    # the factor left to its default is 0.1 ln(factor) + 1
    import dataclasses
    again = rope_tables(pos, 64, 500000.0, dataclasses.replace(
        rule, attention_factor=None))
    np.testing.assert_allclose(again[0], cos, rtol=1e-6)
    # and an unscaled rule is the plain table
    from horovod_tpu.models.transformer import RopeRule
    np.testing.assert_array_equal(
        rope_tables(pos, 128, 10000.0, RopeRule())[0],
        rope_tables(pos, 128, 10000.0)[0])


def test_partial_rotation_leaves_the_other_channels_alone():
    """Half of a head of 128 turns (pairs i, i + 32); channels 64-127 of
    every head pass bit for bit, and q.k of the turned part depends on the
    offset only."""
    from horovod_tpu.models.transformer import apply_rope, rope_tables

    x = jax.random.normal(jax.random.PRNGKey(0), (1, 24, 3, 128))
    tables = rope_tables(jnp.arange(24), 64, 500000.0, _laguna_full_rule())
    y = apply_rope(x, tables)
    np.testing.assert_array_equal(y[..., 64:], x[..., 64:])
    assert float(jnp.max(jnp.abs(y[:, 1:, :, :64] - x[:, 1:, :, :64]))) > 0.1
    same = jnp.broadcast_to(x[:, :1], x.shape)  # one vector everywhere
    t = apply_rope(same, tables)[0, :, 0, :64]
    scores = t @ t.T
    np.testing.assert_allclose(scores[3, 1], scores[12, 10], rtol=1e-4)
    np.testing.assert_allclose(scores[20, 5], scores[15, 0], rtol=1e-4)


def _mixed_config(**over):
    from horovod_tpu.models.transformer import RopeRule

    cfg = dict(
        vocab_size=64, num_layers=3, model_dim=32, num_heads=4,
        num_kv_heads=2, head_dim=16, ff_dim=64, max_len=64,
        dtype=jnp.float32, norm="rmsnorm", positions="rope", use_bias=False,
        fused_qkv=False, mlp="gated_silu", tie_head=False,
        layer_kinds=("full", "window", "window"), window=8,
        layer_heads=(4, 6, 6), attn_gate=True,
        rope_rules=(
            ("full", RopeRule(theta=100.0, dim=8, factor=8.0,
                              original_max_len=64, beta_fast=4.0)),
            ("window", RopeRule(theta=10000.0)),
        ))
    cfg.update(over)
    return TransformerConfig(**cfg)


def test_a_head_count_and_a_rotary_rule_per_layer():
    from horovod_tpu import metrics

    model = Transformer(_mixed_config())
    toks = _tokens(t=32, vocab=64)
    params = jax.jit(model.init)(jax.random.PRNGKey(1), toks)["params"]
    shapes = jax.tree.map(lambda a: a.shape, params)
    for i, heads in enumerate((4, 6, 6)):
        attn = shapes[f"block_{i}"]["attn"]
        assert attn["q"]["Dense_0"]["kernel"] == (32, heads * 16)
        assert attn["k"]["Dense_0"]["kernel"] == (32, 2 * 16)
        assert attn["v"]["Dense_0"]["kernel"] == (32, 2 * 16)
        assert attn["proj"]["Dense_0"]["kernel"] == (heads * 16, 32)
        assert attn["gate"]["kernel"] == (32, heads)
    logits, _ = jax.jit(model.apply)({"params": params}, toks)
    assert np.isfinite(np.asarray(logits)).all()
    for kind, (count, groups, tiles) in {
            "full": (1, 2, 1), "window": (2, 3, 1)}.items():
        labels = {"kind": kind}
        assert metrics.get_gauge("model.layer_kinds", labels) == count
        assert metrics.get_gauge("model.attn.kv_groups", labels) == groups
        assert metrics.get_gauge(
            "model.attn.tiles_per_head", labels) == tiles
    # the flash kernels and materialised scores agree, gradients too
    plain = Transformer(_mixed_config(attn_impl="full"))

    def loss(net):
        return lambda p: jnp.sum(
            net.apply({"params": p}, toks)[0] ** 2) / toks.size

    got, g_got = jax.jit(jax.value_and_grad(loss(model)))(params)
    want, g_want = jax.jit(jax.value_and_grad(loss(plain)))(params)
    assert float(got) == pytest.approx(float(want), rel=1e-5)
    for a, b in zip(jax.tree.leaves(g_got), jax.tree.leaves(g_want)):
        np.testing.assert_allclose(a, b, atol=2e-5, rtol=1e-3)
    # the window, the gate and the rules each move the output
    ungated = {name: dict(block, attn={
        k: v for k, v in block["attn"].items() if k != "gate"})
        if name.startswith("block_") else block
        for name, block in params.items()}
    for change, p in ((dict(window=32), params), (dict(rope_rules=()), params),
                      (dict(attn_gate=False), ungated)):
        moved = jax.jit(Transformer(_mixed_config(**change)).apply)(
            {"params": p}, toks)[0]
        assert float(jnp.max(jnp.abs(moved - logits))) > 1e-3, change


def test_param_shard_axes_follow_the_layers_heads():
    from horovod_tpu.models.transformer import param_shard_axes

    cfg = _mixed_config()
    params = Transformer(cfg).init(
        jax.random.PRNGKey(0), _tokens(t=16, vocab=64))
    axes = param_shard_axes(params, cfg)["params"]
    for i in range(3):
        attn = axes[f"block_{i}"]["attn"]
        for name in ("q", "k", "v", "proj"):
            assert attn[name]["Dense_0"]["kernel"] == cfg.tp_axis
        assert attn["gate"]["kernel"] == ""


def test_settings_the_grouped_layers_refuse():
    toks = _tokens(t=16, vocab=64)
    for change, match in ((dict(fused_qkv=True), "separate"),
                          (dict(num_kv_heads=3), "divide"),
                          (dict(layer_heads=(4, 6)), "layer_heads")):
        model = Transformer(_mixed_config(**change))
        with pytest.raises(ValueError, match=match):
            model.init(jax.random.PRNGKey(0), toks)
