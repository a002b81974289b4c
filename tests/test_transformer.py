"""Transformer LM + sequence-parallel training tests: single-chip forward,
DP training, DP x SP training with ring attention (loss decreases and
matches the single-mesh run), and tensor-parallel pjit sharding."""

import functools

import numpy as np
import pytest
import jax
import jax.numpy as jnp
import optax
from jax.sharding import NamedSharding, PartitionSpec as P

import horovod_tpu as hvd
from horovod_tpu.models import TransformerTiny, transformer_param_specs
from horovod_tpu.parallel import SEQUENCE_AXIS, build_mesh, ring_attention
from horovod_tpu.training import make_sp_train_step, replicate


@pytest.fixture()
def lm_data():
    rng = np.random.RandomState(0)
    tokens = rng.randint(0, 1024, (4, 64)).astype(np.int32)
    # next-token targets computed globally BEFORE sharding
    targets = np.roll(tokens, -1, axis=1)
    return jnp.asarray(tokens), jnp.asarray(targets)


def test_forward_shapes():
    model = TransformerTiny(dtype=jnp.float32)
    tokens = jnp.zeros((2, 32), jnp.int32)
    params = model.init(jax.random.PRNGKey(0), tokens)["params"]
    logits = model.apply({"params": params}, tokens)
    assert logits.shape == (2, 32, 1024)
    assert logits.dtype == jnp.float32


def test_causality():
    # changing a future token must not change past logits
    model = TransformerTiny(dtype=jnp.float32)
    rng = np.random.RandomState(1)
    t1 = jnp.asarray(rng.randint(0, 1024, (1, 16)).astype(np.int32))
    t2 = t1.at[0, 10].set((t1[0, 10] + 7) % 1024)
    params = model.init(jax.random.PRNGKey(0), t1)["params"]
    l1 = model.apply({"params": params}, t1)
    l2 = model.apply({"params": params}, t2)
    np.testing.assert_allclose(
        np.asarray(l1[0, :10]), np.asarray(l2[0, :10]), rtol=1e-5, atol=1e-5
    )
    assert not np.allclose(np.asarray(l1[0, 10:]), np.asarray(l2[0, 10:]))


def test_sp_train_step_loss_decreases(hvd, lm_data):
    hvd.shutdown()
    hvd.init(axes={"data": 2, SEQUENCE_AXIS: 4})
    tokens, targets = lm_data

    model = TransformerTiny(
        dtype=jnp.float32,
        attention_fn=functools.partial(
            ring_attention, axis_name=SEQUENCE_AXIS, block_k=8),
    )
    tx = optax.adam(1e-2)
    # init with the dense twin: attention_fn doesn't affect the param tree,
    # and ring attention needs the seq axis bound (shard_map) to trace
    params = TransformerTiny(dtype=jnp.float32).init(
        jax.random.PRNGKey(0), tokens[:1])["params"]
    params = replicate(params)
    opt_state = replicate(tx.init(params))

    mesh = hvd.mesh()
    sh = NamedSharding(mesh, P("data", SEQUENCE_AXIS))
    tokens = jax.device_put(tokens, sh)
    targets = jax.device_put(targets, sh)

    step = make_sp_train_step(model, tx, seq_axis=SEQUENCE_AXIS)
    losses = []
    for _ in range(8):
        params, opt_state, loss = step(params, opt_state, tokens, targets)
        losses.append(float(loss))
    assert np.isfinite(losses).all()
    assert losses[-1] < losses[0], losses


def test_sp_matches_dense_single_step(hvd, lm_data):
    # one SP step == one dense-attention step on the same data
    tokens, targets = lm_data

    hvd.shutdown()
    hvd.init(axes={"data": 1, SEQUENCE_AXIS: 8})
    model_sp = TransformerTiny(
        dtype=jnp.float32,
        attention_fn=functools.partial(
            ring_attention, axis_name=SEQUENCE_AXIS, block_k=8),
    )
    tx = optax.sgd(0.1)
    params = TransformerTiny(dtype=jnp.float32).init(
        jax.random.PRNGKey(0), tokens[:1])["params"]
    mesh = hvd.mesh()
    sh = NamedSharding(mesh, P("data", SEQUENCE_AXIS))
    # donate=False: the replicated params alias the originals (device_put
    # reuses the local shard), and the dense reference below still needs them
    step = make_sp_train_step(model_sp, tx, seq_axis=SEQUENCE_AXIS,
                              donate=False)
    p1, _, loss_sp = step(
        replicate(params), replicate(tx.init(params)),
        jax.device_put(tokens, sh), jax.device_put(targets, sh),
    )

    # dense single-device reference
    model_d = TransformerTiny(dtype=jnp.float32)

    def loss_fn(p):
        logits = model_d.apply({"params": p}, tokens)
        logp = jax.nn.log_softmax(logits)
        return -jnp.mean(jnp.take_along_axis(logp, targets[..., None], -1))

    loss_d, grads = jax.value_and_grad(loss_fn)(params)
    np.testing.assert_allclose(float(loss_sp), float(loss_d), rtol=1e-5)
    p2 = optax.apply_updates(params, jax.tree_util.tree_map(
        lambda g: -0.1 * g, grads))
    flat1 = jax.tree_util.tree_leaves(p1)
    flat2 = jax.tree_util.tree_leaves(p2)
    for a, b in zip(flat1, flat2):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=1e-4, atol=1e-5
        )


@pytest.mark.parametrize("kv_heads", [None, 2])
def test_tensor_parallel_pjit_sharding(hvd, kv_heads):
    # TP the XLA way: annotate param shardings over the model axis, let the
    # compiler insert the collectives; result must match replicated
    # execution. kv_heads=2 also exercises the GQA q_proj/kv_proj specs.
    hvd.shutdown()
    hvd.init(axes={"data": 2, "model": 4})
    mesh = hvd.mesh()

    model = TransformerTiny(dtype=jnp.float32, kv_heads=kv_heads)
    rng = np.random.RandomState(2)
    tokens = jnp.asarray(rng.randint(0, 1024, (4, 16)).astype(np.int32))
    params = model.init(jax.random.PRNGKey(0), tokens[:1])["params"]
    if kv_heads:
        specs_probe = transformer_param_specs(params, model_axis="model")
        assert specs_probe["block0"]["q_proj"]["kernel"] == P(None, "model")
        assert specs_probe["block0"]["kv_proj"]["kernel"] == P(None, "model")

    specs = transformer_param_specs(params, model_axis="model")
    sharded_params = jax.tree_util.tree_map(
        lambda p, s: jax.device_put(p, NamedSharding(mesh, s)), params, specs
    )
    tokens_sh = jax.device_put(tokens, NamedSharding(mesh, P("data")))

    fwd = jax.jit(lambda p, t: model.apply({"params": p}, t))
    out_tp = fwd(sharded_params, tokens_sh)
    out_ref = model.apply({"params": params}, tokens)
    np.testing.assert_allclose(
        np.asarray(out_tp), np.asarray(out_ref), rtol=2e-4, atol=2e-4
    )


def test_gqa_model_flash_matches_dense_attention():
    """kv_heads < heads: the GQA projections feed the attention stack; the
    flash and dense attention paths must agree on the same parameters, and
    training gradients must flow through the smaller kv projection."""
    import functools

    import optax

    from horovod_tpu.models import TransformerTiny
    from horovod_tpu.ops.flash_attention import flash_attention

    rng = np.random.RandomState(0)
    tokens = jnp.asarray(rng.randint(0, 1024, (2, 32)).astype(np.int32))

    dense_m = TransformerTiny(dtype=jnp.float32, kv_heads=2)
    flash_m = TransformerTiny(
        dtype=jnp.float32, kv_heads=2,
        attention_fn=functools.partial(
            flash_attention, use_pallas=False, block_k=8),
    )
    params = dense_m.init(jax.random.PRNGKey(0), tokens)["params"]
    # GQA projections exist and are smaller than the fused qkv would be
    blk = params["block0"]
    assert "q_proj" in blk and "kv_proj" in blk and "qkv" not in blk
    # kv projection sized 2 * kv_heads * head_dim (vs 2 * dim fused)
    head_dim = 64 // 4
    assert blk["kv_proj"]["kernel"].shape[1] == 2 * 2 * head_dim

    out_d = dense_m.apply({"params": params}, tokens)
    out_f = flash_m.apply({"params": params}, tokens)
    np.testing.assert_allclose(np.asarray(out_d), np.asarray(out_f),
                               rtol=2e-4, atol=2e-4)

    def loss(p):
        logits = flash_m.apply({"params": p}, tokens)
        return optax.softmax_cross_entropy_with_integer_labels(
            logits[:, :-1], tokens[:, 1:]
        ).mean()

    g = jax.grad(loss)(params)
    gnorm = float(
        sum((np.asarray(x) ** 2).sum()
            for x in jax.tree_util.tree_leaves(g))
    )
    assert np.isfinite(gnorm) and gnorm > 0


def test_rope_relative_shift_invariance():
    """RoPE's defining property: q.k dot products depend only on the
    position DIFFERENCE — shifting both positions by s leaves scores
    unchanged (what makes it safe across SP shard boundaries)."""
    from horovod_tpu.models.transformer import apply_rope

    rng = np.random.RandomState(0)
    q = jnp.asarray(rng.randn(1, 6, 2, 16).astype(np.float32))
    k = jnp.asarray(rng.randn(1, 6, 2, 16).astype(np.float32))
    pos = jnp.arange(6)[None, :]
    s1 = jnp.einsum("bqhd,bkhd->bhqk", apply_rope(q, pos),
                    apply_rope(k, pos))
    s2 = jnp.einsum("bqhd,bkhd->bhqk", apply_rope(q, pos + 137),
                    apply_rope(k, pos + 137))
    np.testing.assert_allclose(np.asarray(s1), np.asarray(s2),
                               rtol=1e-4, atol=1e-4)


def test_rope_model_no_pos_table_and_trains(hvd):
    model = TransformerTiny(dtype=jnp.float32, pos_embedding="rope")
    tokens = jnp.asarray(
        np.random.RandomState(0).randint(0, 1024, (2, 16)).astype(np.int32))
    params = model.init(jax.random.PRNGKey(0), tokens)["params"]
    assert "pos_embed" not in params  # rotary: no learned table
    logits = model.apply({"params": params}, tokens)
    assert logits.shape == (2, 16, 1024)
    assert np.isfinite(np.asarray(logits)).all()

    with pytest.raises(ValueError, match="learned.*rope"):
        TransformerTiny(dtype=jnp.float32, pos_embedding="alibi").init(
            jax.random.PRNGKey(0), tokens)


def test_rope_sp_matches_dense_single_step(hvd, lm_data):
    """RoPE under sequence parallelism: per-shard global position offsets
    must phase K identically to the dense single-device run."""
    tokens, targets = lm_data

    hvd.shutdown()
    hvd.init(axes={"data": 1, SEQUENCE_AXIS: 8})
    model_sp = TransformerTiny(
        dtype=jnp.float32, pos_embedding="rope",
        attention_fn=functools.partial(
            ring_attention, axis_name=SEQUENCE_AXIS, block_k=8),
    )
    tx = optax.sgd(0.1)
    params = TransformerTiny(dtype=jnp.float32, pos_embedding="rope").init(
        jax.random.PRNGKey(0), tokens[:1])["params"]
    mesh = hvd.mesh()
    sh = NamedSharding(mesh, P("data", SEQUENCE_AXIS))
    step = make_sp_train_step(model_sp, tx, seq_axis=SEQUENCE_AXIS,
                              donate=False)
    _, _, loss_sp = step(
        replicate(params), replicate(tx.init(params)),
        jax.device_put(tokens, sh), jax.device_put(targets, sh),
    )

    model_d = TransformerTiny(dtype=jnp.float32, pos_embedding="rope")

    def loss_fn(p):
        logits = model_d.apply({"params": p}, tokens)
        logp = jax.nn.log_softmax(logits)
        return -jnp.mean(jnp.take_along_axis(logp, targets[..., None], -1))

    loss_d = loss_fn(params)
    np.testing.assert_allclose(float(loss_sp), float(loss_d), rtol=1e-5)


@pytest.mark.parametrize("variant", ["learned", "rope", "gqa"])
def test_generate_kv_cache_matches_full_forward(variant):
    """Greedy decode through the kv cache must reproduce the no-cache
    oracle (full forward over the prefix at every step, argmax)."""
    from horovod_tpu.models import generate

    kw = dict(dtype=jnp.float32, max_len=64)
    if variant == "rope":
        kw["pos_embedding"] = "rope"
    if variant == "gqa":
        kw["kv_heads"] = 2
    model = TransformerTiny(**kw)
    rng = np.random.RandomState(3)
    prompt = jnp.asarray(rng.randint(0, 1024, (2, 5)).astype(np.int32))
    params = model.init(jax.random.PRNGKey(1), prompt)["params"]

    out = generate(model, params, prompt, max_new_tokens=6)
    assert out.shape == (2, 11)
    np.testing.assert_array_equal(np.asarray(out[:, :5]), np.asarray(prompt))

    # oracle: re-run the full prefix each step, take argmax of the last pos
    seq = np.asarray(prompt)
    for _ in range(6):
        logits = model.apply({"params": params}, jnp.asarray(seq))
        nxt = np.argmax(np.asarray(logits[:, -1]), axis=-1)
        seq = np.concatenate([seq, nxt[:, None].astype(np.int32)], axis=1)
    np.testing.assert_array_equal(np.asarray(out), seq)


def test_generate_sampling_and_validation():
    from horovod_tpu.models import generate

    model = TransformerTiny(dtype=jnp.float32, max_len=16)
    prompt = jnp.asarray(
        np.random.RandomState(0).randint(0, 1024, (1, 4)).astype(np.int32))
    params = model.init(jax.random.PRNGKey(0), prompt)["params"]

    out = generate(model, params, prompt, max_new_tokens=4,
                   temperature=1.0, rng=jax.random.PRNGKey(7))
    assert out.shape == (1, 8)
    assert int(out.min()) >= 0 and int(out.max()) < 1024

    with pytest.raises(ValueError, match="rng"):
        generate(model, params, prompt, max_new_tokens=2, temperature=0.5)
    with pytest.raises(ValueError, match="max_len"):
        generate(model, params, prompt, max_new_tokens=13)
    with pytest.raises(ValueError, match="max_new_tokens"):
        generate(model, params, prompt, max_new_tokens=0)


def test_tp_train_step_matches_replicated_and_keeps_layout(hvd):
    """TP TRAINING via pjit layout annotations: params sharded over the
    model axis train to the same result as replicated execution, and the
    Megatron-style layout survives donated steps (grads/moments/updates all
    stay sharded — per-chip param+optimizer HBM divided by tp)."""
    hvd.shutdown()
    hvd.init(axes={"data": 2, "model": 4})
    mesh = hvd.mesh()
    try:
        model = TransformerTiny(dtype=jnp.float32)
        rng = np.random.RandomState(4)
        tokens = jnp.asarray(rng.randint(0, 1024, (4, 16)).astype(np.int32))
        targets = jnp.asarray(np.roll(np.asarray(tokens), -1, 1))
        params = model.init(jax.random.PRNGKey(0), tokens[:1])["params"]

        from horovod_tpu.training import make_jit_train_step

        def lm_xent(logits, tgts):
            logp = jax.nn.log_softmax(logits.astype(jnp.float32))
            return -jnp.mean(
                jnp.take_along_axis(logp, tgts[..., None], axis=-1))

        tx = hvd.DistributedOptimizer(optax.adam(0.01))
        step_r = make_jit_train_step(model, tx, loss_fn=lm_xent,
                                     donate=False)
        step_t = make_jit_train_step(model, tx, loss_fn=lm_xent,
                                     donate=True)

        specs = transformer_param_specs(params, model_axis="model")
        p_t = jax.tree_util.tree_map(
            lambda p, s: jax.device_put(p, NamedSharding(mesh, s)),
            params, specs)
        opt_t = tx.init(p_t)  # moments inherit the TP layout from params
        p_r = replicate(params)
        opt_r = replicate(tx.init(params))
        tok_sh = jax.device_put(tokens, NamedSharding(mesh, P("data")))
        tgt_sh = jax.device_put(targets, NamedSharding(mesh, P("data")))

        def tp_paths(tree):
            return {
                jax.tree_util.keystr(path)
                for path, l in jax.tree_util.tree_flatten_with_path(tree)[0]
                if getattr(l.sharding, "spec", None)
                and any(e == "model" for e in l.sharding.spec)
            }

        before = tp_paths(p_t)
        assert before, "no param leaf carries the model axis"

        for _ in range(3):
            p_r, _, opt_r, l_r = step_r(p_r, {}, opt_r, tok_sh, tgt_sh)
            p_t, _, opt_t, l_t = step_t(p_t, {}, opt_t, tok_sh, tgt_sh)
            np.testing.assert_allclose(float(l_r), float(l_t), rtol=1e-4)
        # TP reduces in a different order; adam's rsqrt amplifies the fp32
        # noise — tolerance covers reduction order, not semantics
        for a, b in zip(jax.tree_util.tree_leaves(p_r),
                        jax.tree_util.tree_leaves(p_t)):
            np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), rtol=2e-3, atol=1e-3)
        # XLA may ADD model-axis layouts to small unannotated leaves (ln
        # scales); what must not happen is any original TP leaf losing it
        assert before <= tp_paths(p_t), "compiler dropped a TP layout"
        assert tp_paths(opt_t), "optimizer moments lost the TP layout"
    finally:
        hvd.shutdown()
        hvd.init()


def test_generate_ragged_prompts_match_per_row_oracle():
    """prompt_lens: each right-padded row decodes from its own length and
    must reproduce the single-row no-cache rollout exactly — pads never
    leak into attention."""
    from horovod_tpu.models import generate

    model = TransformerTiny(dtype=jnp.float32, max_len=64)
    rng = np.random.RandomState(9)
    lens = [3, 5, 2]
    t_max, new = 5, 4
    rows = [rng.randint(0, 1024, (l,)).astype(np.int32) for l in lens]
    prompt = np.full((3, t_max), 777, np.int32)  # junk padding
    for i, r in enumerate(rows):
        prompt[i, : len(r)] = r
    params = model.init(
        jax.random.PRNGKey(1), jnp.asarray(prompt[:1]))["params"]

    out = np.asarray(generate(
        model, params, jnp.asarray(prompt), max_new_tokens=new,
        prompt_lens=np.array(lens)))

    for i, r in enumerate(rows):
        seq = r[None, :]
        for _ in range(new):
            logits = model.apply({"params": params}, jnp.asarray(seq))
            nxt = np.argmax(np.asarray(logits[:, -1]), axis=-1)
            seq = np.concatenate(
                [seq, nxt[:, None].astype(np.int32)], axis=1)
        np.testing.assert_array_equal(
            out[i, : lens[i] + new], seq[0],
            err_msg=f"row {i} (len {lens[i]})")

    with pytest.raises(ValueError, match="prompt_lens"):
        generate(model, params, jnp.asarray(prompt), max_new_tokens=2,
                 prompt_lens=np.array([3, 5]))


def test_generate_prompt_lens_range_validated():
    from horovod_tpu.models import generate

    model = TransformerTiny(dtype=jnp.float32, max_len=32)
    prompt = jnp.asarray(
        np.random.RandomState(0).randint(0, 1024, (2, 4)).astype(np.int32))
    params = model.init(jax.random.PRNGKey(0), prompt)["params"]
    for bad in ([0, 4], [2, 6]):
        with pytest.raises(ValueError, match=r"\[1, 4\]"):
            generate(model, params, prompt, max_new_tokens=2,
                     prompt_lens=np.array(bad))


def _pp_dense_parity(S, interleaved_v, *, vocab, depth, seed):
    """Shared harness: PP-train one step of a real TransformerLM and assert
    loss + every updated parameter equals the dense single-device step."""
    import horovod_tpu as hvd_mod
    from horovod_tpu.models import TransformerLM
    from horovod_tpu.training import (
        make_transformer_pp_train_step, split_transformer_for_pp, token_xent,
    )

    hvd_mod.shutdown()
    hvd_mod.init(devices=jax.devices()[:S], axes={"pipe": S})
    try:
        model = TransformerLM(vocab=vocab, dim=32, depth=depth, heads=4,
                              max_len=64, dtype=jnp.float32)
        rng = np.random.RandomState(seed)
        M, mb, T = 4, 2, 12
        tokens = rng.randint(0, vocab, (M * mb, T)).astype(np.int32)
        targets = np.roll(tokens, -1, axis=1)
        params = model.init(
            jax.random.PRNGKey(seed), jnp.asarray(tokens[:1]))["params"]

        lr = 0.1
        tx = optax.sgd(lr)
        pp = split_transformer_for_pp(
            model, params, S, interleaved_v=interleaved_v)
        init_stages = (jax.vmap(jax.vmap(tx.init)) if interleaved_v > 1
                       else jax.vmap(tx.init))
        opt_state = {
            "embed": tx.init(pp["embed"]),
            "stages": init_stages(pp["stages"]),
            "head": tx.init(pp["head"]),
        }
        from jax.sharding import NamedSharding as NS

        mesh = hvd_mod.mesh()
        pp["stages"] = jax.tree_util.tree_map(
            lambda p: jax.device_put(p, NS(mesh, P("pipe"))), pp["stages"])
        opt_state["stages"] = jax.tree_util.tree_map(
            lambda s: jax.device_put(s, NS(mesh, P("pipe"))),
            opt_state["stages"])

        step = make_transformer_pp_train_step(
            model, tx, interleaved_v=interleaved_v, donate=False)
        new_pp, _, loss_pp = step(
            pp, opt_state,
            jnp.asarray(tokens).reshape(M, mb, T),
            jnp.asarray(targets).reshape(M, mb, T))

        def dense_loss(p):
            logits = model.apply({"params": p}, jnp.asarray(tokens))
            return token_xent(logits, jnp.asarray(targets))

        loss_d, grads = jax.value_and_grad(dense_loss)(params)
        np.testing.assert_allclose(float(loss_pp), float(loss_d), rtol=1e-5)
        dense_new = optax.apply_updates(
            params, jax.tree_util.tree_map(lambda g: -lr * g, grads))

        def assert_part(got, want, label):
            for path, a in jax.tree_util.tree_flatten_with_path(got)[0]:
                b = want
                for kk in path:
                    b = b[kk.key]
                np.testing.assert_allclose(
                    np.asarray(a), np.asarray(b), rtol=1e-4, atol=1e-5,
                    err_msg=f"{label} {jax.tree_util.keystr(path)}")

        assert_part(new_pp["embed"]["tok_embed"],
                    dense_new["tok_embed"], "tok_embed")
        assert_part(new_pp["embed"]["pos_embed"],
                    dense_new["pos_embed"], "pos_embed")
        assert_part(new_pp["head"]["ln_f"], dense_new["ln_f"], "ln_f")
        assert_part(new_pp["head"]["lm_head"], dense_new["lm_head"],
                    "lm_head")
        n_total = S * interleaved_v
        for k in range(n_total):
            if interleaved_v > 1:
                got = jax.tree_util.tree_map(
                    lambda p: p[k % S, k // S], new_pp["stages"])["b0"]
            else:
                got = jax.tree_util.tree_map(
                    lambda p: p[k], new_pp["stages"])["b0"]
            assert_part(got, dense_new[f"block{k}"], f"block{k}")
    finally:
        hvd_mod.shutdown()
        hvd_mod.init()


def test_transformer_pp_train_step_matches_dense():
    """PP training of the REAL TransformerLM (embed + blocks + head all
    trained): loss and one-step parameter updates must match the dense
    single-device step — pins the per-part gradient bookkeeping (stages /S,
    embed psum over the pipe, head replicated)."""
    _pp_dense_parity(4, 1, vocab=256, depth=4, seed=11)


def test_transformer_pp_interleaved_matches_dense():
    """Interleaved (circular) schedule: S=2 devices x v=2 wrap levels over
    4 blocks — same dense-oracle equality as the GPipe path."""
    _pp_dense_parity(2, 2, vocab=128, depth=4, seed=13)


# ------------------------------------------ token_xent and the logits' dtype


def _log_softmax_xent(logits, targets):
    """The loss as it was before token_xent kept its own residuals:
    autodiff through a float32 log_softmax."""
    logp = jax.nn.log_softmax(logits.astype(jnp.float32))
    return -jnp.mean(jnp.take_along_axis(logp, targets[..., None], axis=-1))


def _xent_case(shape, dtype):
    """Logits of ``shape`` with one row whose largest logit is 3e4, and
    targets that name that logit once."""
    rng = np.random.RandomState(sum(shape))
    x = (3 * rng.randn(*shape)).astype(np.float32)
    x[..., 0, 5] = 3e4
    t = rng.randint(0, shape[-1], shape[:-1]).astype(np.int32)
    t[..., -1] = 5
    return jnp.asarray(x, dtype), jnp.asarray(t)


def _check_xent_value_and_grad(shape, dtype):
    from horovod_tpu.training import token_xent

    x, t = _xent_case(shape, dtype)
    loss, grad = jax.jit(jax.value_and_grad(token_xent))(x, t)
    want, want_grad = jax.jit(jax.value_and_grad(_log_softmax_xent))(x, t)
    assert np.isfinite(float(loss))
    np.testing.assert_allclose(float(loss), float(want), rtol=1e-6)
    assert grad.dtype == x.dtype and grad.shape == x.shape
    # both round one float32 dlogits to the logits' dtype: an ulp of it
    np.testing.assert_allclose(
        np.asarray(grad, np.float32), np.asarray(want_grad, np.float32),
        rtol=2 ** -7 if dtype == jnp.bfloat16 else 1e-5, atol=1e-8)
    # what the backward is handed: the logits as they came, a float32
    # log-sum-exp a token, the targets. Nothing else as wide as the
    # vocabulary
    saved = jax.tree_util.tree_leaves(jax.vjp(token_xent, x, t)[1])
    assert sorted((a.shape, str(a.dtype)) for a in saved) == sorted([
        (x.shape, str(x.dtype)), (t.shape, "float32"), (t.shape, "int32")])


def _check_xent_under_builder(builder, dtype):
    """One step through a step builder: the loss and every updated leaf
    equal the log_softmax form's. (In bfloat16 the two round the same
    float32 dlogits, an ulp of float32 apart, to bfloat16: a few land on
    the other side, and the bfloat16 backward carries that on.)"""
    from horovod_tpu import training

    model = TransformerTiny(vocab=1031, depth=1, max_len=16, dtype=dtype)
    rng = np.random.RandomState(7)
    tokens = rng.randint(0, 1031, (hvd.size(), 16)).astype(np.int32)
    targets = np.roll(tokens, -1, axis=1)
    params = model.init(jax.random.PRNGKey(0), tokens[:1])["params"]
    tx = (hvd.DistributedOptimizer(optax.sgd(0.1))
          if builder == "make_jit_train_step" else optax.sgd(0.1))
    out = {}
    for loss_fn in (training.token_xent, _log_softmax_xent):
        step = getattr(training, builder)(
            model, tx, loss_fn=loss_fn, instrument=False, donate=False)
        new, _, _, loss = step(
            replicate(params), {}, replicate(tx.init(params)),
            training.shard_batch(tokens), training.shard_batch(targets))
        out[loss_fn] = (jax.device_get(new), float(loss))
    (got, loss), (want, want_loss) = out.values()
    np.testing.assert_allclose(loss, want_loss, rtol=1e-6)
    for (path, a), b in zip(jax.tree_util.tree_flatten_with_path(got)[0],
                            jax.tree_util.tree_leaves(want)):
        np.testing.assert_allclose(
            a, b, err_msg=jax.tree_util.keystr(path),
            **(dict(rtol=1e-5, atol=1e-7) if dtype == jnp.float32
               else dict(rtol=2 ** -5, atol=4e-6)))


def _check_training_call_returns_compute_dtype():
    tokens = jnp.zeros((2, 8), jnp.int32)
    for dtype in (jnp.bfloat16, jnp.float32):
        model = TransformerTiny(depth=1, dtype=dtype)
        params = model.init(jax.random.PRNGKey(0), tokens)["params"]
        for kw in ({}, {"train": False}):
            assert model.apply({"params": params}, tokens, **kw).dtype == dtype


def _check_decode_logits_are_the_heads_upcast():
    """A kv-cache call (generate(), the serving engine's applies) returns
    float32, bit for bit the upcast of what the bfloat16 head computed:
    the samplers and the engine's numpy side see the values they saw when
    every call upcast."""
    import dataclasses

    model = TransformerTiny(depth=1, max_len=16)
    rng = np.random.RandomState(5)
    tokens = jnp.asarray(rng.randint(0, 1024, (2, 8)).astype(np.int32))
    params = model.init(jax.random.PRNGKey(0), tokens)["params"]
    dec = dataclasses.replace(model, decode=True, cache_len=16)
    pos = jnp.broadcast_to(jnp.arange(8, dtype=jnp.int32), (2, 8))
    cache = dec.init(jax.random.PRNGKey(0), tokens, positions=pos)["cache"]
    logits, state = dec.apply(
        {"params": params, "cache": cache}, tokens, positions=pos,
        mutable=["cache", "intermediates"],
        capture_intermediates=lambda m, _: m.name == "lm_head")
    (head,) = state["intermediates"]["lm_head"]["__call__"]
    assert head.dtype == jnp.bfloat16 and logits.dtype == jnp.float32
    np.testing.assert_array_equal(
        np.asarray(logits), np.asarray(head.astype(jnp.float32)))


_XENT_CASES = {
    **{f"value_and_grad-{'x'.join(map(str, shape))}-{dtype}": functools.partial(
        _check_xent_value_and_grad, shape, getattr(jnp, dtype))
       for shape in ((7, 1031), (2, 5, 1031), (3, 4, 256))
       for dtype in ("bfloat16", "float32")},
    **{f"{builder}-{dtype}": functools.partial(
        _check_xent_under_builder, builder, getattr(jnp, dtype))
       for builder in ("make_jit_train_step", "make_shardmap_train_step")
       for dtype in ("bfloat16", "float32")},
    "training_call_dtype": _check_training_call_returns_compute_dtype,
    "decode_logits": _check_decode_logits_are_the_heads_upcast,
}


@pytest.mark.parametrize("case", sorted(_XENT_CASES))
def test_token_xent_and_the_logits_dtype(hvd, case):
    """``token_xent`` equals the float32 log_softmax form in value and
    gradient, for bfloat16 and float32 logits, ``[T, V]`` and ``[B, T, V]``,
    a vocabulary off the 128-lane tile and a row at 3e4, alone under jit and
    inside both step builders; the training call hands it the head's own
    dtype, a kv-cache call still returns float32."""
    _XENT_CASES[case]()
