"""Model + training-step tests (reference analog: examples used as smoke
tests in CI, ``.buildkite/gen-pipeline.sh:145-192``)."""

import numpy as np
import pytest
import jax
import jax.numpy as jnp
import optax


@pytest.fixture()
def mnist_setup(hvd):
    from horovod_tpu.models import MnistCNN
    from horovod_tpu.training import init_model, replicate

    model = MnistCNN()
    params, batch_stats = init_model(
        model, jax.random.PRNGKey(0), jnp.zeros((1, 28, 28, 1))
    )
    return model, replicate(params), batch_stats


def _batch(hvd, n_per_rank=2):
    from horovod_tpu.training import shard_batch

    n = hvd.size() * n_per_rank
    rng = np.random.RandomState(0)
    x = shard_batch(rng.rand(n, 28, 28, 1).astype(np.float32))
    y = shard_batch(rng.randint(0, 10, n))
    return x, y


def test_resnet_tiny_forward(hvd):
    from horovod_tpu.models import ResNet18

    model = ResNet18(num_classes=10, num_filters=8, dtype=jnp.float32)
    x = jnp.zeros((2, 32, 32, 3))
    variables = model.init(jax.random.PRNGKey(0), x)
    logits = model.apply(variables, x, train=False)
    assert logits.shape == (2, 10)
    assert logits.dtype == jnp.float32


def test_jit_and_shardmap_steps_agree(hvd, mnist_setup):
    """The pjit-style and explicit-collective steps must produce the same
    parameters from the same state (the two execution modes are semantically
    one framework)."""
    from horovod_tpu.training import (
        make_jit_train_step,
        make_shardmap_train_step,
        replicate,
    )

    model, params, batch_stats = mnist_setup
    x, y = _batch(hvd)
    tx_jit = __import__("horovod_tpu").DistributedOptimizer(optax.sgd(0.1))
    tx_sm = optax.sgd(0.1)

    s1 = make_jit_train_step(model, tx_jit, donate=False)
    s2 = make_shardmap_train_step(model, tx_sm, donate=False)

    opt_state = replicate(tx_sm.init(params))
    p1, _, _, l1 = s1(params, batch_stats, opt_state, x, y)
    p2, _, _, l2 = s2(params, batch_stats, opt_state, x, y)

    np.testing.assert_allclose(float(l1), float(l2), rtol=1e-5)
    for k in ("Dense_0", "Conv_0"):
        np.testing.assert_allclose(
            np.asarray(p1[k]["kernel"]),
            np.asarray(p2[k]["kernel"]),
            rtol=1e-4,
            atol=1e-6,
        )


def test_training_reduces_loss(hvd, mnist_setup):
    from horovod_tpu.training import make_jit_train_step, replicate

    model, params, batch_stats = mnist_setup
    x, y = _batch(hvd, n_per_rank=4)
    tx = __import__("horovod_tpu").DistributedOptimizer(optax.sgd(0.05))
    step = make_jit_train_step(model, tx, donate=False)
    opt_state = replicate(tx.init(params))
    losses = []
    for _ in range(10):
        params, batch_stats, opt_state, loss = step(
            params, batch_stats, opt_state, x, y
        )
        losses.append(float(loss))
    assert losses[-1] < losses[0]


def _sharded_paths(tree, ax):
    """Leaf paths whose dim-0 sharding uses axis `ax`."""
    return {
        jax.tree_util.keystr(path)
        for path, l in jax.tree_util.tree_flatten_with_path(tree)[0]
        if getattr(l.sharding, "spec", None) and l.sharding.spec[0] == ax
    }


def test_zero_sharded_opt_state_matches_replicated(hvd):
    """ZeRO-1 layout: optimizer state sharded over the data axis must train
    bit-for-bit like the replicated layout (sharding is layout, not math)
    and the moment leaves must STAY sharded across donated steps (the HBM
    win persists, it isn't re-replicated by the compiler). MLP rather than
    the CNN: the layout logic is identical and the two extra jit compiles
    stay cheap."""
    import jax

    from horovod_tpu.models import MLP
    from horovod_tpu.training import (
        init_model, make_jit_train_step, replicate, shard_batch,
        zero_shard_opt_state,
    )

    model = MLP(features=(64, 10))
    rng = np.random.RandomState(0)
    params, batch_stats = init_model(
        model, jax.random.PRNGKey(0), jnp.zeros((1, 16))
    )
    params = replicate(params)
    n = hvd.size() * 2
    x = shard_batch(rng.rand(n, 16).astype(np.float32))
    y = shard_batch(rng.randint(0, 10, n))
    tx = __import__("horovod_tpu").DistributedOptimizer(
        optax.adam(0.01)  # adam: real moment tensors to shard
    )
    step_r = make_jit_train_step(model, tx, donate=False)
    step_z = make_jit_train_step(model, tx, donate=True)

    opt_r = replicate(tx.init(params))
    opt_z = zero_shard_opt_state(tx.init(params))

    # at least one big leaf actually sharded over 'data'
    ax = hvd.data_axis()
    sharded_paths = lambda tree: _sharded_paths(tree, ax)

    before = sharded_paths(opt_z)
    assert before, "no optimizer-state leaf got the data-axis layout"

    pr, pz = params, params
    br, bz = batch_stats, batch_stats
    for _ in range(3):
        pr, br, opt_r, lr = step_r(pr, br, opt_r, x, y)
        pz, bz, opt_z, lz = step_z(pz, bz, opt_z, x, y)
        # sharded layouts reduce in a different order -> fp32-level deltas
        np.testing.assert_allclose(float(lr), float(lz), rtol=1e-5)
    for a, b in zip(
        jax.tree_util.tree_leaves(pr), jax.tree_util.tree_leaves(pz)
    ):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=1e-3, atol=1e-5
        )
    # the SAME leaves stay sharded through the donated steps (a count-only
    # check would miss the compiler re-replicating one leaf while another
    # happened to pick up the axis)
    assert sharded_paths(opt_z) == before, "compiler changed the layout"


def test_zero_shard_preserves_model_axis_layout():
    """On a dp x tp mesh, moments of TP-sharded params already carry a
    model-axis layout; the ZeRO placement must MERGE the data axis in, not
    clobber the spec (re-replicating the model dim would inflate HBM)."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    import horovod_tpu as hvd_mod
    from horovod_tpu.training import zero_shard_opt_state

    hvd_mod.shutdown()
    hvd_mod.init(axes={"data": 2, "model": 4})
    try:
        mesh = hvd_mod.mesh()
        mu_tp = jax.device_put(  # moment of a TP-sharded weight
            jnp.zeros((8, 8)), NamedSharding(mesh, P(None, "model"))
        )
        mu_plain = jnp.zeros((8, 4))
        mu_odd = jnp.zeros((3,))  # indivisible dim 0
        out = zero_shard_opt_state(
            {"tp": mu_tp, "plain": mu_plain, "odd": mu_odd}
        )
        assert out["tp"].sharding.spec == P("data", "model")
        spec = out["plain"].sharding.spec
        assert spec[0] == "data" and all(e is None for e in spec[1:])
        assert all(e is None for e in tuple(out["odd"].sharding.spec))
        # a leaf whose dim 0 already uses the data axis is left untouched
        pre = jax.device_put(
            jnp.zeros((8, 8)), NamedSharding(mesh, P("data", None))
        )
        out2 = zero_shard_opt_state({"pre": pre})
        assert out2["pre"].sharding.spec == P("data", None)
    finally:
        hvd_mod.shutdown()


@pytest.mark.slow  # ~16 s big-model forward; the same builder/step machinery runs tier-1 on resnet_tiny
def test_vgg16_forward_and_train_step(hvd):
    """VGG-16 (the reference's allreduce-bandwidth stress workload,
    ``docs/benchmarks.rst:10-14``) is stateless by default (no BN): forward
    shape/dtype, empty batch_stats, and one DP train step."""
    from horovod_tpu.models import VGG16
    from horovod_tpu.training import (
        init_model, make_jit_train_step, replicate, shard_batch,
    )

    model = VGG16(num_classes=10, hidden_dim=32, dtype=jnp.float32)
    x = jnp.zeros((2, 32, 32, 3))
    params, batch_stats = init_model(model, jax.random.PRNGKey(0), x)
    assert batch_stats == {}
    logits = model.apply({"params": params}, x, train=False)
    assert logits.shape == (2, 10) and logits.dtype == jnp.float32

    tx = hvd.DistributedOptimizer(optax.sgd(0.01))
    step = make_jit_train_step(model, tx, donate=False)
    n = hvd.size() * 2
    rng = np.random.RandomState(0)
    images = shard_batch(rng.rand(n, 32, 32, 3).astype(np.float32))
    labels = shard_batch(rng.randint(0, 10, n))
    params = replicate(params)
    opt_state = replicate(tx.init(params))
    _, _, _, loss = step(params, batch_stats, opt_state, images, labels)
    assert np.isfinite(float(loss))


def test_vgg_bn_variant_has_batch_stats(hvd):
    from horovod_tpu.models import VGG
    from horovod_tpu.training import init_model

    model = VGG(stages=((4,), (8,)), num_classes=10, hidden_dim=16,
                dtype=jnp.float32, use_bn=True)
    x = jnp.zeros((2, 16, 16, 3))
    _, batch_stats = init_model(model, jax.random.PRNGKey(0), x)
    assert batch_stats  # BN running stats present


@pytest.mark.slow  # ~26 s big-model forward; stem/shape coverage duplicated by resnet_tiny tier-1
def test_inception_v3_forward(hvd):
    """Inception V3 (reference scaling workload #2). 128x128 input — the
    network is fully convolutional up to the head, so any size surviving
    the stem works; the canonical 299 is run by no test.
    Forward-only: the train-step plumbing for the new families is already
    proven by the VGG test, and V3's backward compile alone costs ~40 s of
    suite time for no additional coverage."""
    from horovod_tpu.models import InceptionV3
    from horovod_tpu.training import init_model

    model = InceptionV3(num_classes=10, dtype=jnp.float32)
    x = jnp.zeros((1, 128, 128, 3))
    params, batch_stats = init_model(model, jax.random.PRNGKey(0), x)
    assert batch_stats  # BN everywhere
    logits = model.apply(
        {"params": params, "batch_stats": batch_stats}, x, train=False
    )
    assert logits.shape == (1, 10) and logits.dtype == jnp.float32


def test_graft_entry_dryrun(hvd):
    """The driver's multichip dryrun must work on the 8-device CPU mesh."""
    import sys, pathlib

    sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[1]))
    import __graft_entry__ as g

    g.dryrun_multichip(8)


def test_fsdp_sharded_params_match_replicated(hvd):
    """FSDP/ZeRO-3 layout: params sharded over the data axis on dim 0 must
    train to the same result as the replicated layout, and the param leaves
    must STAY sharded across donated steps (per-chip param HBM win
    persists). XLA inserts the gather/reduce-scatter pattern itself."""
    import jax

    from horovod_tpu.models import MLP
    from horovod_tpu.training import (
        fsdp_shard_params, init_model, make_jit_train_step, replicate,
        shard_batch, zero_shard_opt_state,
    )

    model = MLP(features=(64, 10))
    rng = np.random.RandomState(0)
    params, batch_stats = init_model(
        model, jax.random.PRNGKey(0), jnp.zeros((1, 16))
    )
    n = hvd.size() * 2
    x = shard_batch(rng.rand(n, 16).astype(np.float32))
    y = shard_batch(rng.randint(0, 10, n))
    tx = __import__("horovod_tpu").DistributedOptimizer(optax.adam(0.01))
    step_r = make_jit_train_step(model, tx, donate=False)
    step_f = make_jit_train_step(model, tx, donate=True)

    p_r = replicate(params)
    opt_r = replicate(tx.init(params))
    p_f = fsdp_shard_params(params)
    opt_f = zero_shard_opt_state(tx.init(p_f))

    ax = hvd.data_axis()
    sharded_paths = lambda tree: _sharded_paths(tree, ax)

    before = sharded_paths(p_f)
    assert before, "no param leaf got the data-axis layout"

    br, bf = batch_stats, batch_stats
    for _ in range(3):
        p_r, br, opt_r, lr = step_r(p_r, br, opt_r, x, y)
        p_f, bf, opt_f, lf = step_f(p_f, bf, opt_f, x, y)
        np.testing.assert_allclose(float(lr), float(lf), rtol=1e-5)
    for a, b in zip(
        jax.tree_util.tree_leaves(p_r), jax.tree_util.tree_leaves(p_f)
    ):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=1e-3, atol=1e-5
        )
    assert sharded_paths(p_f) == before, "compiler changed the param layout"
