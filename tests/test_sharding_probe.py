"""Which leaves are laid out over the data axis, decided without a cost that
grows with the program: ``_is_stacked``, ``hostlocal.is_global_array`` and
``optim.is_sharded_state_leaf`` answer a tracer by its type
(``collective._named_sharding``). A tracer's ``.sharding`` raises, and JAX
builds that error by walking every equation traced so far: probed once per
gradient leaf by the optimizer wrapper's wire-bytes gauge, the cost grew as
leaves x equations."""

import ast
import os

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from jax._src.interpreters import partial_eval as pe
from jax.sharding import NamedSharding, PartitionSpec as P

from horovod_tpu import optim, training
from horovod_tpu.models import TransformerTiny
from horovod_tpu.observability import metrics
from horovod_tpu.ops import collective, hostlocal

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _leaf(hvd, case):
    n = hvd.size()
    if case == "stacked":
        return jax.device_put(jnp.ones((n, 3)),
                              NamedSharding(hvd.mesh(), P("data")))
    if case == "replicated":
        return jax.device_put(jnp.ones((n, 3)), NamedSharding(hvd.mesh(), P()))
    if case == "one_device":
        return jax.device_put(jnp.ones((n, 3)), jax.devices()[0])
    return np.ones((n, 3), np.float32)


_PROBES = {
    "_is_stacked": lambda x: collective._is_stacked(x, "data"),
    "is_global_array": hostlocal.is_global_array,
    "is_sharded_state_leaf": lambda x: optim.is_sharded_state_leaf(x),
}


@pytest.mark.parametrize("case,answers", [
    # (_is_stacked, is_global_array, is_sharded_state_leaf)
    ("stacked", (True, True, True)),
    ("replicated", (False, True, False)),
    ("one_device", (False, False, False)),
    ("numpy", (False, False, False)),
    ("tracer", (False, False, False)),
])
@pytest.mark.parametrize("probe", sorted(_PROBES))
def test_probe_answers_each_kind_of_leaf(hvd, probe, case, answers):
    fn = _PROBES[probe]
    want = answers[["_is_stacked", "is_global_array",
                    "is_sharded_state_leaf"].index(probe)]
    x = _leaf(hvd, "stacked" if case == "tracer" else case)
    if case != "tracer":
        assert fn(x) is want
        return
    seen = []

    @jax.jit
    def f(v):
        seen.append(fn(v))
        return v + 1

    f(x)
    assert seen == [want]


def _traced_step(hvd):
    """``make_jit_train_step`` over a two-block LM and the optimizer
    wrapper, traced once; the tracers' error messages built meanwhile."""
    model = TransformerTiny(depth=2, vocab=64, max_len=32)
    tx = hvd.DistributedOptimizer(optax.adamw(1e-3))
    tokens = np.zeros((hvd.size(), 32), np.int32)
    params = jax.jit(model.init)(jax.random.PRNGKey(0), tokens[:1])["params"]
    step = training.make_jit_train_step(
        model, tx, loss_fn=training.token_xent, instrument=False)
    built = []
    origin_msg = pe.DynamicJaxprTracer._origin_msg

    def spy(tracer):
        built.append(tracer)
        return origin_msg(tracer)

    pe.DynamicJaxprTracer._origin_msg = spy
    try:
        step.trace(training.replicate(params), {},
                   training.replicate(tx.init(params)),
                   training.shard_batch(tokens), training.shard_batch(tokens))
    finally:
        pe.DynamicJaxprTracer._origin_msg = origin_msg
    return params, built


def test_jit_step_trace_builds_no_tracer_error(hvd):
    """No probe of a gradient leaf raises inside the trace: the wrapper's
    gauge priced every leaf through ``getattr(tracer, "sharding")``, each
    a walk of the whole trace so far."""
    params, built = _traced_step(hvd)
    assert len(jax.tree_util.tree_leaves(params)) > 10
    assert built == []


def test_jit_step_gauge_bills_each_traced_leaf_whole(hvd):
    """A traced leaf is never a stacked ``[N, ...]`` one: the ring model
    bills its whole shape, as it did while the probe raised."""
    params, _ = _traced_step(hvd)
    n = hvd.size()
    nbytes = sum(l.size * l.dtype.itemsize
                 for l in jax.tree_util.tree_leaves(params))
    assert metrics.value("grad_sync_bytes_per_step", mode="allreduce") == (
        2.0 * (n - 1) / n * nbytes)


def _sharding_probes(tree):
    """``(function, line)`` of each ``getattr(x, "sharding", …)`` and
    ``hasattr(x, "sharding")`` in a module's syntax tree."""
    found = []

    def visit(node, fn):
        for child in ast.iter_child_nodes(node):
            if isinstance(child, (ast.FunctionDef, ast.AsyncFunctionDef)):
                visit(child, child.name)
                continue
            if (isinstance(child, ast.Call)
                    and isinstance(child.func, ast.Name)
                    and child.func.id in ("getattr", "hasattr")
                    and len(child.args) >= 2
                    and isinstance(child.args[1], ast.Constant)
                    and child.args[1].value == "sharding"):
                found.append((fn, child.lineno))
            visit(child, fn)

    visit(tree, None)
    return found


def test_sharding_is_read_in_one_place():
    """Nothing under horovod_tpu/ probes ``.sharding`` by name but
    ``collective._named_sharding``, which answers a tracer first: a probe
    of a traced value costs a walk of the trace, so one that comes back
    grows the step's trace with the program."""
    probes = {}
    for dirpath, _dirnames, filenames in os.walk(
            os.path.join(_REPO, "horovod_tpu")):
        for name in filenames:
            if name.endswith(".py"):
                path = os.path.join(dirpath, name)
                with open(path) as f:
                    tree = ast.parse(f.read(), path)
                rel = os.path.relpath(path, _REPO)
                for fn, line in _sharding_probes(tree):
                    probes[f"{rel}:{line}"] = (rel, fn)
    assert sorted(probes.values()) == [
        (os.path.join("horovod_tpu", "ops", "collective.py"),
         "_named_sharding")], probes
