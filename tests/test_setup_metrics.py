"""Set-up on the program's own clock: the compile pipeline of a step
builder's step booked by name (``profiler.book_compiles`` / ``book_step``),
the recorded set-up spans ``hvd.init`` and ``hvd.broadcast_parameters``,
and the benchmark's five readers of them."""

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from benchmarks import common, scope_reduce
from horovod_tpu import profiler, training
from horovod_tpu.observability import metrics

STAGES = {"trace": "/jax/core/compile/jaxpr_trace_duration",
          "lower": "/jax/core/compile/jaxpr_to_mlir_module_duration",
          "compile": "/jax/core/compile/backend_compile_duration"}


class _Dense(nn.Module):
    width: int = 4

    @nn.compact
    def __call__(self, x, train=True):
        return nn.Dense(self.width)(x)


def _step(hvd, builder, width=4):
    """A freshly built step of ``builder`` with its arguments."""
    model = _Dense(width)
    params = jax.device_get(model.init(jax.random.PRNGKey(0),
                                       jnp.ones((1, 3)))["params"])
    tx = optax.sgd(0.1)
    if builder == "jit":
        tx = hvd.DistributedOptimizer(tx)
        step = training.make_jit_train_step(
            model, tx, loss_fn=training.softmax_xent)
    else:
        step = training.make_shardmap_train_step(
            model, tx, loss_fn=training.softmax_xent)
    n = hvd.size()
    args = (training.replicate(params), {},
            training.replicate(tx.init(params)),
            training.shard_batch(np.ones((2 * n, 3), np.float32)),
            training.shard_batch(np.zeros((2 * n,), np.int32)))
    return step, args


def _seconds(stage, fn):
    return metrics.value("compile_seconds", stage=stage, fn=fn)


class _Heard:
    """JAX's own compile events while it is on, by stage and function."""

    def __init__(self):
        self.events = []

    def __enter__(self):
        jax.monitoring.register_event_duration_secs_listener(self._on)
        return self

    def __exit__(self, *exc):
        jax.monitoring.unregister_event_duration_listener(self._on)

    def _on(self, event, duration, fun_name="", **_):
        self.events.append((event, fun_name, duration))

    def total(self, stage, name):
        return sum(d for e, f, d in self.events
                   if e == STAGES[stage] and f in (name, f"jit({name})"))


@pytest.mark.parametrize("builder,short", [("jit", "step"),
                                           ("shardmap", "shard_step")])
def test_builder_step_books_its_pipeline_under_its_name(hvd, builder, short):
    name = f"{training._SCOPES}_{short}"
    step, args = _step(hvd, builder)
    assert {s: _seconds(s, name) for s in STAGES} == dict.fromkeys(STAGES, 0)
    with _Heard() as heard:
        step(*args)
    for stage in STAGES:
        assert heard.total(stage, name) > 0
        assert _seconds(stage, name) == pytest.approx(
            heard.total(stage, name))
    # a plain jit is no builder's: it lands in ``other``, the step keeps
    # what it had
    kept = {s: _seconds(s, name) for s in STAGES}
    other = _seconds("compile", "other") or 0.0
    jax.jit(lambda x: x * 3.0 + 1.25)(jnp.ones(7))
    assert _seconds("compile", "other") > other
    assert {s: _seconds(s, name) for s in STAGES} == kept


def test_a_second_build_replaces_the_first_ones_seconds(hvd):
    name = f"{training._SCOPES}_step"
    step, args = _step(hvd, "jit")
    step(*args)
    first = {s: _seconds(s, name) for s in STAGES}
    assert all(v > 0 for v in first.values())
    step, args = _step(hvd, "jit", width=6)
    with _Heard() as heard:
        step(*args)
    for stage in STAGES:
        assert _seconds(stage, name) == pytest.approx(
            heard.total(stage, name))


def test_persistent_cache_hit_and_miss_are_counted(hvd, tmp_path):
    from jax.experimental.compilation_cache import compilation_cache as cc

    keys = ("jax_compilation_cache_dir",
            "jax_persistent_cache_min_compile_time_secs",
            "jax_persistent_cache_min_entry_size_bytes")
    kept = {k: getattr(jax.config, k) for k in keys}
    for k, v in zip(keys, (str(tmp_path), 0.0, -1)):
        jax.config.update(k, v)
    cc.reset_cache()

    def counts():
        return tuple(metrics.value(c, fn="other") or 0.0 for c in (
            "compile_cache_hits", "compile_cache_misses"))

    try:
        x = np.ones(13, np.float32)
        hits, misses = counts()
        # two traces of one program: the second finds the first's entry
        jax.jit(lambda x: jnp.sin(x) * 41.5)(x)
        assert counts() == (hits, misses + 1)
        jax.jit(lambda x: jnp.sin(x) * 41.5)(x)
        assert counts() == (hits + 1, misses + 1)
    finally:
        for k, v in kept.items():
            jax.config.update(k, v)
        cc.reset_cache()


def test_compile_listeners_register_once_a_process(hvd):
    from jax._src import monitoring  # the listener lists are not public

    def ours():
        return (monitoring.get_event_duration_listeners().count(
                    profiler._on_duration),
                monitoring.get_event_listeners().count(profiler._on_event))

    assert ours() == (1, 1)
    hvd.shutdown()
    hvd.init()
    profiler.book_compiles()
    assert ours() == (1, 1)


def test_setup_spans_record_and_reach_the_trace(hvd, tmp_path):
    for span in ("hvd.init", "hvd.broadcast_parameters"):
        metrics.gauge("span_seconds", span=span).set(-1.0)
    with profiler.timeline(str(tmp_path)):
        hvd.shutdown()
        hvd.init()
        params = hvd.broadcast_parameters({"w": jnp.arange(4.0)})
        jax.block_until_ready(params)
    np.testing.assert_array_equal(params["w"], np.arange(4.0))
    for span in ("hvd.init", "hvd.broadcast_parameters"):
        assert metrics.value("span_seconds", span=span) > 0
    names = [n for n, _, _ in scope_reduce.load_xplane(str(tmp_path))["host"]]
    assert names.count("hvd.init") == 1
    assert names.count("hvd.broadcast_parameters") == 1


def test_registry_outlives_shutdown(hvd):
    hvd.shutdown()
    hvd.init()
    jax.jit(lambda x: x - 0.5)(jnp.ones(5))
    kept = (metrics.value("span_seconds", span="hvd.init"),
            metrics.value("compile_seconds", stage="compile", fn="other"))
    assert all(v > 0 for v in kept)
    hvd.shutdown()
    assert (metrics.value("span_seconds", span="hvd.init"),
            metrics.value("compile_seconds", stage="compile",
                          fn="other")) == kept
    hvd.init()


#: each reader, the series it reads and what it must leave alone
_READERS = {
    "setup_step_trace_s.train": ("compile_seconds", {"stage": "trace"}),
    "setup_step_lower_s.train": ("compile_seconds", {"stage": "lower"}),
    "setup_step_compile_s.train": ("compile_seconds", {"stage": "compile"}),
    "setup_init_s.train": ("span_seconds", {"span": "hvd.init"}),
    "setup_broadcast_s.train": ("span_seconds",
                                {"span": "hvd.broadcast_parameters"}),
}


@pytest.mark.parametrize("reader", sorted(_READERS))
def test_setup_reader_reads_the_registry_or_nothing(monkeypatch, reader):
    registry = metrics.Registry()
    monkeypatch.setattr(metrics, "snapshot", registry.snapshot)
    monkeypatch.setattr(metrics, "value", registry.value)
    read = common.load_module("layer_metrics", reader).read
    assert read({}) is None
    family, labels = _READERS[reader]
    if family == "compile_seconds":
        # other functions and the step's other stages are not the step's
        registry.gauge(family, fn="other", **labels).set(100.0)
        for stage in {"trace", "lower", "compile"} - {labels["stage"]}:
            registry.gauge(family, stage=stage, fn="hvd1_step").set(50.0)
        assert read({}) is None
        registry.gauge(family, fn="hvd1_step", **labels).set(2.5)
    else:
        registry.gauge(family, span="hvd.other").set(100.0)
        assert read({}) is None
        registry.gauge(family, **labels).set(2.5)
    assert read({}) == 2.5
