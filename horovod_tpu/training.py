"""Training-step builders: the framework's equivalent of the reference's
benchmark/example training loops (``examples/tensorflow2_synthetic_benchmark.py:45-70``:
loss under ``DistributedGradientTape``, allreduced grads, apply).

Two step styles, same user-visible semantics:

- :func:`make_jit_train_step` — *pjit style*: one global jitted step, batch
  sharded over the data axis, parameters replicated. XLA's sharding propagation
  inserts the gradient ``psum`` and fuses/overlaps it with the backward pass —
  this subsumes the reference's tensor-fusion + cycle pipeline
  (``controller.cc:640-761``, ``operations.cc:550-600``) in the compiler.
- :func:`make_shardmap_train_step` — *explicit-collective style*: per-shard
  compute inside ``shard_map`` with ``hvd.allreduce`` on each gradient, the
  literal Horovod programming model. BatchNorm running stats are rank-averaged
  to keep them replicated (the reference leaves them per-worker and broadcasts
  at checkpoint time; averaging is equivalent in steady state).
"""

from __future__ import annotations

import functools
import time
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np
import optax
from jax.sharding import NamedSharding, PartitionSpec as P

from horovod_tpu import basics
from horovod_tpu import profiler as _profiler
from horovod_tpu.analysis import sanitizer as _sanitizer
from horovod_tpu.observability import flight as _flight
from horovod_tpu.observability import metrics as _metrics
from horovod_tpu.observability import regression as _regression
from horovod_tpu.observability import slo as _slo
from horovod_tpu.observability import straggler as _straggler
from horovod_tpu.ops.collective import (
    Average, allreduce, _mesh_axis_size, _named_sharding, _smap, _sync_scope,
)
from horovod_tpu.ops import overlap as _overlap
from horovod_tpu.compression import Compression
from horovod_tpu.resilience import health as _health
from horovod_tpu.resilience import numerics as _numerics


#: Prefix of the step builders' module names (``jit_hvd1_step``). JAX leaves
#: metadata out of the persistent compile cache's key, so an edit that only
#: moves or renames an ``hvd.*`` scope hits the cache and gets an executable
#: with the OLD names back (a ResNet step compiled before the scopes existed
#: was served, nameless, to the tree that has them; my chip run, PR 26). The
#: module name IS in the key and does not depend on paths or line numbers:
#: bump the digit with any change to the scopes these builders write.
_SCOPES = "hvd1"


def _named(step):
    """``step`` under its module name, ``<_SCOPES>_<name>``, its compile
    pipeline booked under that name (``profiler.book_step``)."""
    step.__name__ = f"{_SCOPES}_{step.__name__}"
    _profiler.book_step(step.__name__)
    return step


def softmax_xent(logits, labels):
    """Cross entropy with integer labels."""
    logp = jax.nn.log_softmax(logits)
    return -jnp.mean(jnp.take_along_axis(logp, labels[:, None], axis=1))


@jax.custom_vjp
def token_xent(logits, targets):
    """Per-token cross entropy for causal LMs: logits ``[..., T, V]`` of any
    float dtype (``TransformerLM``'s training call hands over the head's
    own, in the model's compute dtype), int targets ``[..., T]``.

    The arithmetic is float32 whatever comes in. For the backward it keeps
    the logits as they arrived and one float32 log-sum-exp a token —
    nothing else vocabulary-wide, where autodiff through ``log_softmax``
    keeps a float32 copy of all the log-probabilities (at GPT-2's
    ``[8, 1024, 50257]``: 0.82 GB for 2.47). The gradient comes back in
    the logits' dtype.
    """
    return _token_xent_fwd(logits, targets)[0]


def _token_xent_fwd(logits, targets):
    x = logits.astype(jnp.float32)
    top = jnp.max(x, axis=-1)
    log_sum = jnp.log(jnp.sum(jnp.exp(x - top[..., None]), axis=-1))
    picked = jnp.take_along_axis(
        logits, targets[..., None], axis=-1)[..., 0].astype(jnp.float32)
    lse = top + log_sum
    # (picked - top) first: both are logits, so the loss does not round at
    # the logits' magnitude
    return jnp.mean(log_sum - (picked - top)), (logits, lse, targets)


def _token_xent_bwd(residuals, g):
    logits, lse, targets = residuals
    x = logits.astype(jnp.float32)
    onehot = jax.lax.broadcasted_iota(
        targets.dtype, x.shape, x.ndim - 1) == targets[..., None]
    dlogits = (jnp.exp(x - lse[..., None]) - onehot) * (g / lse.size)
    return dlogits.astype(logits.dtype), None


token_xent.defvjp(_token_xent_fwd, _token_xent_bwd)


def init_model(model, rng, sample_input, train: bool = True):
    """Initialize (params, batch_stats) replicated over the mesh."""
    variables = model.init(rng, sample_input, train=train)
    params = variables.get("params", variables)
    batch_stats = variables.get("batch_stats", {})
    return params, batch_stats


class InstrumentedStep:
    """Wrap a step callable so every call feeds the metrics registry:
    ``train_steps``/``train_examples`` counters, a ``train_step_seconds``
    histogram of the call-to-call interval (in a donation-throttled async
    pipeline the inter-dispatch interval converges to the true device step
    time — the same steady-state argument ``profiler.timed_steps`` makes),
    and a ``train_examples_per_sec`` gauge.

    Attribute access (``.lower``, AOT compilation, etc.) delegates to the
    wrapped callable, so the wrapper is transparent to callers that
    lower/compile the step themselves.
    """

    def __init__(self, fn, *, batch_arg: Optional[int] = None,
                 examples_per_step: Optional[int] = None,
                 name: str = "train"):
        self._fn = fn
        self._batch_arg = batch_arg
        self._examples = examples_per_step
        self._name = name
        self._last_t: Optional[float] = None
        self._step_idx = 0

    def __call__(self, *args, **kwargs):
        # host spans on the profiler's clock (recorded only while a
        # profiler session is on): hvd.step - hvd.step/dispatch is what
        # the hooks and the metrics block below cost per step
        with jax.profiler.StepTraceAnnotation(
                "hvd.step", step_num=self._step_idx):
            return self._call(*args, **kwargs)

    def _call(self, *args, **kwargs):
        # open this step's correlation scope BEFORE dispatch: eager
        # collectives issued by/around the step share (step, gen, seq)
        # keys across ranks (fleet trace correlation + straggler
        # attribution — ISSUE 7). The schedule sanitizer shares the
        # boundary: the finished step's op ring is published and
        # cross-checked here (HOROVOD_SANITIZE=1).
        _straggler.set_step(self._step_idx)
        _sanitizer.set_step(self._step_idx)
        # the flight ring records the boundary too (and counts it as
        # forward progress for the hang watchdog)
        _flight.step_boundary(self._step_idx)
        # the numerics fingerprint plane shares the sanitizer's boundary:
        # the finished step's per-dtype gradient fingerprint is published
        # and rank-0 cross-checked here (no-op unless enabled)
        _numerics.set_step(self._step_idx)
        self._step_idx += 1
        with _profiler.annotate("hvd.step/dispatch"):
            out = self._fn(*args, **kwargs)
        # standalone fingerprint path: without the elastic wrapper nobody
        # calls note_step, and the record published at the next boundary
        # would be a default — read the verdict from the returned state
        # (one sync per step; gated on the opt-in plane)
        _numerics.maybe_note_output(self._step_idx - 1, out)
        # a dispatched step is forward progress: walk the health machine
        # back toward HEALTHY (cheap: one lock, no metrics involved)
        _health.beat()
        if not _metrics.enabled():
            return out
        now = time.perf_counter()
        name = self._name
        examples = self._examples
        if examples is None and self._batch_arg is not None:
            try:
                examples = int(args[self._batch_arg].shape[0])
            except (IndexError, AttributeError, TypeError):
                examples = None
        _metrics.counter(
            f"{name}_steps", help="train steps dispatched"
        ).inc()
        if examples:
            _metrics.counter(
                f"{name}_examples", help="examples trained on"
            ).inc(examples)
        if self._last_t is not None:
            dt = now - self._last_t
            if dt > 0:
                _metrics.histogram(
                    f"{name}_step_seconds",
                    help="inter-dispatch step interval",
                ).observe(dt)
                if examples:
                    _metrics.gauge(
                        f"{name}_examples_per_sec",
                        help="throughput over the last step interval",
                    ).set(examples / dt)
                # SLO plane: the step interval is the step_time series
                # (counted in steps, not wall clock), and the
                # gauge-sourced objectives (subscriber staleness, input
                # data-wait) sample here so THEY are counted in steps too
                _slo.observe("step_time", dt)
                _slo.sample_gauges()
                # regression sentinel: step time / throughput / data
                # wait against their warmup-guarded rolling baselines
                _regression.track(f"{name}_step_seconds", dt)
                if examples:
                    _regression.track(
                        f"{name}_examples_per_sec", examples / dt)
                wait = _metrics.value("data_wait_seconds_recent")
                if isinstance(wait, (int, float)):
                    _regression.track("data_wait_seconds", float(wait))
        self._last_t = now
        return out

    def __getattr__(self, item):
        return getattr(self._fn, item)


def instrument_step(fn, *, batch_arg: Optional[int] = None,
                    examples_per_step: Optional[int] = None,
                    name: str = "train"):
    """Public spelling of the step wrapper: a caller that compiles its own
    step wraps it here; the ``make_*_train_step`` builders apply it
    automatically (``instrument=False`` opts out)."""
    return InstrumentedStep(
        fn, batch_arg=batch_arg, examples_per_step=examples_per_step,
        name=name,
    )


def make_loader_step(step_fn: Callable, loader) -> Callable:
    """Adapt a batch-consuming step to the ``(state, i) -> state`` shape
    :func:`horovod_tpu.resilience.run` / ``elastic.run`` drive, drawing
    each step's batch from a :class:`~horovod_tpu.data.ResumableLoader`::

        stepped = make_loader_step(
            lambda state, batch, i: train(state, *batch), loader)
        final = elastic.run(lambda world: stepped, state, num_steps=N)

    The loader's **cursor** — not the loop index — decides what each step
    consumes: a checkpoint resume, an elastic rollback, or a numerics
    replay moves the cursor (with the replay salt folded in), so the
    adapted step re-draws exactly the batches the recovery semantics
    promise (``docs/data.md``). ``step_fn(state, batch, i)`` receives the
    placed batch (a tuple for multi-array sources)."""

    def stepped(state, i):
        batch = loader.next_batch()
        return step_fn(state, batch, i)

    return stepped


def _attention_per_batch_shard(model):
    """``model`` with its ``attention_fn`` (if it has one) run per batch
    shard: a ``shard_map`` over the data axis, every other mesh axis left
    to the partitioner. :func:`make_jit_train_step` owns the layout —
    activations sharded ``P(data)`` on the batch — and a ``pallas_call``
    (``flash_attention`` on TPU) is opaque to the SPMD partitioner, which
    would otherwise all-gather q/k/v and run the whole batch on every
    chip. For an attention made of plain HLO the wrap states the layout
    the partitioner picks anyway. The mesh is read at trace time, so a
    step retraced after an elastic resize follows the live world."""
    fn = getattr(model, "attention_fn", None)
    if fn is None:
        return model

    def attention(q, k, v, **kw):
        mesh, ax = basics.mesh(), basics.data_axis()
        if _mesh_axis_size(mesh, ax) == 1:
            return fn(q, k, v, **kw)
        return jax.shard_map(
            functools.partial(fn, **kw), mesh=mesh, in_specs=(P(ax),) * 3,
            out_specs=P(ax), check_vma=False,
            axis_names=set(ax) if isinstance(ax, tuple) else {ax},
        )(q, k, v)

    return model.clone(attention_fn=attention)


def make_jit_train_step(
    model,
    tx: optax.GradientTransformation,
    *,
    loss_fn: Callable = softmax_xent,
    donate: bool = True,
    instrument: bool = True,
    overlap: Optional[bool] = None,
    bucket_bytes: Optional[int] = None,
):
    """Global-jit DP train step. Inputs: (params, batch_stats, opt_state,
    images, labels) with images/labels sharded P(data) and the rest replicated.
    Returns (params, batch_stats, opt_state, loss). A model with an
    ``attention_fn`` has it run per batch shard
    (:func:`_attention_per_batch_shard`), so a Pallas attention kernel sees
    its chip's rows and not the gathered batch.

    A numerics-guarded ``tx`` (``DistributedOptimizer(numerics_guard=True)``)
    is detected automatically: the loss is multiplied by the guard's
    dynamic loss scale before the backward pass (unscaled again for the
    return value) and threaded into the update, so a non-finite loss also
    marks the step BAD.

    ``overlap=True`` (env ``HOROVOD_OVERLAP=1``): in the pjit style XLA's
    sharding propagation already emits the gradient ``psum``s where the
    backward produces each cotangent — the overlap opportunity exists in
    the dataflow, and what is missing on TPU is only the compiler
    features that exploit it. The kwarg therefore arms the
    async-collective/latency-hiding flags
    (:func:`horovod_tpu.tuning.apply_xla_flags`; a warning fires if the
    backend initialized first) and leaves the step itself unchanged. For
    explicit per-bucket collectives use
    :func:`make_shardmap_train_step`."""
    if _overlap.resolve_bucket_bytes(overlap, bucket_bytes):
        from horovod_tpu import tuning as _tuning

        _tuning.apply_xla_flags()
    guarded = _numerics.is_guarded(tx)
    model = _attention_per_batch_shard(model)

    def step(params, batch_stats, opt_state, images, labels):
        scale = _numerics.current_scale(opt_state) if guarded else None

        @jax.named_scope("hvd.forward")
        def loss_and_logits(p):
            variables = {"params": p}
            if batch_stats:
                variables["batch_stats"] = batch_stats
                logits, updates = model.apply(
                    variables, images, train=True, mutable=["batch_stats"]
                )
                loss_val = loss_fn(logits, labels)
            else:
                logits = model.apply(variables, images, train=True)
                updates = {"batch_stats": {}}
                loss_val = loss_fn(logits, labels)
            if scale is not None:
                # scale INSIDE the differentiated fn so the backward pass
                # runs at the scaled magnitude (the mixed-precision
                # underflow defense); the guard divides the grads back
                loss_val = loss_val * scale
            return loss_val, updates["batch_stats"]

        (loss, new_stats), grads = jax.value_and_grad(loss_and_logits, has_aux=True)(
            params
        )
        if scale is not None:
            loss = loss / scale
        # no exchange to wrap here: the wrapper's (DistributedOptimizer)
        # sits under hvd.optimizer/hvd.sync, the partitioner's all-reduces
        # are sync by their HLO kind (profiler.scope_of)
        with jax.named_scope("hvd.optimizer"):
            if guarded:
                updates, opt_state = tx.update(
                    grads, opt_state, params, loss=loss)
            else:
                updates, opt_state = tx.update(grads, opt_state, params)
            params = optax.apply_updates(params, updates)
        return params, new_stats, opt_state, loss

    donate_argnums = (0, 1, 2) if donate else ()
    jitted = jax.jit(_named(step), donate_argnums=donate_argnums)
    # args: (params, batch_stats, opt_state, images, labels) -> the global
    # batch is images.shape[0]
    return instrument_step(jitted, batch_arg=3) if instrument else jitted


def make_shardmap_train_step(
    model,
    tx: optax.GradientTransformation,
    *,
    loss_fn: Callable = softmax_xent,
    axis: Optional[str] = None,
    compression=Compression.none,
    reduce_op=Average,
    shard_optimizer: bool = False,
    shard_params: bool = False,
    donate: bool = True,
    instrument: bool = True,
    overlap: Optional[bool] = None,
    bucket_bytes: Optional[int] = None,
):
    """Explicit Horovod-style step: shard_map over the data axis, per-shard
    grads allreduced with ``hvd.allreduce`` (the in-jit path -> lax.psum).

    Pass a *plain* optax optimizer: this step already performs the gradient
    allreduce, so wrapping `tx` in DistributedOptimizer would reduce twice
    (numerically idempotent for Average, but doubled collective traffic).

    ``shard_optimizer=True`` selects the ZeRO-1 step: `tx` must then be a
    ``DistributedOptimizer(..., shard_optimizer=True)`` — the step skips
    its own gradient allreduce (the optimizer reduce-scatters the flat
    gradient buffers, updates this rank's moment shard, and all-gathers the
    update shards), and the optimizer state rides the mesh sharded
    ``P(data)`` on its leading rank axis, so per-chip moment HBM drops by
    the axis size. Build ``opt_state = tx.init(params)`` with that same
    wrapped optimizer; ``compression``/``reduce_op`` here are then unused
    (configure them on the DistributedOptimizer), and
    ``backward_passes_per_step`` must stay 1 (MultiSteps state has no rank
    axis to shard). Both modes report ``grad_sync_bytes_per_step``.

    A numerics-guarded ``tx`` (``DistributedOptimizer(numerics_guard=
    True)`` — works in both modes, wrapping either the plain optax
    optimizer or the ZeRO-1 DistributedOptimizer) is detected
    automatically: the loss is scaled by the guard's dynamic loss scale
    before the backward pass and threaded into the update, and the
    sharded state spec becomes the guard's pytree prefix (scalars
    replicated, inner state ``P(data)``).

    ``shard_params=True`` selects the ZeRO-3 step: ``tx`` must be a
    ``DistributedOptimizer(shard_params=True)`` and ``params`` the packed
    :class:`~horovod_tpu.optim.FsdpParams` shards from
    :func:`horovod_tpu.optim.fsdp_pack_params` (spec'd ``P(data)`` as a
    pytree prefix, like the opt state). The step gathers the full tree
    on use (:func:`~horovod_tpu.optim.fsdp_gather_params` — one
    all-gather per pack group, issue-order pinned; ``HOROVOD_FSDP_WIRE=
    int8`` quantizes the wire), runs the forward under ``jax.checkpoint``
    so the gathered tree is DISCARDED after the forward and re-gathered
    in the backward, and differentiates straight through the gather: its
    transpose reduce-scatters the gradient shards, so the optimizer sees
    exactly ZeRO-1's reduced buffers and the fp32 trajectory is
    bit-identical to ``shard_optimizer=True``. Per-chip param AND
    optimizer HBM drop by the axis size; wire cost is
    ``(N-1)/N·(2·P_gather + P_grad)`` vs ZeRO-1's ``(N-1)/N·2·P``
    (``grad_sync_bytes_per_step{mode=zero3}`` /
    ``param_gather_bytes_per_step{mode=zero3}``). The numerics guard
    does not compose with this mode yet.

    ``overlap=True`` (env ``HOROVOD_OVERLAP=1``; ``bucket_bytes=``
    overrides ``HOROVOD_BUCKET_BYTES``, default 64 MB): the gradient
    exchange becomes **bucketed** — ~bucket-sized flat collectives in
    reverse backprop-emission order, each depending only on its own
    leaves' cotangents, so XLA can launch them while the remaining
    backward still runs (:mod:`horovod_tpu.ops.overlap`). In the
    ``shard_optimizer=True`` mode the exchange belongs to the
    DistributedOptimizer — build it with ``overlap=True`` there (the
    same ``HOROVOD_OVERLAP=1`` env flips both layers together); this
    kwarg then changes nothing here.
    """
    mesh = basics.mesh()
    ax = axis or basics.data_axis()
    ov_bytes = _overlap.resolve_bucket_bytes(overlap, bucket_bytes)
    if getattr(compression, "factorized", False) and not shard_optimizer:
        raise ValueError(
            "PowerSGD compression is stateful (warm-started Q + error "
            "feedback); wrap the optimizer in DistributedOptimizer("
            "compression=Compression.powersgd(r), error_feedback=True) and "
            "pass shard_optimizer=True (or use it without this builder) "
            "instead of passing it as the step's compression="
        )
    guarded = _numerics.is_guarded(tx)

    if shard_params:
        if guarded:
            raise ValueError(
                "numerics_guard does not compose with shard_params=True "
                "yet (see DistributedOptimizer); train ZeRO-3 unguarded "
                "or guard the ZeRO-1 step"
            )
        from horovod_tpu import optim as _optim

        def fsdp_step(params, batch_stats, opt_state, images, labels):
            @jax.named_scope("hvd.forward")
            def loss_and_stats(fp):
                p = _optim.fsdp_gather_params(fp)
                variables = {"params": p}
                if batch_stats:
                    variables["batch_stats"] = batch_stats
                    logits, updates = model.apply(
                        variables, images, train=True,
                        mutable=["batch_stats"]
                    )
                    stats = updates["batch_stats"]
                else:
                    logits = model.apply(variables, images, train=True)
                    stats = {}
                return loss_fn(logits, labels), stats

            # jax.checkpoint: the gathered tree is DISCARDED after the
            # forward and re-gathered in the backward — param liveness
            # stays one bucket deep instead of the whole model, the
            # ZeRO-3 memory deal (the gather wire runs twice for it)
            (loss, new_stats), gshards = jax.value_and_grad(
                jax.checkpoint(loss_and_stats), has_aux=True)(params)
            with _sync_scope("stats"):
                new_stats = jax.tree_util.tree_map(
                    lambda s: allreduce(s, Average, axis=ax), new_stats
                )
            with _sync_scope("loss"):
                loss = allreduce(loss, Average, axis=ax)
            with jax.named_scope("hvd.optimizer"):
                updates, new_opt_state = tx.update(
                    gshards, opt_state, params)
                params = optax.apply_updates(params, updates)
            return params, new_stats, new_opt_state, loss

        rep = P()
        sharded = P(ax)
        smapped = _smap(
            _named(fsdp_step),
            mesh,
            (P(ax), rep, P(ax), sharded, sharded),
            (P(ax), rep, P(ax), rep),
        )
        donate_argnums = (0, 1, 2) if donate else ()
        jitted = jax.jit(smapped, donate_argnums=donate_argnums)
        return instrument_step(jitted, batch_arg=3) if instrument else jitted

    def shard_step(params, batch_stats, opt_state, images, labels):
        scale = _numerics.current_scale(opt_state) if guarded else None

        @jax.named_scope("hvd.forward")
        def loss_and_stats(p):
            variables = {"params": p}
            if batch_stats:
                variables["batch_stats"] = batch_stats
                logits, updates = model.apply(
                    variables, images, train=True, mutable=["batch_stats"]
                )
                stats = updates["batch_stats"]
            else:
                logits = model.apply(variables, images, train=True)
                stats = {}
            loss_val = loss_fn(logits, labels)
            if scale is not None:
                loss_val = loss_val * scale
            return loss_val, stats

        (loss, new_stats), grads = jax.value_and_grad(loss_and_stats, has_aux=True)(
            params
        )
        if scale is not None:
            loss = loss / scale
        if not shard_optimizer:
            if ov_bytes:
                # bucketed backward-pass sync: K reverse-emission flat
                # collectives, overlappable with the remaining backward
                # (bucketed_allreduce records the wire-byte gauges)
                grads, _ = _overlap.bucketed_allreduce(
                    grads, reduce_op, axis=ax, compression=compression,
                    bucket_bytes=ov_bytes,
                )
            else:
                # the Horovod step: combine gradients across ranks
                # (Average, Sum, or Adasum — reference op= on
                # DistributedOptimizer)
                from horovod_tpu.optim import (
                    _record_sync_bytes, _tree_sync_wire_bytes,
                )
                from horovod_tpu.ops.collective import _axis_size

                _record_sync_bytes(
                    "allreduce", _axis_size(ax),
                    _tree_sync_wire_bytes(grads, compression),
                )
                with _sync_scope("grads"):
                    grads = jax.tree_util.tree_map(
                        lambda g: allreduce(
                            g, reduce_op, axis=ax, compression=compression),
                        grads,
                    )
        # keep BN running stats replicated
        with _sync_scope("stats"):
            new_stats = jax.tree_util.tree_map(
                lambda s: allreduce(s, Average, axis=ax), new_stats
            )
        with _sync_scope("loss"):
            loss = allreduce(loss, Average, axis=ax)
        with jax.named_scope("hvd.optimizer"):
            if guarded:
                # the guard consumes the (already rank-averaged) loss so a
                # non-finite loss marks the step BAD alongside the grads
                updates, new_opt_state = tx.update(
                    grads, opt_state, params, loss=loss)
            else:
                updates, new_opt_state = tx.update(
                    grads, opt_state, params)
            params = optax.apply_updates(params, updates)
        return params, new_stats, new_opt_state, loss

    rep = P()
    sharded = P(ax)
    opt_spec = P(ax) if shard_optimizer else rep
    if guarded and shard_optimizer:
        # pytree-prefix spec: the guard's EWMA/loss-scale scalars are
        # replicated; only the wrapped [N, shard] inner state rides P(ax)
        opt_spec = _numerics.shard_state_spec(P(ax))
    smapped = _smap(
        _named(shard_step),
        mesh,
        (rep, rep, opt_spec, sharded, sharded),
        (rep, rep, opt_spec, rep),
    )
    donate_argnums = (0, 1, 2) if donate else ()
    jitted = jax.jit(smapped, donate_argnums=donate_argnums)
    return instrument_step(jitted, batch_arg=3) if instrument else jitted


def make_pp_train_step(
    stage_fn: Callable,
    tx: optax.GradientTransformation,
    *,
    loss_fn: Optional[Callable] = None,
    interleaved: bool = False,
    axis: Optional[str] = None,
    donate: bool = True,
):
    """Pipeline-parallel train step over the ``pipe`` axis (TPU-native
    extension — the reference is DP-only, SURVEY.md §2.7).

    ``stage_fn(stage_params, activation) -> activation`` is one stage's
    forward. Stage parameters arrive stacked on a leading device axis
    (``make_stage_params`` for GPipe: ``[S, ...]``;
    ``make_interleaved_stage_params`` + ``interleaved=True`` for the
    circular schedule: ``[S, v, ...]``) and sharded ``P("pipe")``;
    ``opt_state`` likewise (build it with ``jax.vmap(tx.init)(stacked)``
    so every leaf gains the stage axis). ``x_micro``/``y_micro`` are
    ``[n_micro, mb, ...]`` replicated.

    The backward runs through the schedule's scan (mirrored order); the
    per-device gradient of the psum-replicated loss over-counts by the
    pipe size (psum's transpose is psum — every device differentiates its
    own copy of the same scalar), normalized here before the update.
    Returns jitted ``(stacked_params, opt_state, x_micro, y_micro) ->
    (stacked_params, opt_state, loss)``.
    """
    from jax import lax

    from horovod_tpu.parallel.pipeline import (
        pipeline_apply, pipeline_apply_interleaved,
    )
    from horovod_tpu.parallel.mesh import PIPELINE_AXIS

    if loss_fn is None:
        loss_fn = lambda out, y: jnp.mean((out - y) ** 2)  # noqa: E731
    mesh = basics.mesh()
    ax = axis or PIPELINE_AXIS
    apply_fn = pipeline_apply_interleaved if interleaved else pipeline_apply

    def pp_step(stacked, opt_state, xm, ym):
        local = jax.tree_util.tree_map(lambda p: p[0], stacked)
        local_opt = jax.tree_util.tree_map(lambda s: s[0], opt_state)

        def local_loss(lp):
            out = apply_fn(stage_fn, lp, xm, axis_name=ax)
            out = lax.psum(out, ax)  # valid on the last stage only
            return loss_fn(out, ym)

        loss, grads = jax.value_and_grad(local_loss)(local)
        k = lax.psum(1, ax)
        grads = jax.tree_util.tree_map(lambda g: g / k, grads)
        updates, local_opt = tx.update(grads, local_opt, local)
        local = optax.apply_updates(local, updates)
        return (
            jax.tree_util.tree_map(lambda p: p[None], local),
            jax.tree_util.tree_map(lambda s: s[None], local_opt),
            loss,
        )

    smapped = _smap(
        pp_step,
        mesh,
        (P(ax), P(ax), P(), P()),
        (P(ax), P(ax), P()),
    )
    donate_argnums = (0, 1) if donate else ()
    return jax.jit(smapped, donate_argnums=donate_argnums)


def make_sp_train_step(
    model,
    tx: optax.GradientTransformation,
    *,
    data_axis: Optional[str] = None,
    seq_axis: str = "seq",
    donate: bool = True,
):
    """Sequence-parallel causal-LM train step: shard_map over (data, seq),
    tokens/targets sharded ``P(data, seq)``, params replicated, the model's
    attention running as a ring over the ``seq`` axis
    (:func:`horovod_tpu.parallel.ring_attention`).

    Build the model with
    ``attention_fn=functools.partial(ring_attention, axis_name=seq_axis)`` —
    this step supplies per-shard ``positions`` so embeddings line up, computes
    the next-token loss on aligned ``(tokens, targets)`` shards, and combines
    gradients over *both* axes (data psum = the Horovod exchange; seq psum =
    the sequence-parallel gradient fold). No reference counterpart: Horovod
    0.19.2 has no sequence axis (SURVEY.md §5.7).
    """
    mesh = basics.mesh()
    dax = data_axis or basics.data_axis()

    def shard_step(params, opt_state, tokens, targets):
        t_local = tokens.shape[1]
        seq_idx = jax.lax.axis_index(seq_axis)
        positions = seq_idx * t_local + jnp.arange(t_local)[None, :]

        def loss_fn(p):
            logits = model.apply({"params": p}, tokens, positions=positions)
            return token_xent(logits, targets)

        loss, grads = jax.value_and_grad(loss_fn)(params)
        grads = jax.tree_util.tree_map(
            lambda g: allreduce(allreduce(g, Average, axis=dax),
                                Average, axis=seq_axis),
            grads,
        )
        loss = allreduce(allreduce(loss, Average, axis=dax),
                         Average, axis=seq_axis)
        updates, new_opt_state = tx.update(grads, opt_state, params)
        params = optax.apply_updates(params, updates)
        return params, new_opt_state, loss

    rep = P()
    sharded = P(dax, seq_axis)
    smapped = _smap(
        shard_step,
        mesh,
        (rep, rep, sharded, sharded),
        (rep, rep, rep),
    )
    donate_argnums = (0, 1) if donate else ()
    return jax.jit(smapped, donate_argnums=donate_argnums)


def shard_batch(batch, *, axis: Optional[str] = None):
    """Place a host array with leading batch dim onto the mesh, sharded over
    the data axis (the launcher-side analog of Horovod's per-rank data
    sharding in every example script)."""
    mesh = basics.mesh()
    ax = axis or basics.data_axis()
    with _profiler.annotate("hvd.shard_batch"):
        return jax.device_put(batch, NamedSharding(mesh, P(ax)))


def replicate(tree):
    """Replicate a pytree over the mesh (params/opt state)."""
    mesh = basics.mesh()
    sharding = NamedSharding(mesh, P())
    return jax.tree_util.tree_map(lambda x: jax.device_put(x, sharding), tree)


def host_snapshot(tree):
    """Host-offloaded copy of a state pytree: every array leaf (device or
    host) becomes an owned ``np.ndarray``; other leaves pass through.

    This is the elastic layer's rollback snapshot
    (:mod:`horovod_tpu.resilience.elastic`) and the weight publisher's
    consolidation step (:mod:`horovod_tpu.serving` — the payload must not
    be invalidated mid-upload by the next donated step): the copy blocks on
    each leaf (``np.array`` of a ``jax.Array`` synchronizes), survives a
    mesh teardown — the arrays no longer reference any device buffer — and,
    being an owned copy, cannot be invalidated by a later donated step
    consuming the live state. Cost: one D2H transfer of the state per
    committed step; size it with ``snapshot_every``."""

    def one(x):
        if isinstance(x, (jax.Array, np.ndarray, np.generic)):
            return np.array(x)
        return x

    return jax.tree_util.tree_map(one, tree)


def zero_shard_opt_state(opt_state, *, axis: Optional[str] = None):
    """ZeRO-1 style optimizer-state sharding (no reference analog — upstream
    is pure DP with fully replicated optimizer state on every worker).

    Places every optimizer-state leaf sharded over the data axis on dim 0
    (when divisible; small/indivisible leaves stay replicated). On TPU,
    sharding is a *layout annotation*: the update math is unchanged and XLA
    inserts the reduce-scatter / all-gather pattern around the sharded
    moment update automatically, so per-chip optimizer-state HBM drops by
    ~axis-size x — the ZeRO-1 memory result without a new algorithm. Use on
    the output of ``tx.init`` before entering the step loop::

        opt_state = zero_shard_opt_state(tx.init(params))

    Works with :func:`make_jit_train_step` (donation keeps the layout
    steady across steps).
    """
    return _shard_dim0_tree(opt_state, axis)


def fsdp_shard_params(params, *, axis: Optional[str] = None):
    """FSDP / ZeRO-3 style parameter sharding (no reference analog).

    Same dim-0-over-data-axis placement as :func:`zero_shard_opt_state`,
    applied to the *parameters*: per-chip param HBM drops ~axis-size x, and
    under jit XLA inserts the FSDP communication pattern itself — all-gather
    each weight where the forward/backward consumes it, reduce-scatter the
    gradient where the sharded state updates it. Shard the optimizer state
    too (its leaves inherit the params' layout through ``tx.init``, or pass
    them through :func:`zero_shard_opt_state`) and keep donation on so the
    layout is steady across steps::

        params = fsdp_shard_params(params)
        opt_state = zero_shard_opt_state(tx.init(params))
        step = make_jit_train_step(model, tx)   # unchanged

    Pair with ``jax.checkpoint`` on the model for the usual FSDP memory win
    on deep stacks (re-gather instead of holding gathered weights).
    """
    return _shard_dim0_tree(params, axis)


def _shard_dim0_tree(tree, axis: Optional[str]):
    from horovod_tpu.ops.collective import _mesh_axis_size

    mesh = basics.mesh()
    ax = axis or basics.data_axis()
    n = _mesh_axis_size(mesh, ax)  # product for tuple (host) axes
    repl = NamedSharding(mesh, P())
    #: leaves that WOULD shard but for dim-0 divisibility: (nbytes, name)
    indivisible = []

    def _axes_in(entry):
        if entry is None:
            return ()
        return entry if isinstance(entry, tuple) else (entry,)

    def place(path, x):
        shape = getattr(x, "shape", ())
        existing = _named_sharding(x)
        spec = list(existing.spec) if existing is not None else []
        spec += [None] * (len(shape) - len(spec))
        ax_parts = set(ax) if isinstance(ax, tuple) else {ax}
        ax_used = any(ax_parts & set(_axes_in(e)) for e in spec)
        if (
            len(shape) >= 1
            and shape[0] > 0
            and shape[0] % n == 0
            and spec[0] is None
            and not ax_used
        ):
            # merge the data axis into dim 0, preserving any existing
            # model/pipe/... sharding on the other dims (TP-sharded params
            # give their optimizer moments the same layout; clobbering it
            # would re-replicate them and inflate per-chip HBM)
            spec[0] = ax
            return jax.device_put(x, NamedSharding(mesh, P(*spec)))
        if any(e is not None for e in spec):
            return x  # keep a non-trivial existing layout untouched
        if len(shape) >= 1 and shape[0] > 0 and shape[0] % n != 0:
            # the ONLY disqualifier was divisibility: this leaf stays
            # replicated on every chip — count it so a mostly-replicated
            # "sharded" model shows up in the metrics instead of as a
            # mystery OOM
            nbytes = int(
                np.prod(shape, dtype=np.int64)
            ) * jnp.dtype(getattr(x, "dtype", jnp.float32)).itemsize
            indivisible.append(
                (nbytes, jax.tree_util.keystr(path), tuple(shape)))
        return jax.device_put(x, repl)

    out = jax.tree_util.tree_map_with_path(place, tree)
    if indivisible:
        if _metrics.enabled():
            _metrics.counter(
                "fsdp_leaves_replicated",
                help="leaves left replicated by dim-0 sharding (dim 0 "
                     "not divisible by the axis size)",
                reason="indivisible",
            ).inc(len(indivisible))
        global _INDIVISIBLE_LOGGED
        if not _INDIVISIBLE_LOGGED:
            _INDIVISIBLE_LOGGED = True
            import logging

            worst = max(indivisible)
            logging.getLogger("horovod_tpu").debug(
                "dim-0 sharding left %d leaves replicated (dim 0 not "
                "divisible by axis size %d); worst: %s shape=%s "
                "(%.1f KiB per chip). Pad dim 0 to a multiple of the "
                "axis size, or shard with fsdp_pack_params (the flat "
                "packing pads internally).",
                len(indivisible), n, worst[1], worst[2], worst[0] / 1024,
            )
    return out


#: one-shot flag for the indivisible-leaf debug log (per process, not per
#: call: zero_shard_opt_state/fsdp_shard_params run every restore)
_INDIVISIBLE_LOGGED = False


def split_transformer_for_pp(model, params, n_stages: int, *,
                             interleaved_v: int = 1):
    """Split a :class:`~horovod_tpu.models.TransformerLM` param tree for
    pipeline parallelism: ``depth`` blocks grouped into stages, with the
    (replicated) embedding and head parts separated.

    ``interleaved_v > 1`` lays out ``n_stages * v`` stages round-robin for
    the interleaved/circular schedule (stacked ``[S, v, ...]``); the GPipe
    default stacks ``[S, ...]``.

    Returns ``{"embed": …, "stages": stacked, "head": …}`` — the input to
    :func:`make_transformer_pp_train_step`.
    """
    from horovod_tpu.models.transformer import refuse_training_only

    refuse_training_only(model, "split_transformer_for_pp")
    n_total = n_stages * interleaved_v
    if model.depth % n_total != 0:
        raise ValueError(
            f"depth {model.depth} not divisible by n_stages*v = {n_total}"
        )
    if model.pos_embedding != "learned":
        raise ValueError(
            "PP transformer currently supports pos_embedding='learned' "
            "(positions resolve at embed time; rope would need per-stage "
            "position plumbing)"
        )
    per = model.depth // n_total
    stage_trees = [
        {f"b{j}": params[f"block{s * per + j}"] for j in range(per)}
        for s in range(n_total)
    ]
    from horovod_tpu.parallel.pipeline import (
        make_interleaved_stage_params, make_stage_params,
    )

    if interleaved_v > 1:
        stacked = make_interleaved_stage_params(stage_trees, n_stages)
    else:
        stacked = make_stage_params(stage_trees)
    embed = {"tok_embed": params["tok_embed"], "pos_embed": params["pos_embed"]}
    head = {"ln_f": params["ln_f"], "lm_head": params["lm_head"]}
    return {"embed": embed, "stages": stacked, "head": head}


def make_transformer_pp_train_step(
    model,
    tx: optax.GradientTransformation,
    *,
    interleaved_v: int = 1,
    axis: Optional[str] = None,
    donate: bool = True,
):
    """Pipeline-parallel causal-LM train step for a real
    :class:`~horovod_tpu.models.TransformerLM` — embeddings, transformer
    blocks, and the LM head all trained (TPU-native extension; the generic
    :func:`make_pp_train_step` pipelines uniform stages only).

    Gradient bookkeeping over the pipe axis:

    - **stages**: each device's grad is for its own stage; the
      psum-replicated loss over-counts by the pipe size — divide by S
      (same recipe as :func:`make_pp_train_step`).
    - **embed**: only stage 0 reads the pipeline input
      (``pipeline_apply`` masks it elsewhere), so the true gradient is the
      ``psum`` over the axis of per-device grads (zero off stage 0).
    - **head**: applied to the already-psum-replicated output identically
      on every device, with no collective between head params and the loss
      — the per-device grad IS the true gradient (``pmean`` only tidies
      fp noise).

    Oracle: ``tests/test_transformer.py::
    test_transformer_pp_train_step_matches_dense`` (loss + every updated
    parameter vs the dense single-device step).

    Params come from :func:`split_transformer_for_pp` (pass the same
    ``interleaved_v``); build ``opt_state`` as
    ``{"embed": tx.init(p["embed"]), "head": tx.init(p["head"]),
    "stages": jax.vmap(tx.init)(p["stages"])}`` (double-vmap when
    interleaved: the stages tree is ``[S, v, ...]``). Tokens/targets are
    ``[n_micro, mb, T]`` replicated. Returns jitted
    ``(params, opt_state, tokens_micro, targets_micro) ->
    (params, opt_state, loss)``.
    """
    from jax import lax

    from horovod_tpu.parallel.mesh import PIPELINE_AXIS
    from horovod_tpu.parallel.pipeline import (
        pipeline_apply, pipeline_apply_interleaved,
    )

    if getattr(model, "layers", None) is not None or model.norm != "layernorm":
        raise ValueError(
            "make_transformer_pp_train_step builds LayerNorm blocks that "
            "are all alike (learned positions): a TransformerLM with a "
            "per-layer description or RMSNorm has no pipeline builder yet")
    mesh = basics.mesh()
    ax = axis or PIPELINE_AXIS
    n_stages = mesh.shape[ax]
    if model.depth % (n_stages * interleaved_v) != 0:
        raise ValueError(
            f"depth {model.depth} not divisible by n_stages*v = "
            f"{n_stages * interleaved_v}; pass the same interleaved_v used "
            f"in split_transformer_for_pp"
        )
    per = model.depth // (n_stages * interleaved_v)
    apply_fn = (
        pipeline_apply_interleaved if interleaved_v > 1 else pipeline_apply
    )

    import flax.linen as nn

    from horovod_tpu.models.transformer import TransformerBlock

    block = TransformerBlock(
        model.dim, model.heads, model.mlp_ratio, model.dtype,
        model.attention_fn, kv_heads=model.kv_heads,
    )
    # the real flax modules, so LayerNorm/Dense semantics (stat upcasting,
    # dtype handling) can never drift from TransformerLM's own head
    ln_f = nn.LayerNorm(dtype=model.dtype)
    lm_head = nn.Dense(model.vocab, use_bias=False, dtype=model.dtype)

    def embed_fn(ep, tokens):
        # mirror TransformerLM.__call__'s embedding path (learned positions)
        t = tokens.shape[-1]
        x = jnp.take(ep["tok_embed"]["embedding"], tokens, axis=0)
        x = x.astype(model.dtype)
        return x + ep["pos_embed"][:t].astype(model.dtype)

    def stage_fn(sp, h):
        for j in range(per):
            h = block.apply({"params": sp[f"b{j}"]}, h)
        return h

    def head_fn(hp, x):
        x = ln_f.apply({"params": hp["ln_f"]}, x)
        return lm_head.apply({"params": hp["lm_head"]}, x)

    def pp_step(params, opt_state, toks_m, tgts_m):
        local = jax.tree_util.tree_map(lambda p: p[0], params["stages"])
        local_opt = jax.tree_util.tree_map(
            lambda s: s[0], opt_state["stages"])

        def loss_fn(ep, lp, hp):
            h = embed_fn(ep, toks_m)
            out = apply_fn(stage_fn, lp, h, axis_name=ax)
            out = lax.psum(out, ax)
            return token_xent(head_fn(hp, out), tgts_m)

        loss, (g_e, g_s, g_h) = jax.value_and_grad(
            loss_fn, argnums=(0, 1, 2)
        )(params["embed"], local, params["head"])
        S = lax.psum(1, ax)
        g_s = jax.tree_util.tree_map(lambda g: g / S, g_s)
        g_e = jax.tree_util.tree_map(lambda g: lax.psum(g, ax) / S, g_e)
        # no psum sits between head params and the loss (each device
        # applies the head to the already-replicated output), so the
        # per-device grad IS the true gradient; pmean only tidies fp noise
        g_h = jax.tree_util.tree_map(lambda g: lax.pmean(g, ax), g_h)

        u_s, local_opt = tx.update(g_s, local_opt, local)
        local = optax.apply_updates(local, u_s)
        u_e, opt_e = tx.update(g_e, opt_state["embed"], params["embed"])
        embed = optax.apply_updates(params["embed"], u_e)
        u_h, opt_h = tx.update(g_h, opt_state["head"], params["head"])
        head = optax.apply_updates(params["head"], u_h)
        return (
            {
                "embed": embed,
                "stages": jax.tree_util.tree_map(lambda p: p[None], local),
                "head": head,
            },
            {
                "embed": opt_e,
                "stages": jax.tree_util.tree_map(
                    lambda s: s[None], local_opt),
                "head": opt_h,
            },
            loss,
        )

    # pytree-prefix specs: P() covers whole replicated subtrees, P(ax) the
    # stage-stacked ones — static, so shard_map + jit build ONCE here and
    # the training loop hits the jit cache every step
    part_spec = {"embed": P(), "stages": P(ax), "head": P()}
    smapped = _smap(
        pp_step, mesh,
        (part_spec, part_spec, P(), P()),
        (part_spec, part_spec, P()),
    )
    donate_argnums = (0, 1) if donate else ()
    return jax.jit(smapped, donate_argnums=donate_argnums)
