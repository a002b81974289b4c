"""Continuous-batching transformer inference engine on subscribed weights.

The serving half of ROADMAP item 4's "millions of users" story: weights
stream in through :class:`~horovod_tpu.serving.subscriber.WeightSubscriber`
(train → publish → **serve**), and this engine turns them into tokens under
real request traffic:

- **Paged KV cache** — every layer's cache is one preallocated pool of
  fixed-size pages (``[num_pages, page_size, H_kv, D]``); sequences own
  pages through per-slot page tables, so ONE compiled decode step serves
  any batch composition with fully static shapes (the vLLM memory model).
  The decode-attention path is
  :func:`horovod_tpu.ops.flash_attention.paged_decode_attention` — the
  same primitive :func:`horovod_tpu.models.transformer.generate` uses,
  reached through a page-table gather.
- **Continuous batching** — requests join the batched decode loop at any
  iteration boundary and finished sequences free their slot + pages at
  the boundary they finish (Orca's iteration-level scheduling). Prefill
  is **chunked** (``prefill_chunk`` tokens per iteration) into the same
  schedule, so a long prompt shares iterations with in-flight decodes
  instead of stalling them.
- **Weight arms** — the engine holds one parameter tree per rollout arm
  (``stable``, and ``canary`` while a
  :class:`~horovod_tpu.serving.rollout.GenerationRollout` is evaluating a
  new generation). Params are a *runtime argument* of the one compiled
  step, so arms share the compilation and the page pool.

The engine adds **no training-side collectives**: every jitted function
here is per-process dense compute (pinned by
``tests/test_serving_engine.py`` extracting its collective schedule), so
serving can share a host with training without perturbing the PR-8
schedule fingerprints.

Degrade-don't-crash composes end to end: a stalled subscriber keeps the
engine serving generation ``G−k`` while
:func:`note_subscriber_health` flips ``/health`` to 503 with the lag in
the reason; in-flight sequences are never dropped.
"""

from __future__ import annotations

import dataclasses
import logging
import os
import time
from typing import Any, Dict, List, Optional

import numpy as np

from horovod_tpu.observability import metrics as _metrics
from horovod_tpu.observability import reqtrace as _reqtrace
from horovod_tpu.resilience import chaos as _chaos
from horovod_tpu.serving.scheduler import (
    ContinuousBatchingScheduler,
    QueueFull,
    Request,
)

__all__ = [
    "InferenceEngine",
    "note_subscriber_health",
    "PAGE_SIZE_ENV",
    "PAGES_ENV",
    "MAX_BATCH_ENV",
    "PREFILL_CHUNK_ENV",
    "MAX_QUEUE_ENV",
    "PREFIX_CACHE_ENV",
    "SPEC_LOOKAHEAD_ENV",
    "SPEC_DRAFT_DEPTH_ENV",
    "TP_AXIS_ENV",
]

logger = logging.getLogger("horovod_tpu.serving")

PAGE_SIZE_ENV = "HOROVOD_ENGINE_PAGE_SIZE"
PAGES_ENV = "HOROVOD_ENGINE_PAGES"
MAX_BATCH_ENV = "HOROVOD_ENGINE_MAX_BATCH"
PREFILL_CHUNK_ENV = "HOROVOD_ENGINE_PREFILL_CHUNK"
MAX_QUEUE_ENV = "HOROVOD_ENGINE_MAX_QUEUE"
#: "1" (default) aliases cached prompt pages at admission; "0" disables
PREFIX_CACHE_ENV = "HOROVOD_PREFIX_CACHE"
#: draft tokens proposed per speculative iteration (>= 1)
SPEC_LOOKAHEAD_ENV = "HOROVOD_SPEC_LOOKAHEAD"
#: transformer blocks in the derived draft model; 0 (default) = no
#: draft, speculative decoding off
SPEC_DRAFT_DEPTH_ENV = "HOROVOD_SPEC_DRAFT_DEPTH"
#: mesh axis name to tensor-parallel the serving path over (unset = off)
TP_AXIS_ENV = "HOROVOD_TP_AXIS"


def _env_int(name: str, default: int) -> int:
    return int(os.environ.get(name, str(default)))


def note_subscriber_health(sub) -> None:
    """Publish the serving-side staleness view and feed the health plane:
    ``serving_subscriber_lag`` / ``serving_staleness_seconds`` gauges
    (which ride :class:`~horovod_tpu.observability.aggregate
    .MetricsPublisher` to ``/fleet`` and ``hvd_top`` like every other
    metric), and a ``stale()`` subscriber flips the existing ``/health``
    endpoint to 503 with the lag in the reason
    (:func:`horovod_tpu.resilience.health.record_serving_stale`) until
    the weights are fresh again."""
    from horovod_tpu.resilience import health as _health

    lag = sub.lag()
    age = sub.staleness_seconds()
    if _metrics.enabled():
        _metrics.gauge(
            "serving_subscriber_lag",
            help="generations between the observed head and what the "
                 "engine serves",
        ).set(lag)
        if age is not None:
            _metrics.gauge(
                "serving_staleness_seconds",
                help="wall-clock age of the weights the engine serves",
            ).set(age)
    if sub.stale():
        _health.record_serving_stale(lag, age)
    else:
        _health.record_serving_fresh()


class _Arm:
    def __init__(self, generation: int, params: Any):
        self.generation = generation
        self.params = params
        self.draining = False


class InferenceEngine:
    """Serve a :class:`~horovod_tpu.models.transformer.TransformerLM`
    under continuous batching on a paged KV cache.

    `model` is the *training-shape* module (``decode=False``); the engine
    derives its paged decode twin. Weights arrive via
    :meth:`set_weights` (or :meth:`poll_weights` from an attached
    subscriber); requests via :meth:`submit`; :meth:`step` runs one
    iteration boundary (admission → chunked prefill → batched decode) and
    :meth:`run_until_idle` drains everything queued.

    Greedy decoding through this engine is token-identical to
    :func:`horovod_tpu.models.transformer.generate` for any ragged batch
    and any join/leave order — pinned by
    ``tests/test_serving_engine.py``.
    """

    def __init__(self, model, *, page_size: Optional[int] = None,
                 num_pages: Optional[int] = None,
                 max_batch: Optional[int] = None,
                 prefill_chunk: Optional[int] = None,
                 max_queue: Optional[int] = None,
                 max_seq_len: Optional[int] = None,
                 subscriber=None, eos_token: Optional[int] = None,
                 prefix_cache: Optional[bool] = None,
                 draft_model=None,
                 draft_depth: Optional[int] = None,
                 spec_lookahead: Optional[int] = None,
                 tp_axis: Optional[str] = None):
        import jax

        from horovod_tpu.models.transformer import refuse_training_only

        refuse_training_only(model, "InferenceEngine")
        self._model = model
        self.page_size = int(page_size if page_size is not None
                             else _env_int(PAGE_SIZE_ENV, 16))
        self.num_pages = int(num_pages if num_pages is not None
                             else _env_int(PAGES_ENV, 64))
        self.max_batch = int(max_batch if max_batch is not None
                             else _env_int(MAX_BATCH_ENV, 4))
        self.prefill_chunk = int(
            prefill_chunk if prefill_chunk is not None
            else _env_int(PREFILL_CHUNK_ENV, 16))
        max_queue = int(max_queue if max_queue is not None
                        else _env_int(MAX_QUEUE_ENV, 64))
        self.max_seq_len = int(max_seq_len if max_seq_len is not None
                               else model.max_len)
        if self.max_seq_len > model.max_len:
            raise ValueError(
                f"max_seq_len {self.max_seq_len} exceeds the model's "
                f"max_len {model.max_len}")
        # per-slot page budget, with the capacity rounded up to a whole
        # number of prefill chunks: prefill chunk starts are multiples of
        # prefill_chunk, so a chunk's masked pad tail can never be clamped
        # back INTO the slot's real pages (it either lands at positions the
        # next real write overwrites, or past the row's final frontier
        # where the causal mask hides it)
        pages = -(-self.max_seq_len // self.page_size)
        while (pages * self.page_size) % self.prefill_chunk:
            pages += 1
        self.pages_per_seq = pages
        if self.pages_per_seq > self.num_pages - 1:
            raise ValueError(
                f"page pool too small: one sequence can need "
                f"{self.pages_per_seq} pages, pool has "
                f"{self.num_pages - 1} allocatable (raise {PAGES_ENV} or "
                f"lower max_seq_len)")
        # tensor-parallel serving: param trees land head/feature-sharded
        # over `tp_axis` (transformer_param_specs layouts) and the page
        # pool is head-sharded, so the SAME jitted step partitions over
        # the axis under GSPMD — token-identical to single-chip serving
        # because per-head attention needs no cross-rank reductions and
        # the two per-block psums are bit-deterministic on a fixed mesh
        self.tp_axis = (tp_axis if tp_axis is not None
                        else os.environ.get(TP_AXIS_ENV, "").strip() or None)
        self._mesh = None
        if self.tp_axis:
            from horovod_tpu import basics

            mesh = basics.mesh()
            if self.tp_axis not in mesh.shape:
                raise ValueError(
                    f"tp_axis {self.tp_axis!r} is not an axis of the "
                    f"active mesh (axes: {tuple(mesh.shape)})")
            tp = mesh.shape[self.tp_axis]
            h_kv = model.kv_heads or model.heads
            if model.heads % tp or h_kv % tp:
                raise ValueError(
                    f"heads={model.heads} / kv_heads={h_kv} not divisible "
                    f"by tp axis {self.tp_axis!r} size {tp}")
            self._mesh = mesh
        self.prefix_caching = bool(
            prefix_cache if prefix_cache is not None
            else _env_int(PREFIX_CACHE_ENV, 1))
        self._sched = ContinuousBatchingScheduler(
            num_pages=self.num_pages, page_size=self.page_size,
            max_batch=self.max_batch, pages_per_seq=self.pages_per_seq,
            max_queue=max_queue, prefill_chunk=self.prefill_chunk,
            prefix_cache=self.prefix_caching,
            namespace_of=self._arm_namespace)
        self._subscriber = subscriber
        self.eos_token = eos_token
        # fleet-tier identity: set by FleetReplica so chaos charges can
        # target one replica (``slow_decode=<s>:<arm>@<replica>``) and
        # reqtrace can attribute spans to the engine that served them
        self.replica: Optional[str] = None
        self._arms: Dict[str, _Arm] = {}
        self._drain_seq = 0
        self._dec = dataclasses.replace(
            model, decode=True, paged=True, page_size=self.page_size,
            num_pages=self.num_pages, cache_len=None, name=None)
        self._jax = jax

        def _apply(params, cache, tokens, positions, page_table):
            logits, mut = self._dec.apply(
                {"params": params, "cache": cache}, tokens,
                positions=positions, page_table=page_table,
                mutable=["cache"])
            return logits, mut["cache"]

        self._apply = jax.jit(_apply)
        self._cache = None  # built lazily from shapes on first weights
        self._step_count = 0

        # --- speculative decoding: a small draft model riding the same
        # weight chain. The default draft is the target truncated to its
        # first `draft_depth` blocks — block names are positional
        # (`block0`..`block{d-1}`), so the draft's parameters are a pure
        # SUBSET of every published tree and a new generation fences
        # draft + target together for free.
        self.spec_lookahead = int(
            spec_lookahead if spec_lookahead is not None
            else _env_int(SPEC_LOOKAHEAD_ENV, 4))
        d = int(draft_depth if draft_depth is not None
                else _env_int(SPEC_DRAFT_DEPTH_ENV, 0))
        self._draft_model = draft_model
        if self._draft_model is None and d > 0:
            if d > int(model.depth):
                raise ValueError(
                    f"draft_depth {d} exceeds the target model's depth "
                    f"{model.depth}")
            self._draft_model = dataclasses.replace(
                model, depth=d, name=None)
        self._draft_arms: Dict[str, _Arm] = {}
        self._draft_cache = None
        self._draft_param_shapes = None
        if self._draft_model is not None:
            if self.spec_lookahead < 1:
                raise ValueError(
                    f"spec_lookahead must be >= 1 with a draft model, "
                    f"got {self.spec_lookahead}")
            self._draft_dec = dataclasses.replace(
                self._draft_model, decode=True, paged=True,
                page_size=self.page_size, num_pages=self.num_pages,
                cache_len=None, name=None)

            def _draft_apply(params, cache, tokens, positions,
                             page_table):
                logits, mut = self._draft_dec.apply(
                    {"params": params, "cache": cache}, tokens,
                    positions=positions, page_table=page_table,
                    mutable=["cache"])
                return logits, mut["cache"]

            self._draft_apply = jax.jit(_draft_apply)

    # ------------------------------------------------------------- weights

    def _arm_namespace(self, arm: str) -> Optional[int]:
        """Prefix-cache namespace for `arm`: the weight generation its
        sequences decode under. Cached KV is only reusable under the
        exact weights that wrote it — aliasing across generations would
        silently mix models. None (arm not installed) disables caching
        for the request."""
        a = self._arms.get(arm)
        return None if a is None else int(a.generation)

    def set_weights(self, tree: Any, *, generation: int = 0,
                    arm: str = "stable") -> None:
        """Install a weight tree for `arm` (device-resident; a host tree
        is moved once here, not per step). Trees shaped like a loop state
        (``{"params": ...}``) are unwrapped the same way the publisher's
        ``extract`` does."""
        import jax.numpy as jnp

        from horovod_tpu.serving.publisher import default_extract

        params = self._jax.tree_util.tree_map(
            jnp.asarray, default_extract(tree))
        if self.tp_axis:
            params = self._tp_place_params(params)
        self._park_if_busy(arm)
        self._arms[arm] = _Arm(int(generation), params)
        if self._cache is None:
            self._init_cache()
        if self._draft_model is not None:
            # draft rides the same chain: every published generation
            # derives its draft at install time, so draft and target
            # can never be fenced apart by the rollout state machine
            self._draft_arms[arm] = _Arm(
                int(generation), self._subset_draft_params(params))
            if self._draft_cache is None:
                self._init_draft_cache()
        if _metrics.enabled():
            _metrics.gauge(
                "serving_engine_generation",
                help="weight generation each rollout arm serves",
                arm=arm,
            ).set(int(generation))

    def set_draft_weights(self, tree: Any, *, generation: int = 0,
                          arm: str = "stable") -> None:
        """Install draft params for `arm` explicitly (tests and callers
        publishing the draft separately). Speculative decoding only runs
        while the draft's generation matches the target arm's — a
        lagging draft silently falls back to plain decode rather than
        ever verifying a canary against stale proposals."""
        import jax.numpy as jnp

        from horovod_tpu.serving.publisher import default_extract

        if self._draft_model is None:
            raise ValueError(
                "engine has no draft model (set draft_depth or "
                f"{SPEC_DRAFT_DEPTH_ENV})")
        params = self._jax.tree_util.tree_map(
            jnp.asarray, default_extract(tree))
        self._draft_arms[arm] = _Arm(
            int(generation), self._subset_draft_params(params))
        if self._draft_cache is None:
            self._init_draft_cache()

    def _subset_draft_params(self, params: Any) -> Any:
        """Project a full target tree onto the draft's parameter
        structure (token/position embeddings, the first `draft_depth`
        blocks, final LN, LM head — all shared names)."""
        if self._draft_param_shapes is None:
            import jax
            import jax.numpy as jnp

            b, c = self.max_batch, self.prefill_chunk
            self._draft_param_shapes = jax.eval_shape(
                self._draft_dec.init, jax.random.PRNGKey(0),
                jnp.zeros((b, c), jnp.int32),
                positions=jnp.zeros((b, c), jnp.int32),
                page_table=jnp.zeros(
                    (b, self.pages_per_seq), jnp.int32),
            )["params"]

        def take(shape_node, full_node, path=""):
            if hasattr(shape_node, "items"):
                try:
                    return {k: take(v, full_node[k], f"{path}/{k}")
                            for k, v in shape_node.items()}
                except (KeyError, TypeError):
                    raise ValueError(
                        f"draft model needs parameter subtree {path!r} "
                        f"the published tree does not carry — the draft "
                        f"must be a truncation of the target") from None
            if tuple(getattr(full_node, "shape", ())) \
                    != tuple(shape_node.shape):
                raise ValueError(
                    f"draft parameter {path!r} expects shape "
                    f"{tuple(shape_node.shape)}, published tree carries "
                    f"{tuple(getattr(full_node, 'shape', ()))} — the "
                    f"draft must be a truncation of the target")
            return full_node

        return take(self._draft_param_shapes, params)

    def arm_generation(self, arm: str) -> Optional[int]:
        a = self._arms.get(arm)
        return None if a is None else a.generation

    def arm_params(self, arm: str) -> Optional[Any]:
        a = self._arms.get(arm)
        return None if a is None else a.params

    def _park_if_busy(self, arm: str) -> None:
        """An arm being replaced while it still has in-flight sequences
        parks its old params under a private drain label — a sequence's
        KV cache was built under its weights, so swapping them mid-decode
        would emit incoherent tokens. The parked arm releases itself at
        the step boundary its last sequence finishes."""
        old = self._arms.get(arm)
        if old is None or not self._sched.active(arm):
            return
        self._drain_seq += 1  # unique label even if the same (arm,
        # generation) parks twice across vetoes
        label = f"{arm}-drain-{self._drain_seq}-g{old.generation}"
        old.draining = True
        self._arms[label] = old
        # the draft parks alongside its target: draining sequences keep
        # speculating on the generation they decode under
        od = self._draft_arms.get(arm)
        if od is not None:
            self._draft_arms[label] = od
        moved = self._sched.move_active_to_drain(arm, label)
        logger.info(
            "arm %r replaced with %d sequence(s) in flight; draining "
            "them on generation %d as %r", arm, moved, old.generation,
            label)

    def promote_canary(self) -> None:
        """Canary becomes stable (the rollout controller's promotion).
        In-flight canary sequences are relabeled — the params they decode
        against ARE the promoted ones, so their tokens are unaffected and
        they must not be stranded on an arm that no longer exists. The
        OLD stable arm's in-flight sequences keep their own weights: they
        park under a drain label and finish coherently."""
        arm = self._arms.pop("canary", None)
        if arm is None:
            return
        darm = self._draft_arms.pop("canary", None)
        self._park_if_busy("stable")
        arm.draining = False
        self._arms["stable"] = arm
        if darm is not None:
            self._draft_arms["stable"] = darm
        else:
            # the promoted generation has no draft: leaving the old
            # stable draft behind would fence-fail anyway; drop it
            self._draft_arms.pop("stable", None)
        self._sched.relabel_arm("canary", "stable")
        if _metrics.enabled():
            _metrics.gauge(
                "serving_engine_generation",
                help="weight generation each rollout arm serves",
                arm="stable",
            ).set(arm.generation)

    def retire_arm(self, arm: str) -> None:
        """Stop routing to `arm` but keep its params until every in-flight
        sequence on it finished — a rollback never drops work mid-decode.
        Requests still *queued* for the arm have produced no tokens yet,
        so they simply re-route to stable."""
        a = self._arms.get(arm)
        if a is not None:
            a.draining = True
        if arm != "stable":
            self._sched.relabel_queued_only(arm, "stable")

    def poll_weights(self) -> Optional[int]:
        """Standalone (no rollout controller) weight refresh: poll the
        attached subscriber into the stable arm and feed the health
        plane. Returns the new generation when one arrived."""
        if self._subscriber is None:
            return None
        fresh = self._subscriber.poll()
        note_subscriber_health(self._subscriber)
        if fresh is None:
            return None
        gen = self._subscriber.generation
        self.set_weights(fresh, generation=gen, arm="stable")
        return gen

    def _init_cache(self) -> None:
        import jax
        import jax.numpy as jnp

        b, c = self.max_batch, self.prefill_chunk
        shapes = jax.eval_shape(
            self._dec.init, jax.random.PRNGKey(0),
            jnp.zeros((b, c), jnp.int32),
            positions=jnp.zeros((b, c), jnp.int32),
            page_table=jnp.zeros((b, self.pages_per_seq), jnp.int32),
        )["cache"]
        self._cache = jax.tree_util.tree_map(
            lambda s: jnp.zeros(s.shape, s.dtype), shapes)
        if self.tp_axis:
            self._cache = self._tp_place_cache(self._cache)

    def _tp_place_params(self, params: Any) -> Any:
        """Shard a param tree over the tp axis with the Megatron layouts
        from :func:`~horovod_tpu.models.transformer.transformer_param_specs`
        (qkv/mlp_up column-split, proj/mlp_down row-split → one psum per
        pair, inserted by the partitioner)."""
        import jax
        from jax.sharding import NamedSharding, PartitionSpec

        from horovod_tpu.models.transformer import transformer_param_specs

        specs = transformer_param_specs(params, model_axis=self.tp_axis)
        tp = self._mesh.shape[self.tp_axis]

        def place(x, s):
            # a spec'd dim the axis size does not divide (typically the
            # vocab dim of lm_head/tok_embed) stays replicated — the same
            # indivisible-leaf policy as training's _shard_dim0_tree
            for i, name in enumerate(s):
                if name is not None and x.shape[i] % tp != 0:
                    s = PartitionSpec()
                    break
            return jax.device_put(x, NamedSharding(self._mesh, s))

        return jax.tree_util.tree_map(place, params, specs)

    def _tp_place_cache(self, cache: Any) -> Any:
        """Head-shard the page pools ``[P, page_size, H_kv, D]`` on dim 2
        so each rank's decode attention touches only its own heads."""
        import jax
        from jax.sharding import NamedSharding, PartitionSpec as P

        sh = NamedSharding(self._mesh, P(None, None, self.tp_axis, None))
        return jax.tree_util.tree_map(
            lambda x: jax.device_put(x, sh), cache)

    def _init_draft_cache(self) -> None:
        import jax
        import jax.numpy as jnp

        b, c = self.max_batch, self.prefill_chunk
        shapes = jax.eval_shape(
            self._draft_dec.init, jax.random.PRNGKey(0),
            jnp.zeros((b, c), jnp.int32),
            positions=jnp.zeros((b, c), jnp.int32),
            page_table=jnp.zeros((b, self.pages_per_seq), jnp.int32),
        )["cache"]
        self._draft_cache = jax.tree_util.tree_map(
            lambda s: jnp.zeros(s.shape, s.dtype), shapes)
        if self.tp_axis:
            self._draft_cache = self._tp_place_cache(self._draft_cache)

    # ------------------------------------------------------------ requests

    def submit(self, req_or_prompt, max_new_tokens: Optional[int] = None,
               *, rid=None, temperature: float = 0.0,
               arm: str = "stable") -> Request:
        """Queue a request (a prebuilt :class:`Request` or a prompt
        array). Raises :class:`QueueFull` under admission backpressure and
        ``ValueError`` for prompts that can never fit one sequence's page
        budget."""
        if isinstance(req_or_prompt, Request):
            req = req_or_prompt
        else:
            if max_new_tokens is None:
                raise ValueError("submit(prompt) needs max_new_tokens")
            req = Request(
                rid if rid is not None else f"req-{id(req_or_prompt)}",
                req_or_prompt, max_new_tokens, temperature=temperature,
                arm=arm)
        total = req.prompt.size + req.max_new_tokens
        if total > self.max_seq_len:
            raise ValueError(
                f"request {req.rid!r}: prompt + max_new_tokens = {total} "
                f"exceeds max_seq_len {self.max_seq_len}")
        self._sched.submit(req)
        return req

    @property
    def scheduler(self) -> ContinuousBatchingScheduler:
        return self._sched

    # ---------------------------------------------------------- iteration

    def step(self) -> bool:
        """One iteration boundary: chaos intake → admission → one chunked
        prefill pass, one speculative pass, and one decode pass per
        active arm. Returns True when any compute ran (False = fully
        idle)."""
        self._step_count += 1
        self._chaos_burst()
        if _chaos.take_cache_evict(self._step_count):
            victims, dropped = self._sched.chaos_evict()
            logger.warning(
                "chaos cache_evict at pass %d: dropped %d cached "
                "page(s), %d victim sequence(s) re-prefilling",
                self._step_count, dropped, victims)
        if not self._arms:
            return False  # no weights yet; requests keep queueing
        self._sched.admit()
        ran = False
        for arm in self._sched.arms_active():
            a = self._arms.get(arm)
            if a is None:
                for seq in self._sched.active(arm):
                    self._sched.finish(
                        seq, error=f"no weights for arm {arm!r}")
                continue
            ran |= self._prefill_pass(arm, a)
            handled = self._spec_pass(arm, a)
            ran |= bool(handled)
            ran |= self._decode_pass(arm, a, exclude=handled)
        # a retired arm with nothing left in flight releases its params
        for name in [n for n, a in self._arms.items() if a.draining]:
            if not self._sched.active(name):
                del self._arms[name]
                self._draft_arms.pop(name, None)
        return ran

    def run_until_idle(self, max_iters: int = 10000) -> None:
        """Drive :meth:`step` until queue and slots are empty (tests and
        batch-style callers); raises past `max_iters` instead of spinning
        forever on a scheduling bug."""
        for _ in range(max_iters):
            if self._sched.idle():
                return
            if not self._arms:
                raise RuntimeError(
                    "engine has work queued but no weights installed — "
                    "call set_weights() or poll_weights() first")
            self.step()
        raise RuntimeError(
            f"engine did not drain within {max_iters} iterations")

    def _chaos_burst(self) -> None:
        """``HOROVOD_CHAOS=request_burst=N``: N synthetic requests slam
        the queue at one iteration boundary — the deterministic
        queue-overflow drill. Rejections are the point; they are counted
        by admission control."""
        n = _chaos.take_request_burst()
        for i in range(n):
            try:
                self.submit(
                    Request(f"chaos-burst-{i}", [1, 1], 1))
            except (QueueFull, ValueError) as e:
                logger.debug("chaos burst request rejected: %s", e)

    # ------------------------------------------------------------- passes

    def _maybe_slow(self, arm: str) -> None:
        """``HOROVOD_CHAOS=slow_decode=<s>[:<arm>[@<replica>]]``: sleep
        before this pass when the charge targets `arm` (drain labels
        inherit their source arm's scope) and, when a ``@<replica>``
        suffix is present, only on the engine whose fleet ``replica`` id
        matches — the deterministic latency regression, scopeable to one
        replica's canary arm for fleet-rollback drills. Host-side only:
        tokens are unaffected, so a drill keeps token parity with a
        clean run."""
        charge = _chaos.slow_decode()
        if charge is None:
            return
        secs, target = charge
        if secs <= 0:
            return
        if target is not None:
            base, _, rep = target.partition("@")
            if rep and rep != (self.replica or ""):
                return
            if (base and arm != base
                    and not arm.startswith(f"{base}-drain")):
                return
        _chaos.record_injection("slow_decode")
        time.sleep(secs)

    def _run(self, params, tokens, positions, table, kind: str):
        import jax.numpy as jnp

        logits, self._cache = self._apply(
            params, self._cache, jnp.asarray(tokens),
            jnp.asarray(positions), jnp.asarray(table))
        if _metrics.enabled():
            _metrics.counter(
                "serving_engine_steps",
                help="compiled engine iterations, by phase",
                kind=kind,
            ).inc()
        return np.asarray(logits)

    def _run_draft(self, params, tokens, positions, table, kind: str):
        import jax.numpy as jnp

        logits, self._draft_cache = self._draft_apply(
            params, self._draft_cache, jnp.asarray(tokens),
            jnp.asarray(positions), jnp.asarray(table))
        if _metrics.enabled():
            _metrics.counter(
                "serving_engine_steps",
                help="compiled engine iterations, by phase",
                kind=kind,
            ).inc()
        return np.asarray(logits)

    def _prefill_pass(self, arm: str, a: _Arm) -> bool:
        rows = [s for s in self._sched.active(arm) if s.prefilling]
        if not rows:
            return False
        self._maybe_slow(arm)
        t0 = time.monotonic()
        b, c = self.max_batch, self.prefill_chunk
        tokens = np.zeros((b, c), np.int32)
        positions = np.zeros((b, c), np.int32)
        table = np.zeros((b, self.pages_per_seq), np.int32)  # trash rows
        real_table = self._sched.page_table_rows()
        rems: List[int] = []
        for s in rows:
            # prefill_src is the prompt, or prompt + replayed generated
            # tokens after a forced cache eviction; a prefix-cache hit
            # pre-advanced done_prompt past the aliased pages
            rem = min(c, s.prefill_len - s.done_prompt)
            tokens[s.slot, :rem] = s.prefill_src[
                s.done_prompt:s.done_prompt + rem]
            positions[s.slot] = s.done_prompt + np.arange(c, dtype=np.int32)
            table[s.slot] = real_table[s.slot]
            rems.append(rem)
        logits = self._run(a.params, tokens, positions, table, "prefill")
        da = self._draft_arms.get(arm)
        if da is not None:
            # mirror the writes into the draft cache so proposals can
            # attend to the prompt (same tokens, positions, tables)
            self._run_draft(da.params, tokens, positions, table,
                            "draft_prefill")
        if _metrics.enabled():
            _metrics.counter(
                "serving_prefill_tokens",
                help="prompt tokens written to the paged cache",
            ).inc(sum(rems))
        for s, rem in zip(rows, rems):
            s.done_prompt += rem
            _reqtrace.on_prefill_chunk(s, rem, t0, a.generation)
            if s.done_prompt >= s.prefill_len and not s.generated:
                # the row's first sampled token comes from ITS last real
                # position in this chunk, exactly like generate()'s
                # last_logits gather. A replay (post-eviction rebuild)
                # with tokens already sampled consumes nothing: its
                # next token resumes from last_token in the decode pass.
                self._consume_logits(s, logits[s.slot, rem - 1],
                                     a.generation)
        return True

    def _spec_pass(self, arm: str, a: _Arm) -> set:
        """Speculative decode for every eligible row: the draft proposes
        ``spec_lookahead`` greedy tokens (K single-token forwards on its
        own paged cache), the target verifies all of them in ONE
        ``[b, K+1]`` forward, and the longest agreeing prefix plus the
        target's own next token are emitted. Greedy acceptance makes the
        emitted stream token-identical to sequential decode by
        construction: every emitted token is the target's argmax given
        exactly the tokens before it. A rejected tail costs nothing to
        roll back — its KV sits past the row's frontier, where
        paged_decode_attention zeroes before the matmuls, and the next
        writes overwrite it.

        Eligible: greedy rows with at least K+1 tokens of budget left
        (the verify forward must stay inside the page reservation), on
        an arm whose draft generation MATCHES the target's — a stale
        draft falls back to plain decode, never a canary verifying
        against old proposals. Returns the ids of handled sequences."""
        handled: set = set()
        if self._draft_model is None:
            return handled
        da = self._draft_arms.get(arm)
        if da is None or da.generation != a.generation:
            return handled
        K = self.spec_lookahead
        rows = [s for s in self._sched.active(arm)
                if not s.prefilling and s.last_token is not None
                and s.req.temperature <= 0.0
                and s.req.max_new_tokens - len(s.generated) >= K + 1]
        if not rows:
            return handled
        self._maybe_slow(arm)
        b = self.max_batch
        real_table = self._sched.page_table_rows()
        table = np.zeros((b, self.pages_per_seq), np.int32)
        base: Dict[int, int] = {}
        for s in rows:
            table[s.slot] = real_table[s.slot]
            base[id(s)] = s.length
        # --- proposal: K sequential draft forwards (writes the draft's
        # own KV as it goes, so token j attends to tokens < j)
        drafts = np.zeros((b, K), np.int32)
        cur = np.zeros((b, 1), np.int32)
        pos = np.zeros((b, 1), np.int32)
        for s in rows:
            cur[s.slot, 0] = s.last_token
            pos[s.slot, 0] = base[id(s)]
        for j in range(K + 1):
            dl = self._run_draft(da.params, cur, pos, table,
                                 "draft_propose")
            # the K+1'th forward only WRITES d_K's draft KV (logits
            # unused): on full acceptance the next round's frontier sits
            # past it, and a draft cache hole there would desync the
            # draft from the target — rejected tails need no such care,
            # they are masked then overwritten
            if j < K:
                for s in rows:
                    nxt = int(np.argmax(dl[s.slot, 0]))
                    drafts[s.slot, j] = nxt
                    cur[s.slot, 0] = nxt
            pos = pos + 1
        # --- verify: ONE batched [b, K+1] target forward over
        # [last_token, d_1 .. d_K]; row i's logits are the target's
        # next-token distribution after the first i+1 of those
        vtok = np.zeros((b, K + 1), np.int32)
        vpos = np.zeros((b, K + 1), np.int32)
        for s in rows:
            vtok[s.slot, 0] = s.last_token
            vtok[s.slot, 1:] = drafts[s.slot]
            vpos[s.slot] = base[id(s)] + np.arange(K + 1, dtype=np.int32)
        logits = self._run(a.params, vtok, vpos, table, "spec_verify")
        for s in rows:
            handled.add(id(s))
            row = logits[s.slot]  # [K+1, vocab]
            m = 0
            while (m < K and np.all(np.isfinite(row[m]))
                   and int(np.argmax(row[m])) == int(drafts[s.slot, m])):
                m += 1
            # emit the m accepted tokens plus the target's bonus token
            # at the first divergence (sequential-greedy semantics: stop
            # early if the sequence finishes on budget/EOS/non-finite)
            for i in range(m + 1):
                self._consume_logits(s, row[i], a.generation)
                if s.req.done:
                    break
            if _metrics.enabled():
                _metrics.counter(
                    "spec_proposed",
                    help="draft tokens proposed to the target verifier",
                ).inc(K)
                _metrics.counter(
                    "spec_accepted",
                    help="draft tokens the target verifier accepted",
                ).inc(m)
                if m < K:
                    _metrics.counter(
                        "spec_rollbacks",
                        help="speculative iterations whose tail was "
                             "rejected (frontier rolled back)",
                    ).inc()
            _reqtrace.on_spec_verify(s, K, m, a.generation)
        return handled

    def _decode_pass(self, arm: str, a: _Arm,
                     exclude: Optional[set] = None) -> bool:
        rows = [s for s in self._sched.active(arm)
                if not s.prefilling and s.last_token is not None
                and (exclude is None or id(s) not in exclude)]
        if not rows:
            return False
        self._maybe_slow(arm)
        b = self.max_batch
        tokens = np.zeros((b, 1), np.int32)
        positions = np.zeros((b, 1), np.int32)
        table = np.zeros((b, self.pages_per_seq), np.int32)
        real_table = self._sched.page_table_rows()
        for s in rows:
            tokens[s.slot, 0] = s.last_token
            positions[s.slot, 0] = s.length
            table[s.slot] = real_table[s.slot]
        logits = self._run(a.params, tokens, positions, table, "decode")
        for s in rows:
            self._consume_logits(s, logits[s.slot, 0], a.generation)
        return True

    def _consume_logits(self, s, row_logits: np.ndarray,
                        generation: int = -1) -> None:
        """Sample one token for `s` from its ``[vocab]`` logits row and
        retire the sequence when it is done (budget reached, EOS, or
        non-finite logits — the canary regression signal)."""
        if not np.all(np.isfinite(row_logits)):
            self._sched.finish(seq=s, error="non-finite logits")
            return
        first = not s.generated
        tok = s.sample(row_logits)
        s.generated.append(tok)
        s.last_token = tok
        # TTFT closes on the first sampled token; every later one is a
        # TPOT cadence point — tagged with the weight generation that
        # actually decoded it, so gate windows never mix generations
        if first:
            _reqtrace.on_first_token(s, generation)
        else:
            _reqtrace.on_token(s, generation)
        if _metrics.enabled():
            _metrics.counter(
                "serving_tokens_generated",
                help="tokens sampled by the engine",
            ).inc()
        if (len(s.generated) >= s.req.max_new_tokens
                or (self.eos_token is not None and tok == self.eos_token)):
            self._sched.finish(seq=s)
