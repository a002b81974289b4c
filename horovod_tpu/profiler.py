"""Tracing/profiling surface — the TPU-native Timeline (SURVEY.md §5.1).

The reference writes a Chrome-tracing JSON from the C++ core's negotiation
and op phases (``common/timeline.{h,cc}``, enabled by ``HOROVOD_TIMELINE``,
coordinator-only). The rebuild has two complementary layers:

- **Negotiation timeline** — the native core (``csrc/``) writes the same
  chrome://tracing JSON for enqueue/negotiate/execute phases when
  ``HOROVOD_TIMELINE`` is set (see ``horovod_tpu/core.py``).
- **Device timeline** (this module) — on TPU the op execution itself lives
  inside XLA, invisible to a host-side tracer; the idiomatic tool is the XLA
  profiler. ``start_timeline``/``stop_timeline`` wrap ``jax.profiler`` so one
  call captures device traces (HLO steps, collective time on ICI, HBM
  transfers) viewable in TensorBoard/Perfetto — the role chrome://tracing
  plays for the reference.

The operator's workflow::

    with hvd.profiler.timeline("/tmp/trace"):      # around a few steps
        for _ in range(5):
            state = step(*state, xs, ys)
    # open /tmp/trace in xprof / Perfetto: operations group by hvd.* scope;
    # in a script, join the trace's events to the program's own names:
    table = hvd.profiler.scope_table()             # {module: {instr: ...}}
    entry = table["jit_hvd1_step"]["fusion.7"]     # (op_name, HLO kind)
    phase, kernel = hvd.profiler.scope_of(*entry)

**Scope and span names are a stable interface** (``docs/observability.md``
lists what reads each). Scopes are ``jax.named_scope`` names: HLO metadata,
always there, no runtime cost. Spans are ``TraceMe``s on the profiler's
clock, recorded only while a profiler session is on.

Device scopes, as they read in an instruction's ``op_name``:

- ``jvp(hvd.forward)`` — the differentiated loss function: the forward pass;
  ``transpose(jvp(hvd.forward))`` — its transpose: the backward pass
- ``hvd.sync/grads``, ``/stats``, ``/loss``, ``/params``, ``/updates`` — the
  gradient, BatchNorm-statistics and loss exchange; ZeRO's gathers
- ``hvd.optimizer`` — ``tx.update`` + ``optax.apply_updates``
- ``hvd.allreduce``, ``hvd.allgather``, … (+ ``/<name>`` where the caller
  gave ``name=``) — an in-jit ``hvd.<collective>``, a user's own included
- ``hvd.flash_fwd`` / ``hvd.flash_bwd`` — flash attention's two halves
- ``hvd.moe_route`` — a routed-expert layer's router, top-k, sort, the
  gather into the sorted buffer and the kernel that sums the rows back per
  token (a ``pallas_call`` with no name); ``hvd.moe_experts`` — its grouped
  matrix products and the activation between them; ``hvd.moe_shared`` —
  the shared expert every token passes through beside its routed ones
  (plain matrix products); ``hvd.gdn`` — a Gated DeltaNet layer's work
  between its in- and out-projections (the causal convolution, the q/k
  normalisations, beta and the decays, the chunked delta rule and its
  ``while`` over the chunks, the gated output norm); ``hvd.ssm`` — a
  Mamba-2 layer's work between its in- and out-projections (the causal
  convolution, the step sizes, the chunked recurrence, the gated group
  norm); ``hvd.moe_latent`` — a latent routed layer's projections down to
  its experts' width and back. All cover the forward and, in or under a
  ``transpose(...)`` component, the backward
- ``hvd_<kernel>`` — one ``pallas_call`` (also the Mosaic ``kernel_name``):
  ``hvd_flash_fwd``; ``hvd_moe_gmm`` (a row tile of the sorted buffer times
  its expert's matrix, or its transpose), ``hvd_moe_mlp_fwd`` (a row tile
  through its expert's SwiGLU, weighted), ``hvd_moe_mlp_bwd`` (its
  backward), ``hvd_moe_relu2_fwd`` / ``hvd_moe_relu2_bwd`` (the same of a
  relu² expert) and ``hvd_moe_tgmm`` (an expert's weight gradients) inside
  ``hvd.moe_experts``, keyed apart from it by
  :func:`scope_of`; the quantisation and optimizer kernels of
  ``ops/pallas_kernels.py``

Trace-time gauges say what a trace chose: ``flash_fwd_tile`` /
``flash_bwd_tile`` (+ ``*_grid_steps``), ``moe_rows_budget`` (rows of a
routed layer's sorted buffer: the worst case), ``moe_combine_tile`` (the
tile, window and windows a product of the kernel that sums the rows back,
chosen from the shapes; absent where the gather ran),
``moe_experts_fused`` (1 where the experts' activation and weighting were
traced inside the grouped products' kernels), ``gdn_chunk`` / ``gdn_chunks``
(tokens a chunk of the gated delta rule, and chunks a row), ``ssm_chunk`` /
``ssm_chunks`` (the same of the Mamba-2 recurrence). ``moe_local_rows`` is a
step's counter (the assignments that landed on the experts held here: what
the grouped products' time follows), set by ``parallel.moe.record_rows``
from the step's ``batch_stats``.

Host spans: ``hvd.step`` (``InstrumentedStep.__call__``, a step marker
carrying ``step_num``), ``hvd.step/dispatch`` (the wrapped step call inside
it: the difference is what the per-step hooks cost), ``hvd.shard_batch``.
Set-up spans, which also set the ``span_seconds{span=<name>}`` gauge:
``hvd.init`` (the whole of ``basics.init``), ``hvd.broadcast_parameters``
(the whole tree in ``optim.broadcast_parameters``).

Set-up's compile pipeline (:func:`book_compiles`, once a process, from
``hvd.init``): ``compile_seconds{stage=trace|lower|compile, fn=...}``, the
seconds JAX reports for Python → jaxpr, jaxpr → MLIR and the backend's
compile or load from the persistent cache, and the counters
``compile_cache_hits{fn=...}`` / ``compile_cache_misses{fn=...}``. ``fn`` is
a step builder's step by its own name (``hvd1_step``: the seconds of its
latest build, see :func:`book_step`), else ``other`` (summed since the
process started).
"""

from __future__ import annotations

import contextlib
import os
import re
import threading
import time
from typing import Optional

import jax

from horovod_tpu.observability import metrics as _metrics

_active_dir: Optional[str] = None


def start_timeline(log_dir: str) -> None:
    """Begin capturing a device trace into ``log_dir`` (analog of setting
    ``HOROVOD_TIMELINE``; reference ``operations.cc:404-411`` inits the
    Timeline on the coordinator only — call this on rank/process 0)."""
    global _active_dir
    if _active_dir is not None:
        raise RuntimeError(f"timeline already active in {_active_dir}")
    os.makedirs(log_dir, exist_ok=True)
    jax.profiler.start_trace(log_dir)
    _active_dir = log_dir


def stop_timeline() -> str:
    """Stop the capture; returns the trace directory."""
    global _active_dir
    if _active_dir is None:
        raise RuntimeError("no active timeline; call start_timeline first")
    jax.profiler.stop_trace()
    out, _active_dir = _active_dir, None
    return out


@contextlib.contextmanager
def timeline(log_dir: str):
    """Context-manager spelling::

        with hvd.profiler.timeline("/tmp/trace"):
            train_steps()
    """
    start_timeline(log_dir)
    try:
        yield log_dir
    finally:
        stop_timeline()


def annotate(name: str, *, record: bool = False):
    """The one host-span helper: a ``TraceMe`` on the profiler's clock (the
    device planes' clock), recorded only while a profiler session runs at
    host tracer level >= 1 and costing a flag read otherwise. The program's
    own spans (``hvd.step/dispatch``, ``hvd.shard_batch``) go through it
    (analog of the reference's per-tensor ACTIVITY spans,
    ``common/common.h:31-59``).

    ``record=True`` is for spans that run once a set-up: the span's host
    seconds also set the ``span_seconds{span=<name>}`` gauge, whether a
    profiler session runs or not, and the span may decorate a function
    (``hvd.init``, ``hvd.broadcast_parameters``)."""
    if not record:
        return jax.profiler.TraceAnnotation(name)
    return _recorded(name)


@contextlib.contextmanager
def _recorded(name: str):
    t0 = time.perf_counter()
    with jax.profiler.TraceAnnotation(name):
        yield
    _metrics.gauge(
        "span_seconds", help="host seconds of the latest run of a set-up span",
        span=name).set(time.perf_counter() - t0)


# --------------------------------------------------------------------------
# set-up's compile pipeline, as ``jax.monitoring`` reports it

_STAGES = {
    "/jax/core/compile/jaxpr_trace_duration": "trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower",
    "/jax/core/compile/backend_compile_duration": "compile",
}
_CACHE_EVENTS = {
    "/jax/compilation_cache/cache_hits": "compile_cache_hits",
    "/jax/compilation_cache/cache_misses": "compile_cache_misses",
}
#: the step builders' functions, booked under their own name
_step_names: set = set()
_book_lock = threading.Lock()
_booked = False
#: the persistent cache's verdict on the compile in flight on this thread:
#: JAX reports it before the compile's duration, which names the function
_cache_verdict = threading.local()


def book_step(name: str) -> None:
    """Book the compile pipeline of the function ``name`` under its own
    ``fn`` label from now on, each stage's seconds starting again from 0:
    called by the step builders (``training._named``) as they build, so a
    rebuilt step's seconds replace its last build's."""
    _step_names.add(name)
    for stage in _STAGES.values():
        _compile_seconds(stage, name).set(0.0)


def book_compiles() -> None:
    """Book JAX's compile events into the metrics registry (the series in
    this module's docstring). Registers its listeners once a process,
    however often it is called (``hvd.init`` calls it); costs one registry
    update per compile event and nothing per step."""
    global _booked
    with _book_lock:
        if _booked:
            return
        jax.monitoring.register_event_duration_secs_listener(_on_duration)
        jax.monitoring.register_event_listener(_on_event)
        _booked = True


def _compile_seconds(stage: str, fn: str):
    return _metrics.gauge(
        "compile_seconds",
        help="seconds of a compile stage: a step builder's step, its latest "
             "build; fn=other, every other function since start",
        stage=stage, fn=fn)


def _fn_label(fun_name: str) -> str:
    # ``hvd1_step`` traces; ``jit(hvd1_step)`` lowers and compiles
    name = fun_name.removeprefix("jit(").removesuffix(")")
    return name if name in _step_names else "other"


def _on_duration(event: str, duration: float, fun_name: str = "", **_):
    stage = _STAGES.get(event)
    if stage is None:
        return
    fn = _fn_label(fun_name)
    _compile_seconds(stage, fn).inc(duration)
    verdict = getattr(_cache_verdict, "name", None) \
        if stage == "compile" else None
    if verdict is not None:
        _cache_verdict.name = None
        _metrics.counter(
            verdict, help="executables JAX's persistent compilation cache "
                          "served (hits) or stored (misses)", fn=fn).inc()


def _on_event(event: str, **_):
    verdict = _CACHE_EVENTS.get(event)
    if verdict is not None:
        _cache_verdict.name = verdict


# --------------------------------------------------------------------------
# the scope table: a device trace event names an HLO instruction and nothing
# of the program (its stats are offsets and durations, no ``op_name``), so the
# program's scopes are joined in through the compiled module's metadata

#: HLO kinds that move data between chips, whatever scope they inherited
_COLLECTIVE_KINDS = ("all-reduce", "all-gather", "reduce-scatter",
                     "collective-permute", "all-to-all",
                     "collective-broadcast")
_HLO_LINE = re.compile(r"^\s*(?:ROOT )?%?([^\s=]+) = ")
_HLO_KIND = re.compile(r" = .+? ([a-z][\w\-]*)\(")
_HLO_BRACES = re.compile(r"\{[^{}]*\}")
_OP_NAME = re.compile(r'op_name="([^"]*)"')


def _parse_hlo(text: str) -> dict:
    """``{instruction name: (op_name, HLO kind)}`` of one module's text,
    every computation's instructions (names are unique in a module)."""
    table = {}
    for line in text.splitlines():
        m = _HLO_LINE.match(line)
        if m is None:
            continue
        op = _OP_NAME.search(line)
        # layouts and attribute groups hold parentheses of their own
        # (``{1,0:T(8,128)}``): strip them before reading the kind
        bare = _HLO_BRACES.sub("", _HLO_BRACES.sub("", line))
        kind = _HLO_KIND.search(bare)
        table[m.group(1)] = (op.group(1) if op else "",
                             kind.group(1) if kind else "")
    return table


def scope_table() -> dict:
    """``{module name: {instruction name: (op_name, HLO kind)}}`` for every
    live executable of the backend, parsed from the compiled modules' text:
    no handle on the jitted function and no second lowering. A trace
    event's leading ``%name`` is the key; :func:`scope_of` reads the value.
    A module name that repeats (two ``jit_step``s) keeps the later ones
    under ``name#2``, ``name#3``, …"""
    tables: dict = {}
    for exe in jax.devices()[0].client.live_executables():
        for mod in exe.hlo_modules():
            key, n = mod.name, 1
            while key in tables:
                n += 1
                key = f"{mod.name}#{n}"
            tables[key] = _parse_hlo(mod.to_string())
    return tables


def _components(op_name: str):
    """``op_name`` split at the ``/`` outside parentheses."""
    parts, depth, cur = [], 0, []
    for ch in op_name:
        if ch == "/" and depth == 0:
            parts.append("".join(cur))
            cur = []
            continue
        depth += (ch == "(") - (ch == ")")
        cur.append(ch)
    parts.append("".join(cur))
    return parts


def scope_of(op_name: str, kind: str = ""):
    """``(phase, kernel)`` of an instruction. ``phase`` is the first that
    applies of ``"sync"`` (under ``hvd.sync``, or a collective by its HLO
    kind: a bucketed exchange issued from inside the backward is sync, and
    so is an ``all-reduce`` the partitioner inserted under some backward
    operation's name), ``"optimizer"`` (``hvd.optimizer``), ``"backward"``
    (``hvd.forward`` in or under a ``transpose(...)`` component: the
    transposed pass, and what ``jax.checkpoint`` recomputes during it),
    ``"forward"`` (any other ``hvd.forward``), else ``None``. ``kernel`` is
    the innermost ``hvd.flash_*`` / ``hvd.moe_*`` / ``hvd.gdn`` /
    ``hvd.ssm`` / ``hvd_<kernel>`` component, else ``None``."""
    parts = _components(op_name)
    kernel = next((p for p in reversed(parts)
                   if p.startswith(("hvd.flash_", "hvd.moe_", "hvd.gdn",
                                    "hvd.ssm", "hvd_"))), None)
    if kind.startswith(_COLLECTIVE_KINDS) or \
            any("hvd.sync" in p for p in parts):
        return "sync", kernel
    if any("hvd.optimizer" in p for p in parts):
        return "optimizer", kernel
    at = next((i for i, p in enumerate(parts) if "hvd.forward" in p), None)
    if at is None:
        return None, kernel
    # ``transpose(jvp(hvd.forward))``, or under ``jax.checkpoint``
    # ``transpose(jvp(jvp()))/checkpoint/[rematted_computation/]hvd.forward``
    backward = any("transpose(" in p for p in parts[:at + 1])
    return ("backward" if backward else "forward"), kernel


# Peak bf16 matmul throughput per chip, FLOP/s, keyed by substrings of
# ``jax.Device.device_kind`` (first match wins) — the denominator for MFU
# reporting (used by ``chip_smoke.py`` and the benchmark examples;
# ``benchmarks/peaks.json`` is the benchmark's own table). Sources:
# published TPU specs. Kinds with no entry (TPU7x: whether a device is a
# chip or half of one is not settled here) raise on a TPU backend.
_PEAK_BF16_FLOPS = (
    ("v6", 918e12),
    ("trillium", 918e12),
    ("v5e", 197e12),
    ("v5 lite", 197e12),
    ("v5litepod", 197e12),
    ("v5", 459e12),  # v5p reports "TPU v5"; the v5e spellings match above
    ("v4", 275e12),
    ("v3", 123e12),
    ("v2", 45e12),
)

# Published per-chip HBM bandwidth (bytes/s) — denominator for the MFU
# probe's bandwidth-utilization figure.
_PEAK_HBM_BYTES = (
    ("v6", 1640e9),
    ("trillium", 1640e9),
    ("v5e", 819e9),
    ("v5 lite", 819e9),
    ("v5litepod", 819e9),
    ("v5", 2765e9),
    ("v4", 1228e9),
    ("v3", 900e9),
    ("v2", 700e9),
)


def timed_steps(run_one, n_steps: int, *, lag: int = 2):
    """Time ``n_steps`` calls of ``run_one()`` with a lagged device→host
    read; returns ``(fenced_values, dt_seconds)``.

    ``run_one`` executes one step (keeping its state in a closure) and
    returns a device scalar (typically the loss). Each returned scalar is
    fetched to the host — callers check the values — and transitively
    depends on the previous step's state, so fetching it forces every
    step up to that point; reading with a ``lag``-step delay keeps the
    device pipeline full (steps overlap the host sync) while the final
    drain forces the complete chain before the clock stops.
    """
    import collections
    import time

    fenced = []
    in_flight = collections.deque()
    t0 = time.perf_counter()
    for _ in range(n_steps):
        in_flight.append(run_one())
        if len(in_flight) > lag:
            fenced.append(float(in_flight.popleft()))
    while in_flight:
        fenced.append(float(in_flight.popleft()))
    return fenced, time.perf_counter() - t0


def _lookup_peak(table, device_kind: Optional[str]) -> Optional[float]:
    if device_kind is None:
        device_kind = jax.devices()[0].device_kind
    kind = device_kind.lower()
    for key, peak in table:
        if key in kind:
            return peak
    if jax.default_backend() == "tpu":
        raise ValueError(
            f"no peak entry for TPU device_kind {device_kind!r}; add it to "
            f"the tables in horovod_tpu/profiler.py (known: "
            f"{', '.join(k for k, _ in table)})")
    return None


def device_peak_flops(device_kind: Optional[str] = None) -> Optional[float]:
    """Peak bf16 FLOP/s for a device kind (default: first local device).
    On a TPU backend an unknown kind raises — a utilization must never
    vanish silently on the hardware it is for; off TPU (e.g. ``cpu``)
    it returns None and callers skip MFU reporting."""
    return _lookup_peak(_PEAK_BF16_FLOPS, device_kind)


def device_peak_hbm_bytes(device_kind: Optional[str] = None) -> Optional[float]:
    """Published per-chip HBM bandwidth in bytes/s, same lookup
    convention as :func:`device_peak_flops`."""
    return _lookup_peak(_PEAK_HBM_BYTES, device_kind)
