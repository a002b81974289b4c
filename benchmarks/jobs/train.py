"""Job kind ``train``: one cell's training step, built through the program's
public entry (``hvd.init`` -> ``broadcast_parameters`` -> a
``training.make_*_train_step`` builder), driven from the seed through its
first three steps, then for ``--seconds`` through the same call and feed,
then held against the plain reference.

One object — the compiled step with its state — does all of that: the three
steps the reference follows are its warm-up, and the window goes on from
step 4.
"""

import math
import os
import shutil
import sys
import time

from benchmarks import common, compare, trace_reduce
from benchmarks.reference import steps as ref_steps

#: steps the reference follows, also the warm-up, unless the workload file
#: says fewer (``checked_steps``)
CHECKED_STEPS = 3
#: dispatched steps the host may run ahead of the last one it saw complete
IN_FLIGHT = 2


def _find_field(node, name):
    """The first field ``name`` (``mu``, ``trace``) of an optax state,
    wherever a wrapper put it."""
    found = getattr(node, name, None)
    if found is not None and not callable(found):
        return found
    children = (node.values() if isinstance(node, dict)
                else node if isinstance(node, (tuple, list)) else ())
    for child in children:
        found = _find_field(child, name)
        if found is not None:
            return found
    return None


class Program:
    """The timed path: the builder's step with its state, and the feed."""

    def __init__(self, cell, built, hvd):
        from horovod_tpu import training

        wl = cell.workload
        self.built, self.hvd = built, hvd
        self._replicate = training.replicate
        self._shard_batch = training.shard_batch
        tx = built["tx"]
        builder = wl["step_builder"]
        if builder == "jit":
            # the README's path: one global jit, the optimizer wrapper syncs
            tx = hvd.DistributedOptimizer(tx)
            self.step_fn = training.make_jit_train_step(
                built["model"], tx, loss_fn=built["loss_fn"])
        elif builder == "shardmap":
            # the explicit hvd.allreduce -> psum step, plain optax
            self.step_fn = training.make_shardmap_train_step(
                built["model"], tx, loss_fn=built["loss_fn"])
        else:
            raise SystemExit(f"unknown step_builder {builder!r}")
        self.tx = tx

    def load(self, weights):
        """Start from the benchmark's weights: broadcast, replicate, and a
        fresh optimizer state."""
        import jax
        import jax.numpy as jnp

        self.names = list(weights)
        params = self.hvd.broadcast_parameters(self.built["to_tree"](weights))
        self.params = self._replicate(params)
        # a fresh copy: the step donates its state, and a second load (the
        # readings tool's next seed) must not find the first one's deleted
        self.batch_stats = self._replicate(jax.tree_util.tree_map(
            jnp.array, self.built["batch_stats"]))
        self.opt_state = self._replicate(self.tx.init(self.params))

    def step(self, inputs, targets):
        """One training step on one host batch; returns the device loss."""
        self.params, self.batch_stats, self.opt_state, loss = self.step_fn(
            self.params, self.batch_stats, self.opt_state,
            self._shard_batch(inputs), self._shard_batch(targets))
        return loss

    def first_grad(self, opt_cfg):
        """The first gradient as the optimizer got it, from its state after
        one step: Adam's first moment is (1 - b1) g, a momentum trace is g."""
        for field, factor in (("mu", 1.0 - opt_cfg.get("b1", 0.0)),
                              ("trace", 1.0)):
            found = _find_field(self.opt_state, field)
            if found is not None:
                return self.built["ref_names"](found, self.names), factor
        raise SystemExit("optimizer state holds neither mu nor trace")

    def weights(self):
        return self.built["ref_names"](self.params, self.names)

    def free(self):
        import jax

        for leaf in jax.tree_util.tree_leaves(
                (self.params, self.batch_stats, self.opt_state)):
            leaf.delete()
        self.params = self.batch_stats = self.opt_state = None


def _trace_options(wl):
    """Device operations and, at host tracer level 1, the benchmark's own
    host spans; no Python tracer. A workload file lowers the level to 0 where
    the runtime's own level-1 events swamp the feed they observe: the host
    lays a ResNet image batch out for the chip in two million ``Transpose``
    calls, each an event, and the traced steps then run 3x slower than the
    untraced ones."""
    import jax

    options = jax.profiler.ProfileOptions()
    options.host_tracer_level = wl.get("host_tracer_level", 1)
    options.python_tracer_level = 0
    return options


def _span(name):
    import jax

    return jax.profiler.TraceAnnotation(trace_reduce.HOST_SPAN_PREFIX + name)


class _Window:
    """Steps through ``program.step`` with at most ``IN_FLIGHT`` dispatched
    beyond the last seen complete; keeps every loss and the host time at
    which each step was seen complete."""

    def __init__(self, program, pool, next_batch):
        self.program, self.pool, self.i = program, pool, next_batch
        self.pending, self.losses, self.done_at = [], [], []

    def dispatch(self):
        with _span("feed_dispatch"):
            loss = self.program.step(*self.pool[self.i % len(self.pool)])
        self.i += 1
        self.pending.append(loss)
        self.losses.append(loss)
        while len(self.pending) > IN_FLIGHT:
            self._wait_oldest()

    def _wait_oldest(self):
        with _span("wait_step"):
            self.pending.pop(0).block_until_ready()
        self.done_at.append(time.perf_counter())

    def drain(self):
        import jax

        while self.pending:
            self._wait_oldest()
        with _span("wait_step"):
            jax.block_until_ready(self.program.params)
        return time.perf_counter()


def open_cell(cell, *, require_chip=True):
    """What a run and the readings tool both start from: the compile cache,
    ``hvd.init`` on the cell's chips, the device record, the configuration's
    reference and adapter, the traffic generator and the built program."""
    import jax

    cache_dir = common.place_compile_cache()
    import horovod_tpu as hvd

    devices = jax.devices()[:cell.chips]
    device = common.device_record(devices, require_chip=require_chip,
                                  chips=cell.chips)
    hvd.init(devices=devices)
    program = Program(
        cell, cell.module("adapters").build(cell.config, cell.workload), hvd)
    return {"hvd": hvd, "devices": devices, "device": device,
            "cache_dir": cache_dir, "ref": cell.module("reference"),
            "generator": common.load_module("traffic",
                                            cell.traffic["generator"]),
            "program": program}


def checked_steps(wl):
    return wl.get("checked_steps", CHECKED_STEPS)


def first_steps(program, pool, ref, cfg, wl, seed_halves):
    """The program's first three steps, through the window's own call and
    feed: each loss, the first gradient's norm by leaf (from the optimizer's
    state after one step) and the norm of each leaf's change after the
    three. The state goes on into the window as it is."""
    win = _Window(program, pool, 0)
    grad_norms = None
    for _ in range(checked_steps(wl)):
        win.dispatch()
        if grad_norms is None:
            grads, factor = program.first_grad(wl["optimizer"])
            grad_norms = {k: v / factor for k, v in ref_steps.to_floats(
                ref_steps.leaf_norms(grads)).items()}
            del grads
    win.drain()
    w0 = ref.make_weights(cfg, seed_halves)
    update_norms = ref_steps.to_floats(
        ref_steps.diff_norms(program.weights(), w0))
    return {"losses": [float(x) for x in win.losses],
            "grad_norms": grad_norms, "update_norms": update_norms}


def measure_window(program, pool, wl, args, t_start, log, trace_dir):
    """Steps for ``--seconds`` from the state the first steps left. With
    ``--trace 1`` the profiler is on for ``trace_steps`` steps from 40 %
    into the window, between two drains, so the trace holds exactly those."""
    win = _Window(program, pool, checked_steps(wl))
    steady = None
    mark = log.mark()
    t_open = time.perf_counter()
    w = {"setup_s": t_open - t_start, "traced_steps": 0,
         "trace_dir": trace_dir}
    while time.perf_counter() - t_open < args.seconds:
        if args.trace and not w["traced_steps"] \
                and time.perf_counter() - t_open >= 0.4 * args.seconds:
            import jax

            steady_s = win.drain() - t_open
            steady = (len(win.done_at), steady_s)
            shutil.rmtree(trace_dir, ignore_errors=True)
            jax.profiler.start_trace(trace_dir,
                                     profiler_options=_trace_options(wl))
            for _ in range(wl["trace_steps"]):
                win.dispatch()
            win.drain()
            jax.profiler.stop_trace()
            w["traced_steps"] = wl["trace_steps"]
            continue
        win.dispatch()
    w["window_s"] = win.drain() - t_open
    w["built_in_window"] = log.since(mark)[0]
    # the steps before the profiler went on: what the host-clock metrics of
    # a traced run are taken over
    n_steady, steady_s = steady or (len(win.done_at), w["window_s"])
    done = win.done_at[:n_steady]
    w.update(steady_steps=n_steady, steady_s=steady_s,
             step_gaps_s=[b - a for a, b in zip(done, done[1:])])
    return win, w


def traced_metrics(ctx, w, device):
    """The per-layer metrics of a ``--trace 1`` run, each from its own
    reader, and the breakdown. The trace stays on disk until the cell's next
    traced run, for ``tools/trace_look.py``."""
    trace = trace_reduce.load_xplane(w["trace_dir"])
    reduced = trace_reduce.reduce(trace)
    ctx = dict(ctx, trace=trace, reduced=reduced, **{
        k: w[k] for k in ("traced_steps", "steady_steps", "steady_s",
                          "step_gaps_s")})
    metrics = {}
    for m in ctx["cell"].reported("per_layer"):
        value = common.load_module("layer_metrics", m["name"]).read(ctx)
        if value is not None and math.isfinite(value):
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    out = {"metrics": metrics}
    if reduced:
        device["busy_s"] = reduced["busy_s"]
        device["window_s"] = reduced["window_s"]
        out["breakdown"] = {"device_ops": reduced["device_ops"],
                            "idle_gaps": reduced["idle_gaps"]}
    return out


def run(cell, args, *, require_chip=True, t_start=None, break_program=None):
    """Run the cell once. ``break_program`` is the tests' hook: it gets the
    built :class:`Program` and may break the timed path underneath."""
    t_start = time.perf_counter() if t_start is None else t_start
    import jax
    import numpy as np

    phases, t_phase = [f"imports {time.perf_counter() - t_start:.2f}s"], [
        time.perf_counter()]

    def phase(name):
        now = time.perf_counter()
        phases.append(f"{name} {now - t_phase[0]:.2f}s")
        t_phase[0] = now

    wl, cfg = cell.workload, cell.config
    opened = open_cell(cell, require_chip=require_chip)
    hvd, devices, device = opened["hvd"], opened["devices"], opened["device"]
    ref, program = opened["ref"], opened["program"]
    phase("backend+hvd.init")
    log = common.CompileLog()
    seed_halves = common.split_seed(args.seed)
    global_batch = cell.traffic["per_chip_batch"] * cell.chips

    pool = opened["generator"].make(cell.traffic, cfg, args.seed,
                                    global_batch)
    phase("traffic")
    program.load(ref.make_weights(cfg, seed_halves))
    jax.block_until_ready(program.params)
    phase("weights+state")
    if break_program is not None:
        break_program(program)
    observed = first_steps(program, pool, ref, cfg, wl, seed_halves)
    phase("first steps")
    print(f"[setup] {', '.join(phases)}; first losses {observed['losses']}",
          file=sys.stderr)

    setup_compile = log.since((0, 0, 0.0))
    win, w = measure_window(program, pool, wl, args, t_start, log,
                            os.path.join(common.ROOT, ".bench_trace",
                                         cell.name))
    steps = len(win.losses)
    losses = np.asarray(jax.device_get(win.losses), np.float64)
    failed = int((~np.isfinite(losses)).sum())
    device["memory_peak_bytes"] = common.memory_peak_bytes(devices)
    print(f"[memory] {devices[0].memory_stats()}", file=sys.stderr)
    examples_per_s_per_chip = (steps * global_batch / w["window_s"]
                               / cell.chips)
    tokens = cell.traffic.get("seq_len")
    print(f"[window] {steps} steps of {global_batch} examples in "
          f"{w['window_s']:.3f}s on {cell.chips} chip(s): "
          f"{examples_per_s_per_chip:.4f} examples/s/chip"
          + (f", {examples_per_s_per_chip * tokens:.1f} tokens/s/chip"
             if tokens else "")
          + f"; loss {losses[0]:.4f} -> {losses[-1]:.4f}; "
          f"{w['built_in_window']} compilation(s) inside the window; set-up "
          f"{w['setup_s']:.2f}s of which compile {setup_compile[2]:.2f}s "
          f"({setup_compile[0]} executables, {setup_compile[1]} cache hits, "
          f"cache {opened['cache_dir']})", file=sys.stderr)

    from horovod_tpu.observability import metrics as hvd_metrics

    sync_bytes = hvd_metrics.value("grad_sync_bytes_per_step",
                                   mode="allreduce")
    program.free()
    hvd.shutdown()

    # the reference follows the same three batches from the same seed
    t_ref = time.perf_counter()
    reference = ref_steps.first_steps(
        ref, cfg, wl, seed_halves, pool[:checked_steps(wl)])
    print(f"[reference] {checked_steps(wl)} steps in "
          f"{time.perf_counter() - t_ref:.2f}s", file=sys.stderr)
    checks = compare.checks(observed, reference, wl["limits"])
    checks["compilations_in_window"] = {
        "value": w["built_in_window"], "limit": 0,
        "ok": w["built_in_window"] == 0}
    checks["nonfinite_losses"] = {"value": failed, "limit": 0,
                                  "ok": failed == 0}
    correct = all(c["ok"] for c in checks.values())

    values = {"train_examples_per_s_per_chip": examples_per_s_per_chip,
              "setup_s": w["setup_s"]}
    result = {"correct": correct, "attempted": steps, "failed": failed}
    if not args.trace:
        result["metrics"] = {
            m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
            for m in cell.reported("end_to_end")}
    else:
        ctx = {"cell": cell, "global_batch": global_batch,
               "peaks": (common.peaks_for(device["kind"]) if require_chip
                         else None),
               "flops": cell.module("flops"),
               "memory_peak_bytes": device["memory_peak_bytes"],
               "grad_sync_bytes_per_step": sync_bytes}
        result.update(traced_metrics(ctx, w, device))
    result["device"] = device
    return result, checks
