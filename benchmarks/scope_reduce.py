"""From a profiler trace to the program's own terms: device time by phase
(forward, backward, optimizer, sync), by named kernel, the share no scope
accounts for, the share of the sync that runs hidden behind other work, and
the host time the program's own spans take per step.

A device event names an HLO instruction and carries no scope, so each
``XLA Ops`` event is joined through its leading ``%name`` to
``horovod_tpu.profiler.scope_table()`` (instruction -> ``op_name``, HLO
kind, read from the live executables) and classed by
``profiler.scope_of``. Times are **unions of intervals per scope**
(``trace_reduce.merge / total / subtract``), so a ``while`` and the
operations of its body are not counted twice, and the phases partition the
busy time by ``scope_of``'s precedence: they and the unattributed rest sum
to it exactly.

The arithmetic works on plain lists, as ``trace_reduce``'s does, and is held
against the hand-made trace in ``scope_sample.json``
(``tests/test_trace_scopes.py``). ``load_xplane`` is the only function that
reads the profiler's file; ``for_cell`` the only one that asks the program
for its table. On a program without ``profiler.scope_table`` (this
benchmark laid over an older commit) ``for_cell`` returns nothing and every
reader of it leaves its metric out.
"""

import functools
import glob
import json
import os
import re
import statistics
import sys

from benchmarks import common, trace_reduce

#: ``profiler.scope_of``'s phases, in its precedence
PHASES = ("sync", "optimizer", "backward", "forward")
#: HLO kinds whose event spans the events of a called computation: they
#: count for their own scope, and never as "another operation beside"
_CONTAINERS = ("while", "conditional", "call")
#: the program's host spans (``horovod_tpu/profiler.py``'s list)
HOST_SPAN_PREFIX = "hvd."
STEP_SPAN, FEED_SPAN = "hvd.step", "hvd.shard_batch"
DISPATCH_SPAN = "hvd.step/dispatch"

_EVENT_INSTRUCTION = re.compile(r"^%?([^\s=]+)")
_MODULE_RUN = re.compile(r"^(.*)\(\d+\)$")


def instruction_of(event_name):
    """The instruction a device event names: its leading ``%name``."""
    return _EVENT_INSTRUCTION.match(event_name).group(1)


def module_of(event_name):
    """``jit_step(2767699925973090214)`` -> ``jit_step``."""
    m = _MODULE_RUN.match(event_name)
    return m.group(1) if m else event_name


def pick_tables(tables, modules, instructions):
    """Of the scope table's modules, those the trace ran, merged into one
    ``{instruction: (op_name, kind)}``. A module name that repeats in the
    table (``jit_step``, ``jit_step#2``: the reference's step is live too)
    is settled by which candidate knows most of the trace's instructions
    and, between equals, carries more of the program's names."""
    picked = {}
    for name in modules:
        candidates = [t for key, t in tables.items()
                      if key == name or key.startswith(name + "#")]
        if candidates:
            picked.update(max(candidates, key=lambda t: (
                sum(i in t for i in instructions),
                sum("hvd." in op for op, _ in t.values()))))
    return picked


def classify(events, table, scope_of):
    """``(phase, kernel, kind, start, end)`` per device event; an instruction
    the table lacks has phase and kernel ``None`` and kind ``""``."""
    memo, out = {}, []
    for name, start, dur in events:
        inst = instruction_of(name)
        if inst not in memo:
            entry = table.get(inst)
            memo[inst] = ((*scope_of(*entry), entry[1]) if entry
                          else (None, None, ""))
        out.append((*memo[inst], start, start + dur))
    return out


def reduce_device(classified):
    """One device's split, in nanoseconds."""
    def union(keep):
        return trace_reduce.merge(
            [(s, e) for p, k, kind, s, e in classified if keep(p, k, kind)])

    busy = union(lambda p, k, kind: True)
    parts, covered = {}, []
    for phase in PHASES:
        mine = union(lambda p, k, kind: p == phase)
        parts[phase] = trace_reduce.total(
            trace_reduce.subtract(mine, covered))
        covered = trace_reduce.merge(covered + mine)
    sync = union(lambda p, k, kind: p == "sync")
    beside = union(lambda p, k, kind: p != "sync"
                   and kind not in _CONTAINERS)
    kernels = {}
    for name in {k for _, k, _, _, _ in classified if k}:
        kernels[name] = trace_reduce.total(
            union(lambda p, k, kind: k == name))
    # what the unattributed time is made of, by HLO kind, for PERF.md
    unknown = {}
    for kind in {kind for p, _, kind, _, _ in classified if p is None}:
        alone = trace_reduce.total(trace_reduce.subtract(
            union(lambda p, k, kd: p is None and kd == kind), covered))
        if alone:
            unknown[kind or "not in the table"] = alone
    return {
        "busy": trace_reduce.total(busy),
        "phases": parts,
        "unattributed": trace_reduce.total(
            trace_reduce.subtract(busy, covered)),
        "sync_total": trace_reduce.total(sync),
        "sync_hidden": trace_reduce.total(sync) - trace_reduce.total(
            trace_reduce.subtract(sync, beside)),
        "kernels": kernels,
        "unattributed_kinds": sorted(unknown.items(), key=lambda x: -x[1]),
    }


def host_per_step(host):
    """Per ``hvd.step`` span, in nanoseconds: the step's own host time, the
    part of it outside ``hvd.step/dispatch`` (the hooks and the metrics
    block), and the ``hvd.shard_batch`` time fed since the step before."""
    steps = sorted((s, d) for n, s, d in host if n == STEP_SPAN)
    dispatch = sorted((s, d) for n, s, d in host if n == DISPATCH_SPAN)
    feeds = sorted((s, d) for n, s, d in host if n == FEED_SPAN)
    out, last = [], float("-inf")
    for start, dur in steps:
        inside = sum(d for s, d in dispatch if start <= s < start + dur)
        fed = sum(d for s, d in feeds if last <= s < start)
        out.append({"step": dur, "hooks": dur - inside, "feed": fed})
        last = start
    return out


def reduce(trace, tables, scope_of):
    """The run's numbers in seconds: phases, unattributed time and kernels
    of the busiest device, the sync's hidden share of the device that hides
    least, and the host's medians per step."""
    instructions = {instruction_of(n) for ev in trace["devices"].values()
                    for n, _, _ in ev}
    table = pick_tables(tables, trace["modules"], instructions)
    # a module with none of the program's names is a program without them
    # (or an executable that an older one left in the compile cache)
    if not trace["devices"] or not any(
            "hvd." in op for op, _ in table.values()):
        return None
    devs = {name: reduce_device(classify(ev, table, scope_of))
            for name, ev in sorted(trace["devices"].items())}
    worst = max(devs.values(), key=lambda d: d["busy"])
    hidden = [d["sync_hidden"] / d["sync_total"] for d in devs.values()
              if d["sync_total"]]
    out = {
        "busy_s": worst["busy"] * 1e-9,
        "phases_s": {p: v * 1e-9 for p, v in worst["phases"].items()},
        "unattributed_s": worst["unattributed"] * 1e-9,
        "unattributed_kinds": [[k, v * 1e-9] for k, v
                               in worst["unattributed_kinds"][:8]],
        "kernels_s": {k: max(d["kernels"].get(k, 0.0) for d in devs.values())
                      * 1e-9 for k in worst["kernels"]},
        "sync_s": worst["sync_total"] * 1e-9,
        "sync_hidden_share": min(hidden) if hidden else None,
    }
    steps = host_per_step(trace.get("host", ()))
    if steps:
        out["host_steps"] = len(steps)
        out["host_feed_s_p50"] = statistics.median(
            s["step"] + s["feed"] for s in steps) * 1e-9
        out["host_hooks_s_p50"] = statistics.median(
            s["hooks"] for s in steps) * 1e-9
    return out


def load_xplane(trace_dir):
    """The newest ``.xplane.pb`` under ``trace_dir`` as ``{"modules": [name],
    "devices": {plane: [(name, start_ns, dur_ns)]}, "host": [(name, start_ns,
    dur_ns)]}``: the module names of the ``XLA Modules`` lines, the ``XLA
    Ops`` events of each device plane, the program's ``hvd.*`` host spans."""
    from jax.profiler import ProfileData

    files = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    out = {"modules": [], "devices": {}, "host": []}
    if not files:
        return out
    for plane in ProfileData.from_file(files[-1]).planes:
        if plane.name.startswith("/device:"):
            for line in plane.lines:
                if line.name == "XLA Modules":
                    for e in line.events:
                        name = module_of(e.name)
                        if name not in out["modules"]:
                            out["modules"].append(name)
                elif line.name == "XLA Ops":
                    out["devices"].setdefault(plane.name, []).extend(
                        (e.name, float(e.start_ns), float(e.duration_ns))
                        for e in line.events)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                out["host"].extend(
                    (e.name.split("#")[0], float(e.start_ns),
                     float(e.duration_ns))
                    for e in line.events
                    if e.name.startswith(HOST_SPAN_PREFIX))
    return out


def for_cell(ctx):
    """The reduction of the cell's newest traced run (``.bench_trace/<cell>``,
    where ``jobs/train.py`` writes it), made once per run and shared by the
    readers; its summary goes to stderr as one ``[scopes]`` line. Nothing
    where the program has no scope table or the trace no module of it."""
    return _for_cell(ctx["cell"].name)


@functools.lru_cache(maxsize=None)
def _for_cell(cell):
    from horovod_tpu import profiler

    if not hasattr(profiler, "scope_table"):
        return None
    trace_dir = os.path.join(common.ROOT, ".bench_trace", cell)
    trace, tables = load_xplane(trace_dir), profiler.scope_table()
    red = reduce(trace, tables, profiler.scope_of)
    print(f"[scopes] {json.dumps(red)}", file=sys.stderr)
    # the table dies with this process: keep the traced modules' part beside
    # the trace, for ``tools/scope_look.py``
    with open(os.path.join(trace_dir, "scope_table.json"), "w") as f:
        json.dump({k: t for k, t in tables.items()
                   if k.split("#")[0] in trace["modules"]}, f)
    return red


def per_step_ms(ctx, pick):
    """``pick(reduction)`` seconds over the traced steps, as ms a step."""
    red = for_cell(ctx)
    if not red or not ctx["traced_steps"]:
        return None
    value = pick(red)
    return None if value is None else 1e3 * value / ctx["traced_steps"]


def tables_from_json(obj):
    """A scope table back from JSON, where its pairs became lists."""
    return {m: {i: tuple(v) for i, v in t.items()} for m, t in obj.items()}


def self_check(sample):
    """The reduction against the hand-made trace's known answers."""
    from horovod_tpu import profiler

    red = reduce(sample["trace"], tables_from_json(sample["table"]),
                 profiler.scope_of)
    flat = {"busy_s": red["busy_s"], "unattributed_s": red["unattributed_s"],
            "sync_hidden_share": red["sync_hidden_share"],
            "host_feed_s_p50": red["host_feed_s_p50"],
            "host_hooks_s_p50": red["host_hooks_s_p50"],
            **{"phase." + p: v for p, v in red["phases_s"].items()},
            **{"kernel." + k: v for k, v in red["kernels_s"].items()}}
    want = sample["expect"]
    bad = {k: (flat.get(k), want[k]) for k in want
           if flat.get(k) is None
           or abs(flat[k] - want[k]) > 1e-9 * max(abs(want[k]), 1e-30)}
    if bad:
        raise SystemExit(f"scope_reduce self-check failed (got, want): {bad}")
