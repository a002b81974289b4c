"""From a profiler trace to numbers: device busy and idle time, the
operations that took most of it, the longest idle gaps and what the host
was doing in them, and the time a collective ran with nothing beside it.

The arithmetic works on plain lists — ``(name, start_ns, duration_ns)`` per
device, and host spans alike — so ``run.py --check`` can hold it against the
hand-made trace in ``trace_sample.json`` without JAX. ``load_xplane`` is
the only function that reads the profiler's own file.
"""

import glob
import os
import re

_COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter",
                "collective-permute", "all-to-all", "collective-broadcast")
#: lines of a device plane that hold device operations, most specific first
_OP_LINES = ("XLA Ops",)
#: host spans the jobs write around their own calls into the program
HOST_SPAN_PREFIX = "bench."


_LAYOUT = re.compile(r"\{[^{}]*\}")
_INSTRUCTION = re.compile(r"^%?\S+ = (.+?) ([a-z][\w\-]*)\(")


def short_name(name):
    """An operation's name without layouts: the trace gives the whole HLO
    instruction."""
    return _LAYOUT.sub("", name)


def op_group(name):
    """What a breakdown sums under: the instruction's kind and result
    shapes, so the 24 layers' calls of one kernel are one entry and not 24
    (``custom-call (bf16[128,1024,64], f32[128,1024,1])``). A name that is
    no HLO instruction stands for itself."""
    name = short_name(name)
    m = _INSTRUCTION.match(name)
    return (f"{m.group(2)} {m.group(1)}" if m else name)[:100].rstrip()


def is_collective(name):
    return any(c in name for c in _COLLECTIVES)


def merge(intervals):
    """Union of ``(start, end)`` intervals, sorted and disjoint."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def total(intervals):
    return sum(e - s for s, e in intervals)


def subtract(a, b):
    """The part of merged intervals ``a`` that no interval of merged ``b``
    covers."""
    out, j = [], 0
    for s, e in a:
        cur = s
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                out.append((cur, b[k][0]))
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            out.append((cur, e))
    return out


def _spans(events):
    return [(s, s + d) for _, s, d in events]


def reduce_device(events, host_spans=()):
    """One device's numbers over its traced window (first operation's start
    to last operation's end), times in seconds."""
    if not events:
        return None
    busy = merge(_spans(events))
    t0, t1 = busy[0][0], busy[-1][1]
    coll = merge(_spans([e for e in events if is_collective(e[0])]))
    rest = merge(_spans([e for e in events if not is_collective(e[0])]))
    by_name = {}
    for name, _, d in events:
        name = op_group(name)
        by_name[name] = by_name.get(name, 0.0) + d
    gaps = []
    for (_, e), (s, _) in zip(busy, busy[1:]):
        gaps.append((_host_label(e, s, host_spans), (s - e) * 1e-9))
    gap_by_label = {}
    for label, sec in gaps:
        gap_by_label[label] = gap_by_label.get(label, 0.0) + sec
    return {
        "window_s": (t1 - t0) * 1e-9,
        "busy_s": total(busy) * 1e-9,
        "collective_s": total(coll) * 1e-9,
        "collective_exposed_s": total(subtract(coll, rest)) * 1e-9,
        "ops": sorted(((n, d * 1e-9) for n, d in by_name.items()),
                      key=lambda x: -x[1]),
        "gaps": sorted(gap_by_label.items(), key=lambda x: -x[1]),
    }


def _host_label(start, end, host_spans):
    """The host span that covers most of an idle gap; ``host:none`` where
    the benchmark's spans are silent there."""
    best, best_cover = "host:none", 0.0
    for name, s, d in host_spans:
        cover = min(end, s + d) - max(start, s)
        if cover > best_cover:
            best, best_cover = "host:" + name, cover
    return best


def reduce(trace):
    """Every device's numbers, and the run's: busy seconds averaged over
    the devices, idle share of the worst one."""
    per_device = {name: reduce_device(ev, trace.get("host", ()))
                  for name, ev in sorted(trace["devices"].items())}
    per_device = {k: v for k, v in per_device.items() if v}
    if not per_device:
        return None
    devs = list(per_device.values())
    worst = max(devs, key=lambda d: 1 - d["busy_s"] / d["window_s"])
    return {
        "per_device": per_device,
        "busy_s": sum(d["busy_s"] for d in devs) / len(devs),
        "window_s": sum(d["window_s"] for d in devs) / len(devs),
        "idle_share_worst": 1 - worst["busy_s"] / worst["window_s"],
        "collective_exposed_s": max(d["collective_exposed_s"] for d in devs),
        "collective_s": max(d["collective_s"] for d in devs),
        "device_ops": [[n, s] for n, s in worst["ops"][:10]],
        "idle_gaps": [[n, s] for n, s in worst["gaps"][:10]],
    }


def ops_matching(trace, predicate):
    """Summed seconds of the matching operations, worst device."""
    sums = [sum(d for n, _, d in ev if predicate(n)) * 1e-9
            for ev in trace["devices"].values()]
    return max(sums, default=0.0)


def load_xplane(trace_dir):
    """The newest ``.xplane.pb`` under ``trace_dir`` as ``{"devices":
    {plane: [(name, start_ns, dur_ns)]}, "host": [(name, start_ns,
    dur_ns)]}``. Device planes are ``/device:...``; of their lines the
    operations' (``XLA Ops``) where present, else all. Host spans are the
    benchmark's own ``bench.*`` annotations."""
    from jax.profiler import ProfileData

    files = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not files:
        return {"devices": {}, "host": []}
    data = ProfileData.from_file(files[-1])
    devices, host = {}, []
    for plane in data.planes:
        if plane.name.startswith("/device:"):
            lines = list(plane.lines)
            picked = [l for l in lines if l.name in _OP_LINES] or lines
            events = [(e.name, float(e.start_ns), float(e.duration_ns))
                      for l in picked for e in l.events]
            if events:
                devices[plane.name] = events
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host.extend(
                    (e.name[len(HOST_SPAN_PREFIX):], float(e.start_ns),
                     float(e.duration_ns))
                    for e in line.events
                    if e.name.startswith(HOST_SPAN_PREFIX))
    return {"devices": devices, "host": host}


def self_check(sample):
    """The reduction against the hand-made trace's known answers."""
    red = reduce(sample["trace"])
    want = sample["expect"]
    got = {"busy_s": red["busy_s"], "window_s": red["window_s"],
           "idle_share_worst": red["idle_share_worst"],
           "collective_exposed_s": red["collective_exposed_s"],
           "top_op": red["device_ops"][0][0],
           "top_gap": red["idle_gaps"][0][0]}
    bad = {k: (got[k], want[k]) for k in want
           if (abs(got[k] - want[k]) > 1e-9 * abs(want[k])
               if isinstance(want[k], float)
               else got[k] != want[k])}
    if bad:
        raise SystemExit(f"trace_reduce self-check failed (got, want): {bad}")
