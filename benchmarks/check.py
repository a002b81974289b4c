"""``run.py --check``: the manifest against the rules the driver refuses a
PR by, the cell's files, and the trace reduction against its hand-made
trace. Needs no chip and touches no JAX device.
"""

import json
import os
import re

from benchmarks import common, trace_reduce

_NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
_UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
_PATH = re.compile(r"^[A-Za-z0-9_.\-/]{1,200}$")
_SOURCES = ("device_trace", "program_span", "program_counter", "host_clock")
_WIDTH = re.compile(r"(_dim|_rank)$|hidden|intermediate|latent|state_size|"
                    r"proj|head_size|n_embd|n_inner|d_model|d_ff|expan|"
                    r"experts_per_tok|width")
_TOP = {"command", "paths", "run_seconds", "configs", "workloads",
        "end_to_end", "per_layer"}
_TRAFFIC_EXT = (".json", ".jsonl", ".toml", ".txt", ".csv")
MAX_RUN_SECONDS = 51


def _line(errors, what, text):
    if not isinstance(text, str) or not 1 <= len(text) <= 200 \
            or "\n" in text or "\t" in text:
        errors.append(f"{what}: 1 to 200 characters on one line, no tab")


def problems(manifest, root=common.ROOT):
    """Every rule the manifest breaks, as text; empty when it is sound."""
    errors = []
    if set(manifest) != _TOP:
        errors.append(f"top-level keys must be exactly {sorted(_TOP)}")
        return errors
    if len(json.dumps(manifest)) > 64 * 1024:
        errors.append("manifest over 64 KiB")
    cmd, paths = manifest["command"], manifest["paths"]
    if not 1 <= len(cmd) <= 32:
        errors.append("command: 1 to 32 strings")
    for word in cmd:
        _line(errors, f"command word {word!r}", word)
        if word.startswith("/") or ".." in word.split("/"):
            errors.append(f"command word {word!r} leaves the repo")
        if "/" in word and not any(
                word == p or word.startswith(p + "/") for p in paths):
            errors.append(f"command names {word!r}, outside paths")
    if not 1 <= len(paths) <= 16:
        errors.append("paths: 1 to 16 directories")
    for p in paths:
        if not _PATH.match(p) or p.startswith("/") or ".." in p.split("/"):
            errors.append(f"path {p!r}: relative, letters digits _ . - /")
    rs = manifest["run_seconds"]
    if not isinstance(rs, int) or not 1 <= rs <= MAX_RUN_SECONDS:
        errors.append(f"run_seconds: a whole number 1..{MAX_RUN_SECONDS}")

    def names_of(group, keys, extra=()):
        seen = set()
        for e in manifest[group]:
            if not set(keys) <= set(e) <= set(keys) | set(extra):
                errors.append(f"{group} entry {e.get('name')!r}: keys must be "
                              f"{sorted(keys)} (+ {sorted(extra)})")
            n = e.get("name", "")
            if not _NAME.match(n):
                errors.append(f"{group} name {n!r}: letters digits _ . -, "
                              "at most 64")
            if n in seen:
                errors.append(f"{group} name {n!r} appears twice")
            seen.add(n)
        return seen

    configs = names_of("configs", ("name", "source", "file", "reduced", "why"))
    cells = names_of("workloads", ("name", "config", "traffic", "chips", "why"))
    e2e = names_of("end_to_end", ("name", "unit", "better", "bound", "source"),
                   ("workloads",))
    layer = names_of("per_layer", ("name", "unit", "better", "source",
                                   "layer", "moves"), ("workloads",))
    for n in e2e & layer:
        errors.append(f"metric name {n!r} is both end-to-end and per-layer")
    for group, lo, hi in (("configs", 1, 24), ("workloads", 1, 24),
                          ("end_to_end", 1, 16), ("per_layer", 1, 128)):
        if not lo <= len(manifest[group]) <= hi:
            errors.append(f"{group}: {lo} to {hi} entries")

    files = set()
    for c in manifest["configs"]:
        _line(errors, f"config {c['name']} source", c.get("source"))
        _line(errors, f"config {c['name']} why", c.get("why"))
        f = c.get("file", "")
        if not any(f.startswith(p + "/") for p in paths):
            errors.append(f"config file {f!r} is not under paths")
        if f in files:
            errors.append(f"config file {f!r} serves two configurations")
        files.add(f)
        if not os.path.isfile(os.path.join(root, f)):
            errors.append(f"config file {f!r} does not exist")
        if len(c.get("reduced", ())) > 16:
            errors.append(f"config {c['name']}: reduced has over 16 keys")
        for key in c.get("reduced", ()):
            if not _NAME.match(key):
                errors.append(f"reduced key {key!r}: not a name")
            if _WIDTH.search(key):
                errors.append(f"config {c['name']}: reduced names a width, "
                              f"{key!r}")
        if c["name"] not in {w["config"] for w in manifest["workloads"]}:
            errors.append(f"config {c['name']!r} is used by no cell")

    pairs, four = set(), 0
    bench = os.path.join(root, "benchmarks")
    for w in manifest["workloads"]:
        _line(errors, f"workload {w['name']} why", w.get("why"))
        if w.get("config") not in configs:
            errors.append(f"workload {w['name']}: unknown config "
                          f"{w.get('config')!r}")
        if not _NAME.match(str(w.get("traffic", ""))):
            errors.append(f"workload {w['name']}: traffic is not a name")
        if w.get("chips") not in (1, 4):
            errors.append(f"workload {w['name']}: chips is 1 or 4")
        four += w.get("chips") == 4
        pair = (w.get("config"), w.get("traffic"))
        if pair in pairs:
            errors.append(f"configuration and traffic {pair} appear twice")
        pairs.add(pair)
        wl_file = os.path.join(bench, "workloads", w["name"] + ".json")
        if not os.path.isfile(wl_file):
            errors.append(f"workload {w['name']}: no {wl_file}")
            continue
        with open(wl_file) as f:
            wl = json.load(f)
        if not os.path.isfile(os.path.join(
                bench, "jobs", str(wl.get("job")) + ".py")):
            errors.append(f"workload {w['name']}: no job kind "
                          f"{wl.get('job')!r} under benchmarks/jobs")
        if not any(os.path.isfile(os.path.join(
                bench, "traffic", w["traffic"] + ext))
                for ext in _TRAFFIC_EXT):
            errors.append(f"workload {w['name']}: no traffic data file "
                          f"benchmarks/traffic/{w['traffic']}.*")
    if four > max(1, len(manifest["workloads"]) // 4):
        errors.append(f"{four} four-chip cells: at most a quarter of "
                      f"{len(manifest['workloads'])} cells, and one always")

    def cells_of(metric):
        return set(metric.get("workloads", cells))

    e2e_by_name = {m["name"]: m for m in manifest["end_to_end"]}
    if "setup_s" not in e2e_by_name:
        errors.append("end_to_end lacks setup_s")
    for m in manifest["end_to_end"] + manifest["per_layer"]:
        if not _UNIT.match(str(m.get("unit", ""))):
            errors.append(f"metric {m['name']}: unit {m.get('unit')!r}")
        if m.get("better") not in ("lower", "higher"):
            errors.append(f"metric {m['name']}: better is lower or higher")
        if m.get("source") not in _SOURCES:
            errors.append(f"metric {m['name']}: source {m.get('source')!r}")
        unknown = cells_of(m) - cells
        if unknown:
            errors.append(f"metric {m['name']}: unknown cells "
                          f"{sorted(unknown)}")
    for m in manifest["end_to_end"]:
        if m.get("source") not in ("host_clock", "device_trace"):
            errors.append(f"end-to-end metric {m['name']}: source is "
                          "host_clock or device_trace")
        b = m.get("bound")
        if not isinstance(b, (int, float)) or not 0.01 <= b <= 0.1:
            errors.append(f"end-to-end metric {m['name']}: bound 0.01..0.1")
    for m in manifest["per_layer"]:
        _line(errors, f"metric {m['name']} layer", m.get("layer"))
        moved = e2e_by_name.get(m.get("moves"))
        if moved is None:
            errors.append(f"per_layer metric {m['name']} moves "
                          f"{m.get('moves')!r}, which is no end-to-end metric")
            continue
        # the rule that refused PR 22
        for c in sorted(cells_of(m) - cells_of(moved)):
            errors.append(
                f"per_layer metric {m['name']} is reported on workload {c}, "
                f"where {moved['name']}, which it should move, is not")
        reader = os.path.join(bench, "layer_metrics", m["name"] + ".py")
        if not os.path.isfile(reader):
            errors.append(f"per_layer metric {m['name']}: no reader {reader}")
        if ("roofline" in m["name"] or "mfu" in m["name"]) \
                and m.get("unit") != "%":
            errors.append(f"metric {m['name']}: a share of a peak has unit %")
    for c in cells:
        mine = [m for m in manifest["end_to_end"] if c in cells_of(m)]
        if "setup_s" not in {m["name"] for m in mine} or len(mine) < 2:
            errors.append(f"cell {c}: reports setup_s and one more "
                          "end-to-end metric")
        if not any(c in cells_of(m) for m in manifest["per_layer"]):
            errors.append(f"cell {c}: reports no per-layer metric")
    runs = 2 + 14 * 24
    fit = runs * (rs + 60) + 24 * 2 * 90 + 1200 if isinstance(rs, int) else 0
    if fit > 43200:
        errors.append(f"run_seconds {rs}: a full check of 24 cells takes "
                      f"{fit} s, over 43200")
    return errors


def main():
    with open(common.MANIFEST) as f:
        manifest = json.load(f)
    errors = problems(manifest)
    if errors:
        raise SystemExit("BENCHMARK.json:\n  " + "\n  ".join(errors))
    trace_reduce.self_check(common.load_json("trace_sample.json"))
    print(f"check ok: {len(manifest['workloads'])} cell(s), "
          f"{len(manifest['end_to_end'])} end-to-end and "
          f"{len(manifest['per_layer'])} per-layer metrics; trace reduction "
          "matches trace_sample.json")
