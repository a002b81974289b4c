"""What every job kind shares: finding a cell's files by the names in
``BENCHMARK.json``, the compile log, the device record, the result line.

Nothing here touches JAX at import, so ``run.py --check`` stays off the
backend.
"""

import importlib.util
import json
import os
import sys

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)
MANIFEST = os.path.join(ROOT, "BENCHMARK.json")

_BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"
_CACHE_HIT = "/jax/compilation_cache/cache_hits"


def load_json(*parts):
    with open(os.path.join(BENCH_DIR, *parts)) as f:
        return json.load(f)


def load_module(kind, name):
    """``benchmarks/<kind>/<name>.py`` as a module. Names may hold dots
    (``step_mfu.train``), so this loads by path, not by import."""
    path = os.path.join(BENCH_DIR, kind, name + ".py")
    if not os.path.isfile(path):
        raise FileNotFoundError(f"no {kind} file for {name!r}: {path}")
    mod_name = "benchmarks_%s_%s" % (kind, name.replace(".", "_").replace("-", "_"))
    if mod_name in sys.modules:
        return sys.modules[mod_name]
    spec = importlib.util.spec_from_file_location(mod_name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[mod_name] = mod
    spec.loader.exec_module(mod)
    return mod


class Cell:
    """One entry of ``workloads`` with every file it names, loaded."""

    def __init__(self, name, chips, config_name, config, workload, traffic,
                 manifest):
        self.name, self.chips, self.config_name = name, chips, config_name
        self.config, self.workload, self.traffic = config, workload, traffic
        self.manifest = manifest

    @classmethod
    def load(cls, name):
        with open(MANIFEST) as f:
            manifest = json.load(f)
        entry = next((w for w in manifest["workloads"] if w["name"] == name),
                     None)
        if entry is None:
            raise SystemExit(
                f"unknown workload {name!r}; BENCHMARK.json has "
                f"{[w['name'] for w in manifest['workloads']]}")
        conf = next(c for c in manifest["configs"]
                    if c["name"] == entry["config"])
        with open(os.path.join(ROOT, conf["file"])) as f:
            config = json.load(f)
        return cls(name, entry["chips"], entry["config"], config,
                   load_json("workloads", name + ".json"),
                   load_json("traffic", entry["traffic"] + ".json"), manifest)

    def reported(self, group):
        """Metric entries of ``end_to_end`` / ``per_layer`` this cell reports."""
        return [m for m in self.manifest[group]
                if "workloads" not in m or self.name in m["workloads"]]

    def module(self, kind):
        """The configuration's ``reference`` / ``adapters`` / ``flops``
        module: the family its file names, else one of its own name."""
        key = {"adapters": "adapter"}.get(kind, kind)
        return load_module(kind, self.config.get(key, self.config_name))


def peaks_for(device_kind):
    table = load_json("peaks.json")
    if device_kind not in table:
        raise SystemExit(
            f"no peaks for device kind {device_kind!r} in benchmarks/peaks.json "
            f"(has {sorted(k for k in table if not k.startswith('_'))}); "
            f"a share of a peak needs a published peak")
    return table[device_kind]


class CompileLog:
    """Executables built (compiled, or fetched from the persistent cache)
    and the seconds that took, as ``jax.monitoring`` reports them. Copied
    from ``chip_smoke.CompileLog``."""

    def __init__(self):
        import jax.monitoring as monitoring

        self.built = 0
        self.hits = 0
        self.seconds = 0.0
        monitoring.register_event_duration_secs_listener(self._on_duration)
        monitoring.register_event_listener(self._on_event)

    def _on_duration(self, event, duration, **_):
        if event == _BACKEND_COMPILE:
            self.built += 1
            self.seconds += duration

    def _on_event(self, event, **_):
        if event == _CACHE_HIT:
            self.hits += 1

    def mark(self):
        return (self.built, self.hits, self.seconds)

    def since(self, mark):
        return (self.built - mark[0], self.hits - mark[1],
                self.seconds - mark[2])


def place_compile_cache():
    """The persistent compile cache: where ``JAX_COMPILATION_CACHE_DIR``
    says, else ``<checkout>/.jax_cache`` (a fixed path: the path is part
    of the cache's key). Every executable is kept, however quick its
    compile: PR 21's warm run still spent 22 s on programs under JAX's
    1 s threshold."""
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = os.path.join(ROOT, ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return path


def split_seed(seed):
    """``--seed`` may pass 2**31: two 31-bit halves, both traced, so one
    compiled generator serves every seed."""
    import numpy as np

    seed = int(seed)
    if seed < 0:
        raise SystemExit(f"--seed must be a whole number >= 0, got {seed}")
    return np.int32(seed & 0x7FFFFFFF), np.int32((seed >> 31) & 0x7FFFFFFF)


def device_record(devices, *, require_chip, chips):
    """The ``device`` key of the result line; leaves the run where JAX found
    no accelerator or too few chips."""
    import jax

    dev = devices[0]
    if require_chip and dev.platform == "cpu":
        raise SystemExit(
            "benchmark: JAX found no accelerator (platform=cpu); a cell is "
            "measured on the chip only")
    if len(devices) < chips:
        raise SystemExit(f"benchmark: the cell needs {chips} chip(s), JAX "
                         f"sees {len(jax.devices())}")
    return {"platform": dev.platform, "kind": dev.device_kind,
            "count": len(jax.devices())}


def memory_peak_bytes(devices):
    """The fullest chip's peak: the allocator's ``peak_bytes_in_use`` (live
    arrays: state, batches) plus ``peak_bytes_reserved``, the scratch the
    runtime sets aside for the compiled step's temporaries and does not
    count among the arrays (GPT-2 step: 6.8 + 6.2 GB against a compiled
    11.2 GB + 1.6 GB of first weights; my chip run, PR 25)."""
    peaks = []
    for d in devices:
        stats = d.memory_stats() or {}
        if "peak_bytes_in_use" in stats:
            peaks.append(int(stats["peak_bytes_in_use"])
                         + int(stats.get("peak_bytes_reserved", 0)))
    return max(peaks) if peaks else 0


def print_result(result, checks):
    """The numbers compared, each beside its limit, as the last lines of
    stderr; then the one JSON line, ``checks`` as its last key."""
    for name, c in checks.items():
        print(f"check {name} value={c['value']!r} limit={c['limit']!r} "
              f"ok={c['ok']}", file=sys.stderr)
    sys.stderr.flush()
    result = dict(result)
    result["checks"] = checks
    print(json.dumps(result), flush=True)
