"""``run.py --check``: the committed manifest passes, and the manifests
that were refused before do not."""

import copy
import json

from benchmarks import check, common, trace_reduce


def _manifest():
    with open(common.MANIFEST) as f:
        return json.load(f)


def test_committed_manifest_is_sound():
    assert check.problems(_manifest()) == []


def test_pr22_manifest_is_refused():
    """A per-layer metric on a cell that does not report the end-to-end
    metric it moves: what the driver refused PR 22 for."""
    m = copy.deepcopy(_manifest())
    first = m["workloads"][0]["name"]
    m["end_to_end"][0]["workloads"] = [first]
    m["end_to_end"].append({"name": "serve_ttft_p95_ms", "unit": "ms",
                            "better": "lower", "bound": 0.05,
                            "source": "host_clock",
                            "workloads": [w["name"] for w in m["workloads"][1:]]})
    for pl in m["per_layer"]:
        pl.pop("workloads", None)
    errors = check.problems(m)
    moved = m["end_to_end"][0]["name"]
    assert any(f"where {moved}, which it should move, is not" in e
               for e in errors), errors


def test_rules_the_driver_refuses_by():
    m = copy.deepcopy(_manifest())
    m["run_seconds"] = 52
    m["end_to_end"][0]["unit"] = "examples per second"
    m["end_to_end"][0]["bound"] = 0.2
    m["workloads"][0]["chips"] = 2
    m["configs"][0]["reduced"] = ["n_embd"]
    m["per_layer"][0]["why"] = "no such key"
    errors = "\n".join(check.problems(m))
    for needle in ("run_seconds", "unit", "bound", "chips is 1 or 4",
                   "names a width", "keys must be"):
        assert needle in errors, (needle, errors)


def test_a_second_four_chip_cell_needs_eight_cells():
    m = copy.deepcopy(_manifest())
    for w in m["workloads"][:2]:
        w["chips"] = 4
    if len(m["workloads"]) < 8:
        assert any("four-chip" in e for e in check.problems(m))


def test_trace_reduction_on_the_hand_made_trace():
    sample = common.load_json("trace_sample.json")
    trace_reduce.self_check(sample)
    red = trace_reduce.reduce(sample["trace"])
    dev0 = red["per_device"]["/device:TPU:0"]
    assert abs(dev0["collective_s"] - 60e-9) < 1e-18
    assert abs(dev0["collective_exposed_s"] - 40e-9) < 1e-18
    assert trace_reduce.subtract([(0, 10)], [(2, 3), (5, 20)]) == [
        (0, 2), (3, 5)]
    assert trace_reduce.merge([(5, 7), (0, 2), (1, 3)]) == [(0, 3), (5, 7)]
