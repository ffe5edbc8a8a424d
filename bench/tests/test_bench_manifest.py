"""The manifest's checks, on BENCHMARK.json and on broken copies of it."""

import copy
import json
import os

import _bench_path
import pytest

from mbench import manifest

ROOT = _bench_path.ROOT


@pytest.fixture(scope="module")
def man():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def test_benchmark_json_is_sound(man):
    assert manifest.validate(man, ROOT) == []
    assert man["command"][1] == "bench/run.py"
    for section in ("end_to_end", "per_layer"):
        for m in man[section]:
            assert set(m) <= {"name", "unit", "better", "bound", "source",
                              "layer", "moves", "workloads"}


def _broken(man, change):
    m = copy.deepcopy(man)
    change(m)
    return manifest.validate(m, ROOT)


@pytest.mark.parametrize("change,needle", [
    (lambda m: m["per_layer"][0].update(name="has space"), "name"),
    (lambda m: m["end_to_end"][0].update(unit="tokens per s"), "unit"),
    (lambda m: m["per_layer"][0].update(workloads=["no-such-cell"]),
     "unknown workload"),
    (lambda m: m["workloads"].__setitem__(slice(None), []), "has no cell"),
    (lambda m: m["workloads"].extend(
        dict(m["workloads"][0], name=f"four{i}", traffic=f"t{i}", chips=4)
        for i in range(2)), "four chips"),
    (lambda m: m["end_to_end"][0].update(bound=0.3), "bound"),
    (lambda m: m["per_layer"][0].update(moves="nothing"), "moves"),
    (lambda m: m.update(extra=1), "top-level"),
])
def test_broken_manifest_is_refused(man, change, needle):
    problems = _broken(man, change)
    assert any(needle in p for p in problems), problems


def test_metrics_for_follows_workload_lists(man):
    cell = man["workloads"][0]["name"]
    layers = [m["name"] for m in manifest.metrics_for(man, "per_layer", cell)]
    e2e = [m["name"] for m in manifest.metrics_for(man, "end_to_end", cell)]
    assert "bounds_step_roofline" in layers and "device_idle_share" in layers
    assert "setup_s" in e2e and "qps" in e2e
    listed = dict(man, per_layer=[dict(man["per_layer"][0],
                                       workloads=["another-cell"])])
    assert manifest.metrics_for(listed, "per_layer", cell) == []
