"""The float cell ``float448-gui-serial``: its configuration, mix and
manifest entries; a whole run at a tiny size on the CPU (correct, and the
bfloat16 control not); and the two per-layer metrics it adds, which read
nothing from a program whose answers carry no ``resident_bytes``."""

import copy
import importlib.util
import json
import os
import types

import _bench_path
import pytest

from mbench import cell, manifest, traffic

ROOT = _bench_path.ROOT
PATHS = cell.Paths(ROOT)
CELL = "float448-gui-serial"
METRICS = ("float_verify_step_roofline", "float_verify_mb_per_query")
# 48 × 48 = 18 lanes of 128: the float rows the device holds in lanes
TINY = {"n_masks": 192, "height": 48, "width": 48,
        "tier_settings": {"tenant_rate": 1e6, "tenant_burst": 1e6,
                          "queue_depth": 256, "batch_max": 32}}
SMALL_POOL = {"per_client": 32}


def _json(*parts):
    with open(os.path.join(ROOT, *parts)) as f:
        return json.load(f)


def test_config_mix_and_manifest_entries():
    man = _json("BENCHMARK.json")
    assert manifest.validate(man, ROOT) == []
    entry = manifest.cell(man, CELL)
    assert (entry["config"], entry["traffic"], entry["chips"]) == \
        ("paper-float-448", "gui-float-serial", 1)
    assert manifest.config_entry(man, "paper-float-448")["reduced"] == \
        ["n_masks"]
    layers = manifest.metrics_for(man, "per_layer", CELL)
    assert [m["name"] for m in layers] == list(METRICS)
    assert all(m["moves"] == "qps" for m in layers)
    assert {m["name"] for m in manifest.metrics_for(
        man, "end_to_end", CELL)} == {"qps", "setup_s"}

    cfg = _json("bench", "configs", "paper-float-448.json")
    packed = _json("bench", "configs", "paper-packed-448.json")
    assert set(cfg) == set(packed)
    assert (cfg["tier"], cfg["precision"], cfg["n_masks"]) == \
        ("float", "float32", 9000)
    assert cfg["published"] == {"n_masks": 22275, "height": 448,
                                "width": 448}
    assert (cfg["height"], cfg["width"]) == (448, 448)
    for key in ("assumed", "service", "tier_settings", "guarantees",
                "chi_grid", "chi_bins", "roles"):
        assert cfg[key] == packed[key], key

    mix = traffic.load_mix(PATHS.bench, "gui-float-serial")
    binary = traffic.load_mix(PATHS.bench, "gui-binary-serial")
    for key in ("loop", "clients", "per_client", "tenants"):
        assert mix[key] == binary[key]
    assert len(mix["templates"]) == len(binary["templates"])
    for t, b in zip(mix["templates"], binary["templates"]):
        assert set(t) == set(b)
        for key in t:
            want = {"bins": 16} if key in ("range", "pred_range") else b[key]
            assert t[key] == want, (t["kind"], key)


@pytest.mark.parametrize("more", [0, 1], ids=["half", "over_half"])
def test_four_chip_cells_are_held_to_half(more):
    """The manifest now has more than one cell: as many four-chip cells
    again as it has cells make half of them and pass, one more is
    refused."""
    man = _json("BENCHMARK.json")
    n = len(man["workloads"])
    grown = copy.deepcopy(man)
    grown["workloads"].extend(
        dict(man["workloads"][0], name=f"four{i}", traffic=f"t{i}", chips=4)
        for i in range(n + more))
    problems = manifest.validate(grown, ROOT)
    assert any("four chips" in p for p in problems) == bool(more), problems


def test_pool_ranges_lie_on_bin_edges():
    mix = traffic.load_mix(PATHS.bench, "gui-float-serial")
    pool = [r["spec"] for lst in traffic.closed_lists(mix) for r in lst]
    assert len(pool) == 256
    kinds = {s["kind"] for s in pool}
    assert kinds == set(traffic.KINDS)
    for s in pool:
        for term in (s.get("term"), s.get("pred")):
            if term:
                assert 0 <= term["lv"] < term["uv"] <= 1
                assert (term["lv"] * 16).is_integer()
                assert (term["uv"] * 16).is_integer()


def _traced_run(monkeypatch, seed):
    """A tiny traced run on the CPU, and the context its readers got."""
    seen = {}
    real = cell.read_layers

    def capture(paths, metrics, ctx):
        seen["ctx"] = ctx
        return real(paths, metrics, ctx)

    monkeypatch.setattr(cell, "read_layers", capture)
    out = cell.run(PATHS, CELL, seed, 3.0, True, process_start=0.0,
                   require_tpu=False, cfg_override=TINY,
                   mix_override=SMALL_POOL)
    return out, seen["ctx"]


def test_tiny_run_is_correct_and_reads_the_new_metrics(monkeypatch):
    out, ctx = _traced_run(monkeypatch, 2**33 + 17)
    assert out["correct"] is True
    assert out["checks"]["wrong_answers"]["value"] == 0
    assert out["checks"]["unanswered"]["value"] == 0
    assert out["attempted"] > 0 and out["failed"] == 0

    # every gathered row is a whole stored row; no query gathers more than
    # the store, once per term of its verification
    row = cell.row_bytes(dict(cell.load_cell(PATHS, CELL)[2], **TINY))
    assert row == 48 * 48 * 4
    mb = out["metrics"]["float_verify_mb_per_query"]
    assert mb["unit"] == "MB"
    assert 0 < mb["value"] <= 2 * TINY["n_masks"] * row / 1e6
    assert any(d.get("resident_bytes", 0) % row == 0 and d["resident_bytes"]
               for _, d in ctx.deltas)
    # the CPU has no device plane and no peak: no roofline share
    assert "float_verify_step_roofline" not in out["metrics"]

    # the same window's counters over a device time: bytes at the peak
    # over the float verification steps' seconds
    nbytes = sum(d.get("resident_bytes", 0) for r, d in ctx.deltas
                 if not r.body.get("cache_hit"))
    steps = {"_device_multi_counts": 0.02, "_device_group_counts": 0.01,
             "gather": 0.005, "pair_counts": 0.005,
             "_device_cp_bounds": 1.0}
    chip = types.SimpleNamespace(**dict(
        vars(ctx), hbm_bytes_per_s=819e9,
        trace={"busy_s": 2.0, "modules": steps, "ops": {}, "gaps": []}))
    share = _reader("float_verify_step_roofline").read(chip)
    assert share == pytest.approx(100 * nbytes / 819e9 / 0.04)
    assert 0 < share < 100


def _reader(name):
    path = os.path.join(PATHS.bench, "layers", f"{name}.py")
    spec = importlib.util.spec_from_file_location(f"reader_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.mark.parametrize("name", METRICS)
def test_reader_reads_nothing_without_the_counter(name):
    """A program without ``resident_bytes`` (the parent of this cell) and
    a window with no trace give no reading, and raise nothing."""
    rec = types.SimpleNamespace(op="query", body={"cache_hit": False},
                                req={"session": False,
                                     "spec": {"kind": "topk"}})
    trace = {"busy_s": 1.0, "modules": {"_device_multi_counts": 0.5},
             "ops": {}, "gaps": []}
    old = types.SimpleNamespace(deltas=[(rec, {"n_verified": 256})],
                                trace=trace, hbm_bytes_per_s=819e9)
    assert _reader(name).read(old) is None
    bare = types.SimpleNamespace(deltas=[], trace=None, hbm_bytes_per_s=None)
    assert _reader(name).read(bare) is None


def test_control_fails_the_comparison():
    rows = cell.control(PATHS, CELL, [5, 2**34 + 9, 123], 10.0,
                        require_tpu=False, cfg_override=TINY,
                        mix_override=SMALL_POOL)
    assert all(r["correct"] is False and r["wrong_answers"] > 0
               for r in rows), rows
