"""``BENCHMARK.json``: loading a cell's files by name, and the checks the
manifest must pass before any run."""

from __future__ import annotations

import json
import os
import re

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
TOP_KEYS = {"command", "paths", "run_seconds", "configs", "workloads",
            "end_to_end", "per_layer"}
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


def load(bench_json: str) -> dict:
    with open(bench_json) as f:
        return json.load(f)


def cell(manifest: dict, name: str) -> dict:
    for w in manifest["workloads"]:
        if w["name"] == name:
            return w
    raise KeyError(f"no workload {name!r} in BENCHMARK.json")


def config_entry(manifest: dict, name: str) -> dict:
    for c in manifest["configs"]:
        if c["name"] == name:
            return c
    raise KeyError(f"no config {name!r} in BENCHMARK.json")


def metrics_for(manifest: dict, section: str, workload: str) -> list:
    """The ``section`` metrics a cell reports: those that list it, or
    that list no cells."""
    return [m for m in manifest[section]
            if workload in m.get("workloads", [workload])]


def validate(manifest: dict, root: str) -> list:
    """→ the problems found (empty when the manifest is sound)."""
    bad = []
    if set(manifest) != TOP_KEYS:
        bad.append(f"top-level keys {sorted(manifest)}")
    names = {}
    for section in ("configs", "workloads", "end_to_end", "per_layer"):
        for entry in manifest.get(section, []):
            n = entry.get("name", "")
            if not NAME.match(n):
                bad.append(f"{section} name {n!r}")
            if n in names.get(section, set()):
                bad.append(f"duplicate {section} name {n!r}")
            names.setdefault(section, set()).add(n)
    cells = names.get("workloads", set())
    for m in manifest.get("end_to_end", []) + manifest.get("per_layer", []):
        if not UNIT.match(m.get("unit", "")):
            bad.append(f"unit {m.get('unit')!r} of {m['name']}")
        if m.get("better") not in ("lower", "higher"):
            bad.append(f"better of {m['name']}")
        if m.get("source") not in SOURCES:
            bad.append(f"source of {m['name']}")
        for w in m.get("workloads", []):
            if w not in cells:
                bad.append(f"{m['name']} lists unknown workload {w!r}")
    for m in manifest.get("end_to_end", []):
        if not 0 < m.get("bound", 0) <= 0.25:
            bad.append(f"bound of {m['name']}")
        if m["source"] not in ("host_clock", "device_trace"):
            bad.append(f"end-to-end {m['name']} from {m['source']}")
    e2e = {m["name"] for m in manifest.get("end_to_end", [])}
    if "setup_s" not in e2e:
        bad.append("no setup_s")
    for m in manifest.get("per_layer", []):
        if m.get("moves") not in e2e:
            bad.append(f"{m['name']} moves {m.get('moves')!r}")
        if not os.path.isfile(os.path.join(root, "bench", "layers",
                                           f"{m['name']}.py")):
            bad.append(f"no reader for {m['name']}")
    used = {w["config"] for w in manifest.get("workloads", [])}
    for c in manifest.get("configs", []):
        if c["name"] not in used:
            bad.append(f"config {c['name']} has no cell")
        if not os.path.isfile(os.path.join(root, c["file"])):
            bad.append(f"config file {c['file']} missing")
    pairs = set()
    for w in manifest.get("workloads", []):
        if w["config"] not in names.get("configs", set()):
            bad.append(f"{w['name']} uses unknown config {w['config']!r}")
        if (w["config"], w["traffic"]) in pairs:
            bad.append(f"{w['name']} repeats a config and traffic pair")
        pairs.add((w["config"], w["traffic"]))
        if w.get("chips") not in (1, 4):
            bad.append(f"chips of {w['name']}")
        if not os.path.isfile(os.path.join(root, "bench", "traffic",
                                           f"{w['traffic']}.json")):
            bad.append(f"no traffic file for {w['traffic']}")
    four = sum(w.get("chips") == 4 for w in manifest.get("workloads", []))
    if four > max(len(manifest.get("workloads", [])) // 2, 1):
        bad.append(f"{four} cells on four chips")
    return bad
