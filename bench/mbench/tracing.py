"""From the JAX profiler's trace to device time.

:func:`collect` reads an ``.xplane.pb`` with nothing but JAX and keeps the
events of the device planes (``/device:TPU:n``) plus the host planes'
events, as plain ``(plane, line, name, start_ns, duration_ns)`` tuples.
:func:`reduce` turns those into what the metrics read:

* ``busy_s``: per chip, the union of the intervals in which an operation
  ran (the ``XLA Ops`` line), averaged over the chips;
* ``modules``: device seconds per jitted program (the ``XLA Modules``
  line), by the program's name without JAX's ``jit_`` prefix and the
  ``(id)`` suffix, e.g. ``_device_cp_bounds``;
* ``ops``: device seconds per operation name;
* ``gaps``: the device's idle intervals, longest first, each labelled
  with the host event that overlaps it most (what the host was doing).
"""

from __future__ import annotations

import glob
import os
import re

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
_SUFFIX = re.compile(r"\(\d+\)$")


def collect(trace_dir: str) -> list:
    from jax.profiler import ProfileData
    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True))
    events = []
    for path in paths:
        pd = ProfileData.from_file(path)
        for plane in pd.planes:
            device = plane.name.startswith("/device:")
            host = plane.name.startswith("/host:")
            if not (device or host):
                continue
            for line in plane.lines:
                if device and line.name not in (OPS_LINE, MODULES_LINE):
                    continue
                for ev in line.events:
                    events.append((plane.name, line.name, ev.name,
                                   float(ev.start_ns), float(ev.duration_ns)))
    return events


def module_name(name: str) -> str:
    name = _SUFFIX.sub("", name.strip())
    return name[4:] if name.startswith("jit_") else name


def _union(intervals: list) -> list:
    out: list = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def reduce(events: list, *, top: int = 10) -> dict:
    """Device time from collected events; ``busy_s`` is ``None`` when no
    device operation ran."""
    chips: dict = {}
    modules: dict = {}
    ops: dict = {}
    host: list = []
    for plane, line, name, start, dur in events:
        if plane.startswith("/host:"):
            if dur > 0:
                host.append((start, start + dur, name))
            continue
        if line == OPS_LINE:
            chips.setdefault(plane, []).append((start, start + dur))
            label = op_label(name)
            ops[label] = ops.get(label, 0.0) + dur * 1e-9
        elif line == MODULES_LINE:
            m = module_name(name)
            modules[m] = modules.get(m, 0.0) + dur * 1e-9
    if not chips:
        return {"busy_s": None, "modules": modules, "ops": ops, "gaps": []}
    busy = 0.0
    gaps = []
    for plane, iv in sorted(chips.items()):
        u = _union(iv)
        busy += sum(e - s for s, e in u) * 1e-9
        if plane == min(chips):          # gaps are read on the first chip
            gaps = [(u[i][1], u[i + 1][0]) for i in range(len(u) - 1)]
    gaps = sorted(gaps, key=lambda g: g[0] - g[1])[:top]
    return {"busy_s": busy / len(chips), "modules": modules, "ops": ops,
            "gaps": [[_label(g, host), (g[1] - g[0]) * 1e-9] for g in gaps]}


def _label(gap: tuple, host: list) -> str:
    """The host event that overlaps the gap most, among events at most
    four times its length (longer ones, a thread's whole run, say
    nothing about the gap)."""
    best, name = 0.0, "host"
    length = gap[1] - gap[0]
    for s, e, n in host:
        ov = min(e, gap[1]) - max(s, gap[0])
        if ov > best and e - s <= 4 * length:
            best, name = ov, n
    return name


def op_label(name: str) -> str:
    """An HLO op's name and result type, without its operands."""
    head = name.split(" = ", 1)
    if len(head) == 2:
        return head[0].lstrip("%") + " " + head[1].split("{", 1)[0].split(" ", 1)[0]
    return name[:120]


def step_seconds(reduced: dict, names) -> float:
    """Device seconds of the jitted programs whose name is in ``names``."""
    return sum(t for m, t in reduced["modules"].items() if m in names)


def breakdown(reduced: dict, top: int = 10) -> dict:
    ops = sorted(reduced["ops"].items(), key=lambda kv: -kv[1])[:top]
    return {"device_ops": [[n, t] for n, t in ops],
            "idle_gaps": reduced["gaps"][:top]}
