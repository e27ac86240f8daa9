"""From a JAX profiler trace to the numbers the per-layer readers use.

``extract`` reads the ``.xplane.pb`` the profiler wrote.  It keeps the
host spans the harness wrote itself (names starting ``chipbench.``) and
every program execution of a device plane's ``XLA Modules`` line as
events ``[plane, line, name, start_ns, dur_ns]``; the ``XLA Ops`` line,
millions of events for a few ticks of this engine, is only summed per op
name over the traced window (the host span ``chipbench.window``).

``reduce`` turns that into a summary: the traced window, device busy
time (the union of program executions inside it, averaged over the
devices), the durations of each program's executions that lie wholly
inside it, op time per name, and the idle gaps labelled by the host span
that covers each gap.
"""

from __future__ import annotations

import glob
import os
from collections import defaultdict

OPS, MODULES = "XLA Ops", "XLA Modules"
WINDOW = "chipbench.window"


def extract(trace_dir: str) -> dict:
    from jax.profiler import ProfileData

    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not paths:
        raise RuntimeError(f"no .xplane.pb under {trace_dir}")
    planes = [p for path in paths for p in ProfileData.from_file(path).planes]
    events = []
    for plane in planes:
        if plane.name.startswith("/device:"):
            continue
        for line in plane.lines:
            events += [[plane.name, line.name, e.name, float(e.start_ns),
                        float(e.duration_ns)]
                       for e in line.events if e.name.startswith("chipbench.")]
    w0, w1 = _window(events)
    ops = {}
    for plane in planes:
        if not plane.name.startswith("/device:"):
            continue
        mine = defaultdict(float)
        for line in plane.lines:
            if line.name == MODULES:
                events += [[plane.name, MODULES, e.name, float(e.start_ns),
                            float(e.duration_ns)] for e in line.events]
            elif line.name == OPS:
                for e in line.events:
                    a = max(e.start_ns, w0)
                    b = min(e.start_ns + e.duration_ns, w1)
                    if b > a:
                        # an op's event is named by its whole HLO
                        # instruction; keep the instruction's name
                        mine[e.name.split(" = ", 1)[0]] += b - a
        if mine:
            ops[plane.name] = dict(mine)
    return {"events": events,
            "ops_ns": ops}


def _window(events):
    wins = [e for e in events if e[2] == WINDOW]
    if not wins:
        raise ValueError(f"trace has no host span {WINDOW!r}")
    return wins[0][3], wins[0][3] + wins[0][4]


def _union(intervals):
    """Sorted, merged [start, end) intervals."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def reduce(trace: dict) -> dict:
    """Summary of one traced window; see the module docstring."""
    events = trace["events"]
    w0, w1 = _window(events)
    host = [e for e in events if e[1] != MODULES and e[2] != WINDOW]
    # a device plane that ran no program (the trace can list more planes
    # than the cell uses) is not a device of the cell
    planes = sorted({e[0] for e in events if e[1] == MODULES})
    busy, gaps = [], []
    whole = defaultdict(list)
    for plane in planes:
        spans = []
        for pl, ln, name, s, d in events:
            if pl != plane or ln != MODULES:
                continue
            a, b = max(s, w0), min(s + d, w1)
            if b > a:
                spans.append((a, b))
            if s >= w0 and s + d <= w1:
                whole[name].append(d * 1e-9)
        merged = _union(spans)
        busy.append(sum(b - a for a, b in merged))
        edges = [w0] + [x for ab in merged for x in ab] + [w1]
        for a, b in zip(edges[::2], edges[1::2]):
            if b > a:
                gaps.append((b - a, _label(host, (a + b) / 2)))
    n = max(len(planes), 1)
    ops = defaultdict(float)
    for per_plane in trace["ops_ns"].values():
        for k, v in per_plane.items():
            ops[k] += v * 1e-9 / n
    gaps.sort(key=lambda g: -g[0])
    return {
        "window_s": (w1 - w0) * 1e-9,
        "busy_s": sum(busy) / n * 1e-9,
        "devices": len(planes),
        "programs_s": dict(whole),
        "ops_s": dict(ops),
        "gaps": [[label, g * 1e-9] for g, label in gaps],
    }


def _label(host, t):
    """The innermost harness span covering time ``t``."""
    best = None
    for _, _, name, s, d in host:
        if s <= t <= s + d and (best is None or d < best[1]):
            best = (name, d)
    return best[0] if best else "outside any harness span"


def breakdown(summary: dict, top: int = 10) -> dict:
    ops = sorted(summary["ops_s"].items(), key=lambda kv: -kv[1])[:top]
    return {"device_ops": [[k, v] for k, v in ops],
            "idle_gaps": summary["gaps"][:top]}
