"""From a profiler trace to device busy time, program time and idle gaps.

``extract(path)`` reads an ``.xplane.pb`` with ``jax.profiler`` and
keeps what the metrics need, in a compact JSON-able form:

    {"window": [start_ns, end_ns],              the harness's window span
     "devices": {plane: {"programs": [[program, start_ns, dur_ns], ...],
                         "ops": [[op, program, start_ns, dur_ns], ...]}},
     "host": [[span, start_ns, dur_ns], ...]}   the harness's own spans

A TPU plane's programs are the events of its ``XLA Modules`` line (a
jitted kernel's program is ``jit_<function>(<id>)``); its operations
are the events of its ``XLA Ops`` and ``Async XLA Ops`` lines, named by
their HLO instruction (``%fusion.3``) and by the program around them.
Host spans are the annotations the harness writes: ``window``,
``query <name>`` and ``op:<node kind>``.

``reduce(compact)`` turns that into the numbers: busy seconds (the union
of program and operation intervals inside the window, averaged over the
chips), device seconds per jitted function (its programs' durations),
the operations that took most time, and the idle gaps, each named by
what the host was doing in its middle — the innermost harness span
there.
"""

from __future__ import annotations

import bisect
import re
from collections import defaultdict
from typing import Dict, List, Optional, Tuple

WINDOW = "window"
_HOST_SPAN = re.compile(r"^(window$|query |op:)")
_PROGRAM = re.compile(r"^jit_(?P<fn>[A-Za-z0-9_]+?)(?:[.(].*)?$")


def program_function(program: str) -> str:
    """The jitted function behind an XLA program name:
    ``jit__hash_core(7)`` -> ``_hash_core``; other names unchanged."""
    m = _PROGRAM.match(program)
    return m.group("fn") if m else program


def extract(path: str) -> dict:
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    devices: Dict[str, dict] = {}
    host: List[list] = []
    for plane in data.planes:
        if plane.name.startswith("/device:TPU:"):
            lines = {line.name: list(line.events) for line in plane.lines}
            programs = sorted(
                ([e.name, int(e.start_ns), int(e.duration_ns)]
                 for e in lines.get("XLA Modules", ())), key=lambda p: p[1])
            starts = [p[1] for p in programs]
            ops = []
            for e in lines.get("XLA Ops", []) + lines.get("Async XLA Ops", []):
                i = bisect.bisect_right(starts, e.start_ns) - 1
                inside = i >= 0 and sum(programs[i][1:]) >= e.start_ns
                ops.append([e.name.split(" = ")[0],
                            programs[i][0] if inside else "",
                            int(e.start_ns), int(e.duration_ns)])
            if programs or ops:
                devices[plane.name] = {"programs": programs, "ops": ops}
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host += [[e.name, int(e.start_ns), int(e.duration_ns)]
                         for e in line.events if _HOST_SPAN.match(e.name)]
    windows = [h for h in host if h[0] == WINDOW]
    if len(windows) != 1:
        raise ValueError(f"{path}: {len(windows)} '{WINDOW}' spans, want 1")
    _, w0, wd = windows[0]
    return {"window": [w0, w0 + wd], "devices": devices,
            "host": sorted(h for h in host if h[0] != WINDOW)}


def union(intervals: List[Tuple[int, int]]) -> List[Tuple[int, int]]:
    """Merge intervals into disjoint ones, in order."""
    out: List[Tuple[int, int]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def _label(t: int, host: List[list]) -> str:
    """What the host was doing at ``t``: the innermost harness span."""
    best: Optional[list] = None
    for name, s, d in host:
        if s <= t < s + d and (best is None or d < best[2]):
            best = [name, s, d]
    if best is None:
        return "between queries"
    if best[0].startswith("query "):
        return "loaders and executor"
    return best[0]


def reduce(compact: dict) -> dict:
    """busy_s, window_s, program_s, device_ops and idle_gaps."""
    w0, w1 = compact["window"]
    window_s = (w1 - w0) / 1e9
    busy, program_s, op_s = [], defaultdict(float), defaultdict(float)
    gaps = defaultdict(float)
    n_chips = max(len(compact["devices"]), 1)
    for plane, events in sorted(compact["devices"].items()):
        spans = []
        for program, s, d in events["programs"]:
            s, e = max(s, w0), min(s + d, w1)
            if e > s:
                program_s[program_function(program)] += (e - s) / 1e9
                spans.append((s, e))
        for name, program, s, d in events["ops"]:
            s, e = max(s, w0), min(s + d, w1)
            if e > s:
                op_s[f"{program_function(program)}/{name}"] += (e - s) / 1e9
                spans.append((s, e))
        merged = union(spans)
        busy.append(sum(e - s for s, e in merged) / 1e9)
        edges = [w0] + [x for iv in merged for x in iv] + [w1]
        for gs, ge in zip(edges[::2], edges[1::2]):
            if ge > gs:
                gaps[_label((gs + ge) // 2, compact["host"])] += \
                    (ge - gs) / 1e9 / n_chips
    top = sorted(op_s.items(), key=lambda kv: -kv[1])[:10]
    idle = sorted(gaps.items(), key=lambda kv: -kv[1])[:10]
    return {"window_s": window_s,
            "busy_s": sum(busy) / len(busy) if busy else 0.0,
            "program_s": dict(program_s),
            "device_ops": [list(kv) for kv in top],
            "idle_gaps": [list(kv) for kv in idle]}
