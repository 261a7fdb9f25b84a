"""The program's own spans in a profiler trace: the time each holds, and
the device's idle time put down to the host step open at each instant.

The program (``repro.core.obs``) marks each step of its hot path with a
profiler annotation, one dotted prefix per layer — ``ops.*`` (relational
ops), ``kernel.*`` (the device-kernel call edge), ``loader.*`` and
``sipc.*`` — nested per thread, on the profiler's clock.
``extract(path)`` reads them from an ``.xplane.pb``:

    [[name, thread, start_ns, dur_ns], ...]

``thread`` numbers the trace's host lines (a line is one thread).
``reduce(compact, spans)``, where ``compact`` is ``harness.trace.extract``
of the same trace, gives:

    span_self_s   per span name, the seconds inside the window that the
                  span's children on its own thread do not cover
    idle_by_span  the device's idle time inside the window, split over
                  the innermost program span open at each instant — the
                  one opened last, across threads — by overlap, as
                  [[name, seconds], ...], largest first; time with no
                  program span open is ``outside program spans``.
                  Averaged over the chips, as ``trace.reduce``'s
                  ``idle_gaps`` is.
    idle_s        the device's idle time inside the window

``seconds_per_query`` turns ``span_self_s`` into the per-layer numbers:
the self time of every span under one prefix (``ops.``: the ops' host
work, the ``kernel.*`` calls inside them left out; ``kernel.``: the
whole time inside device-kernel calls) over the window's queries.
"""

from __future__ import annotations

import heapq
import re
from collections import defaultdict
from typing import Dict, List, Tuple

from harness.trace import union

PROGRAM_SPAN = re.compile(r"^(ops|kernel|loader|sipc)\.")
OUTSIDE = "outside program spans"


def extract(path: str) -> List[list]:
    from jax.profiler import ProfileData
    spans: List[list] = []
    thread = 0
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            spans += [[e.name, thread, int(e.start_ns), int(e.duration_ns)]
                      for e in line.events if PROGRAM_SPAN.match(e.name)]
            thread += 1
    return sorted(spans, key=lambda s: (s[2], -s[3], s[1], s[0]))


def _clip(s: int, e: int, w0: int, w1: int) -> Tuple[int, int]:
    return max(s, w0), min(e, w1)


def self_seconds(spans: List[list], w0: int, w1: int) -> Dict[str, float]:
    """Per name, the time inside ``[w0, w1)`` that no child span on the
    same thread covers."""
    out: Dict[str, float] = defaultdict(float)
    threads: Dict[int, list] = defaultdict(list)
    for name, thread, s, d in spans:
        threads[thread].append((s, s + d, name))
    for items in threads.values():
        items.sort(key=lambda x: (x[0], -x[1]))
        stack: List[list] = []            # [start, end, name, own_ns]
        for s, e, name in items:
            while stack and stack[-1][1] <= s:
                _, _, done, own = stack.pop()
                out[done] += own / 1e9
            cs, ce = _clip(s, e, w0, w1)
            if stack:                     # the child's time is not its
                ps, pe = _clip(stack[-1][0], stack[-1][1], w0, w1)
                stack[-1][3] -= max(min(ce, pe) - max(cs, ps), 0)
            stack.append([s, e, name, max(ce - cs, 0)])
        for _, _, done, own in stack:
            out[done] += own / 1e9
    return {k: v for k, v in out.items() if v > 0}


def _innermost(spans: List[list], w0: int, w1: int) -> List[tuple]:
    """Disjoint ``(start, end, name)`` pieces of ``[w0, w1)``, each named
    by the span opened last among those open there (on a tie, the
    shorter: the child)."""
    events = []
    for i, (name, _, s, d) in enumerate(spans):
        cs, ce = _clip(s, s + d, w0, w1)
        if ce > cs:
            events += [(cs, 1, i), (ce, 0, i)]
    events.sort()
    pieces: List[tuple] = []
    heap: List[tuple] = []                # (-start, dur, index)
    ended = set()
    prev = None
    for t, opens, i in events:
        while heap and heap[0][2] in ended:
            heapq.heappop(heap)
        if heap and prev is not None and t > prev:
            pieces.append((prev, t, spans[heap[0][2]][0]))
        if opens:
            heapq.heappush(heap, (-spans[i][2], spans[i][3], i))
        else:
            ended.add(i)
        prev = t
    return pieces


def _idle(devices: dict, w0: int, w1: int) -> List[List[Tuple[int, int]]]:
    """Per chip, the idle intervals of the window: its complement of the
    union of program and operation intervals (as ``trace.reduce``)."""
    out = []
    for _, events in sorted(devices.items()):
        busy = [(s, s + d) for _, s, d in events["programs"]] + \
               [(s, s + d) for _, _, s, d in events["ops"]]
        merged = union([iv for iv in (_clip(s, e, w0, w1) for s, e in busy)
                        if iv[1] > iv[0]])
        edges = [w0] + [x for iv in merged for x in iv] + [w1]
        out.append([(s, e) for s, e in zip(edges[::2], edges[1::2])
                    if e > s])
    return out


def reduce(compact: dict, spans: List[list]) -> dict:
    w0, w1 = compact["window"]
    pieces = _innermost(spans, w0, w1)
    idle = _idle(compact["devices"], w0, w1)
    by_span: Dict[str, float] = defaultdict(float)
    n_chips = max(len(idle), 1)
    for gaps in idle:
        j = 0
        for gs, ge in gaps:
            covered = 0
            while j < len(pieces) and pieces[j][1] <= gs:
                j += 1
            k = j
            while k < len(pieces) and pieces[k][0] < ge:
                s, e, name = pieces[k]
                overlap = min(e, ge) - max(s, gs)
                by_span[name] += overlap / 1e9 / n_chips
                covered += overlap
                k += 1
            if ge - gs > covered:
                by_span[OUTSIDE] += (ge - gs - covered) / 1e9 / n_chips
    return {"span_self_s": self_seconds(spans, w0, w1),
            "idle_by_span": sorted(([k, v] for k, v in by_span.items()),
                                   key=lambda kv: -kv[1]),
            "idle_s": sum(e - s for gaps in idle for s, e in gaps)
            / 1e9 / n_chips}


def seconds_per_query(span_self_s: Dict[str, float], prefix: str,
                      queries: int):
    """Self time of the spans under ``prefix`` over ``queries``; None
    when no such span is in the window."""
    held = [v for k, v in span_self_s.items() if k.startswith(prefix)]
    if not held or not queries:
        return None
    return sum(held) / queries
