"""Reduction of a JAX profiler trace to the benchmark's device numbers.

``load`` reads the ``.xplane.pb`` that ``jax.profiler`` writes and keeps
three kinds of events, each as ``(start_ns, end_ns, name)``:

  * ``ops[device]``: the "XLA Ops" line of each TPU plane, one event per
    operation that ran on that device;
  * ``modules[device]``: its "XLA Modules" line, one event per execution
    of a compiled program, named after the jitted function;
  * ``spans``: host events whose name starts with ``bench.``: the spans
    the harness puts around its calls into the program's layers.

Everything after it works on those plain lists, so a test can build a
trace by hand.
"""
from __future__ import annotations

import glob
import os
import re
from collections import defaultdict
from typing import Dict, Iterable, List, Sequence, Tuple

Event = Tuple[int, int, str]
DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
SPAN_PREFIX = "bench."


class Trace:
    def __init__(self, ops: Dict[int, List[Event]],
                 modules: Dict[int, List[Event]], spans: List[Event]):
        self.ops, self.modules, self.spans = ops, modules, spans


def load(log_dir: str) -> Trace:
    from jax.profiler import ProfileData
    paths = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return from_profile(ProfileData.from_file(paths[-1]))


def from_profile(pd) -> Trace:
    ops: Dict[int, List[Event]] = {}
    modules: Dict[int, List[Event]] = {}
    spans: List[Event] = []
    for plane in pd.planes:
        m = DEVICE_PLANE.match(plane.name)
        for line in plane.lines:
            if m and line.name in ("XLA Ops", "XLA Modules"):
                dest = ops if line.name == "XLA Ops" else modules
                dest.setdefault(int(m.group(1)), []).extend(
                    (int(e.start_ns), int(e.end_ns), e.name)
                    for e in line.events)
            elif not m and plane.name.startswith("/host"):
                spans.extend((int(e.start_ns), int(e.end_ns), e.name)
                             for e in line.events
                             if e.name.startswith(SPAN_PREFIX))
    return Trace(ops, modules, spans)


def union(intervals: Iterable[Tuple[int, int]], lo: int, hi: int
          ) -> List[Tuple[int, int]]:
    """Merged intervals, clipped to [lo, hi]."""
    out: List[List[int]] = []
    for s, e in sorted((max(s, lo), min(e, hi)) for s, e in intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def busy_ns(events: Sequence[Event], lo: int, hi: int) -> int:
    return sum(e - s for s, e in union(((s, e) for s, e, _ in events),
                                       lo, hi))


def gaps(events: Sequence[Event], lo: int, hi: int
         ) -> List[Tuple[int, int]]:
    """Idle intervals in [lo, hi]: where no event runs."""
    out, t = [], lo
    for s, e in union(((s, e) for s, e, _ in events), lo, hi):
        if s > t:
            out.append((t, s))
        t = e
    if hi > t:
        out.append((t, hi))
    return out


def program_name(event_name: str) -> str:
    """``jit_d_step(123)`` or ``jit_d_step.4`` -> ``jit_d_step``."""
    return re.split(r"[(\s.]", event_name, maxsplit=1)[0]


def time_by_program(events: Sequence[Event], lo: int, hi: int
                    ) -> Dict[str, int]:
    out: Dict[str, int] = defaultdict(int)
    for s, e, name in events:
        s, e = max(s, lo), min(e, hi)
        if e > s:
            out[program_name(name)] += e - s
    return dict(out)


def op_name(event_name: str) -> str:
    """``%fusion.37 = (f32[128]...) fusion(...)`` -> ``fusion.37``."""
    return event_name.split(" = ", 1)[0].lstrip("%")


def time_by_op(ops: Sequence[Event], modules: Sequence[Event], lo: int,
               hi: int) -> Dict[str, int]:
    """Device time of each operation, named ``<program>/<op>`` after the
    program execution it ran in."""
    out: Dict[str, int] = defaultdict(int)
    mods = sorted(modules)
    i = 0
    for s, e, name in sorted(ops):
        s2, e2 = max(s, lo), min(e, hi)
        if e2 <= s2:
            continue
        while i < len(mods) and mods[i][1] <= s:
            i += 1
        prog = program_name(mods[i][2]) if i < len(mods) \
            and mods[i][0] <= s else "?"
        out[f"{prog}/{op_name(name)}"] += e2 - s2
    return dict(out)


def attribute_gaps(idle: Sequence[Tuple[int, int]], spans: Sequence[Event],
                   outside: str = "host: outside the program's layers"
                   ) -> Dict[str, int]:
    """Idle nanoseconds by the innermost (shortest) host span open in the
    middle of each gap."""
    out: Dict[str, int] = defaultdict(int)
    order = sorted(spans)
    i, active = 0, []
    for s, e in sorted(idle):
        t = (s + e) // 2
        while i < len(order) and order[i][0] <= t:
            active.append(order[i])
            i += 1
        active = [sp for sp in active if sp[1] > t]
        name = min(active, key=lambda sp: sp[1] - sp[0])[2] if active \
            else outside
        out[name] += e - s
    return dict(out)


def span_ns(spans: Sequence[Event], name: str, lo: int, hi: int) -> int:
    return sum(min(e, hi) - max(s, lo) for s, e, n in spans
               if n == name and min(e, hi) > max(s, lo))


def top(totals: Dict[str, int], n: int = 10) -> List[list]:
    return [[k, v / 1e9] for k, v in
            sorted(totals.items(), key=lambda kv: -kv[1])[:n]]
