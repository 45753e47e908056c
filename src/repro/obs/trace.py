"""Span tracing for the federated split engine, on two kinds of clock.

**Virtual clock.**  The engine advances a *virtual* clock (the paper's
analytic time model: download + segment compute + LAN hops + uplink).  A
:class:`Span` carries ``v_start``/``v_end`` in virtual seconds; the engine
knows a client's whole virtual timeline the moment it schedules it, so
spans are recorded with :meth:`Tracer.record` after the fact, not timed
live.  Hierarchy is explicit: every span holds its parent's id, so round
-> client-execution -> split-segment -> boundary-crossing nests exactly
the way the engine composed the round, and a trace viewer shows the LAN
hops inside the compute window they actually occupy.  :func:`to_chrome`
exports the Chrome-trace / Perfetto JSON object model
(``{"traceEvents": [...]}``, "X" complete events, one tid lane per
track), loadable in ``ui.perfetto.dev`` or ``chrome://tracing``;
:func:`validate_chrome_trace` is the schema check CI runs on the exported
file.

**The profiler's clock.**  The round's host layers are bounded by
``jax.profiler`` annotations named by the ``SPAN_*`` constants below.
They land on the profiler's host plane, on the same clock as the
device's "XLA Ops", so a device trace can say what the host was doing in
each idle gap.  Parents are given by time nesting on the host thread;
the round is a step annotation whose ``step_num`` is the round index.
With no trace being taken an annotation costs about a microsecond, so
the spans are always on.
"""
from __future__ import annotations

import json
import math
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

NAN = float("nan")

# Chrome-trace pid of the virtual clock's "process"
PID_VIRTUAL = 1

# Spans on the profiler's clock.  A trace of a window finds each layer's
# host time, and the host work behind each device idle gap, by these names.
SPAN_ROUND = "fsl.round"            # one round (a step annotation)
SPAN_INPUT = "fsl.input"            # one client's batches sampled
SPAN_CLIENT_STEP = "fsl.client_step"  # one client's (or group's) steps
SPAN_SYNC = "fsl.sync"              # the host blocked on a device read
SPAN_REDUCE = "fsl.reduce"          # server reduce of the uplinks
SPAN_GENERATOR = "fsl.generator"    # the server's G steps


def to_host(x) -> float:
    """``float(x)`` of a device scalar: one blocking device-to-host read,
    inside an ``fsl.sync`` span, so that the reads are counted where they
    happen."""
    with jax.profiler.TraceAnnotation(SPAN_SYNC):
        return float(x)


@jax.jit
def _stack_scalars(xs):
    return jnp.stack(xs)


def to_host_all(xs: Sequence) -> List[float]:
    """``[float(x) for x in xs]`` of device scalars as one blocking read:
    the scalars are stacked on the device, then copied in one transfer,
    inside one ``fsl.sync`` span."""
    if not xs:
        return []
    with jax.profiler.TraceAnnotation(SPAN_SYNC):
        return [float(v) for v in np.asarray(_stack_scalars(list(xs)))]


@dataclass(frozen=True)
class Span:
    """One named interval on one track of the virtual clock."""
    span_id: int
    parent_id: Optional[int]
    name: str
    cat: str                      # coarse kind: round|client|segment|...
    track: str                    # viewer lane (client id, device id, server)
    v_start: float = NAN          # virtual seconds (engine clock)
    v_end: float = NAN
    args: Dict[str, Any] = field(default_factory=dict)

    @property
    def v_dur(self) -> float:
        return self.v_end - self.v_start

    @property
    def has_virtual(self) -> bool:
        return math.isfinite(self.v_start) and math.isfinite(self.v_end)


class Tracer:
    """Append-only log of virtually-timed spans with explicit parents.

    :meth:`record` appends a span whose VIRTUAL interval is already priced
    (the engine computes a client's download/compute/uplink times when it
    schedules the client, not as they "happen").

    ``set_virtual_offset`` re-bases subsequent virtual times: the trainer
    calls it when it rebuilds the engine (whose virtual clock restarts at
    0) so one recording's virtual timeline stays monotone across rebuilds.
    """

    def __init__(self, run_id: str = "run"):
        self.run_id = run_id
        self.spans: List[Span] = []
        self._next_id = 0
        self._v_offset = 0.0

    # ------------------------------------------------------------------
    def set_virtual_offset(self, offset_s: float) -> None:
        self._v_offset = float(offset_s)

    @property
    def virtual_offset(self) -> float:
        return self._v_offset

    def last_virtual_end(self) -> float:
        """Latest virtual end across all spans (0.0 when none) — what the
        trainer re-bases a fresh engine's clock to."""
        ends = [s.v_end for s in self.spans if s.has_virtual]
        return max(ends) if ends else 0.0

    # ------------------------------------------------------------------
    def record(self, name: str, *, cat: str, track: str,
               v_start: float, v_end: float,
               parent: Optional[int] = None,
               args: Optional[Dict[str, Any]] = None) -> int:
        """Append a virtually-timed span; returns its id (for children)."""
        sid = self._next_id
        self._next_id += 1
        self.spans.append(Span(
            sid, parent, name, cat, track,
            v_start=self._v_offset + float(v_start),
            v_end=self._v_offset + float(v_end),
            args=dict(args or {})))
        return sid

    # ------------------------------------------------------------------
    def children(self, span_id: Optional[int]) -> List[Span]:
        return [s for s in self.spans if s.parent_id == span_id]

    def by_cat(self, cat: str) -> List[Span]:
        return [s for s in self.spans if s.cat == cat]

    def by_id(self, span_id: int) -> Span:
        for s in self.spans:
            if s.span_id == span_id:
                return s
        raise KeyError(span_id)

    # ------------------------------------------------------------------
    def to_chrome(self) -> Dict[str, Any]:
        """Chrome-trace object: X events in microseconds on the virtual
        clock's pid, one tid per track."""
        tids: Dict[str, int] = {}

        def tid(track: str) -> int:
            if track not in tids:
                tids[track] = len(tids) + 1
            return tids[track]

        events: List[Dict[str, Any]] = []
        for s in self.spans:
            if not s.has_virtual:
                continue
            # args must be JSON-finite: a trace with NaN breaks strict
            # Chrome-trace parsers, so non-finite values are stringified
            args = {k: (v if not isinstance(v, float) or math.isfinite(v)
                        else repr(v)) for k, v in s.args.items()}
            args["span_id"] = s.span_id
            if s.parent_id is not None:
                args["parent_id"] = s.parent_id
            events.append({
                "name": s.name, "cat": s.cat, "ph": "X",
                "pid": PID_VIRTUAL, "tid": tid(s.track),
                "ts": s.v_start * 1e6,
                "dur": max(0.0, s.v_dur) * 1e6,
                "args": args})
        meta: List[Dict[str, Any]] = [
            {"name": "process_name", "ph": "M", "pid": PID_VIRTUAL,
             "tid": 0, "args": {"name": "virtual clock"}}]
        for track, t in sorted(tids.items(), key=lambda kv: kv[1]):
            meta.append({"name": "thread_name", "ph": "M",
                         "pid": PID_VIRTUAL, "tid": t,
                         "args": {"name": track}})
        return {"traceEvents": meta + events, "displayTimeUnit": "ms",
                "otherData": {"run_id": self.run_id, "clock": "virtual"}}

    def export_chrome(self, path: str) -> str:
        obj = self.to_chrome()
        validate_chrome_trace(obj)
        with open(path, "w") as f:
            # allow_nan=False: a file Perfetto rejects must fail HERE
            json.dump(obj, f, allow_nan=False)
        return path


def validate_chrome_trace(obj: Any) -> int:
    """Chrome-trace JSON-object-format schema check; returns the number of
    "X" complete events.  Raises ``ValueError`` on any violation — this is
    what CI runs against the exported file."""
    if not isinstance(obj, dict) or "traceEvents" not in obj:
        raise ValueError("trace must be an object with a traceEvents list")
    events = obj["traceEvents"]
    if not isinstance(events, list):
        raise ValueError("traceEvents must be a list")
    n_complete = 0
    for i, ev in enumerate(events):
        if not isinstance(ev, dict):
            raise ValueError(f"event {i} is not an object")
        for k in ("name", "ph", "pid", "tid"):
            if k not in ev:
                raise ValueError(f"event {i} missing required key {k!r}")
        if not isinstance(ev["ph"], str) or len(ev["ph"]) != 1:
            raise ValueError(f"event {i}: ph must be a 1-char phase code")
        if ev["ph"] == "X":
            n_complete += 1
            for k in ("ts", "dur"):
                v = ev.get(k)
                if not isinstance(v, (int, float)) or not math.isfinite(v):
                    raise ValueError(
                        f"event {i}: X event needs finite numeric {k!r}")
            if ev["dur"] < 0:
                raise ValueError(f"event {i}: negative dur")
    if n_complete == 0:
        raise ValueError("trace contains no complete ('X') events")
    return n_complete
