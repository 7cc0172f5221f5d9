"""Reduction of a JAX profiler trace to the numbers the benchmark reports.

A traced run writes an ``.xplane.pb``; :func:`load` keeps what the metrics
need from it as a :class:`TraceView`:

* device operations (line ``XLA Ops`` of each ``/device:TPU:<i>`` plane)
  and the programs they belong to (line ``XLA Modules``);
* the benchmark's own host spans, recorded with
  ``jax.profiler.TraceAnnotation`` under names that start with ``bench/``;
* the traced window: the span of the ``bench/window`` annotation.

All times are seconds on the trace's clock.  A view round-trips through
JSON (:meth:`TraceView.to_json`), so a recorded trace can be reduced again
by the tests.
"""

from __future__ import annotations

import dataclasses
import glob
import gzip
import json
import os
import re
from typing import Callable, Dict, List, Sequence, Tuple

Interval = Tuple[float, float]

WINDOW = "bench/window"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"


@dataclasses.dataclass
class Event:
    """One timed event: ``name`` over ``[start, start + dur)`` seconds."""
    name: str
    start: float
    dur: float

    @property
    def end(self) -> float:
        return self.start + self.dur


@dataclasses.dataclass
class TraceView:
    """What the metrics read from one traced window."""
    ops: List[List[Event]]          # per device: operations
    modules: List[List[Event]]      # per device: programs
    host: List[Event]               # benchmark spans (``bench/...``)
    window: Interval

    @property
    def window_s(self) -> float:
        return self.window[1] - self.window[0]

    # -- busy / idle ---------------------------------------------------------
    def busy_s(self) -> float:
        """Seconds in which an operation ran, inside the window, averaged
        over the devices."""
        if not self.ops:
            return 0.0
        tot = sum(_length(_clip(_union(ev), self.window)) for ev in self.ops)
        return tot / len(self.ops)

    def idle_share(self) -> float:
        """1 - busy / window, in percent."""
        return 100.0 * (1.0 - self.busy_s() / self.window_s)

    def idle_gaps(self, top: int = 10) -> List[Tuple[str, float]]:
        """The longest idle gaps on device 0 inside the window, each named
        by the innermost benchmark span that covers its midpoint."""
        if not self.ops:
            return []
        busy = _clip(_union(self.ops[0]), self.window)
        gaps, t = [], self.window[0]
        for a, b in busy:
            if a > t:
                gaps.append((t, a))
            t = max(t, b)
        if t < self.window[1]:
            gaps.append((t, self.window[1]))
        gaps.sort(key=lambda g: g[1] - g[0], reverse=True)
        return [(self._label((a + b) / 2), b - a) for a, b in gaps[:top]]

    def _label(self, t: float) -> str:
        inner = None
        for ev in self.host:
            if ev.name != WINDOW and ev.start <= t < ev.end:
                if inner is None or ev.dur < inner.dur:
                    inner = ev
        return inner.name if inner is not None else "idle"

    # -- operation and program times -----------------------------------------
    def op_seconds(self, match: Callable[[str], bool]) -> float:
        """Summed device time of the operations whose name matches, inside
        the window, averaged over the devices."""
        if not self.ops:
            return 0.0
        tot = 0.0
        for ev in self.ops:
            tot += sum(_overlap(e, self.window) for e in ev if match(e.name))
        return tot / len(self.ops)

    def module_events(self, match: Callable[[str], bool]) -> List[Event]:
        """Device 0's program executions whose name matches, inside the
        window."""
        if not self.modules:
            return []
        return [e for e in self.modules[0] if match(e.name)
                and e.start >= self.window[0] and e.end <= self.window[1]]

    def top_ops(self, top: int = 10) -> List[Tuple[str, float]]:
        """The device operations that took most time on device 0, by short
        name, each counted by its self time (its duration less that of the
        operations nested in it, such as a loop's body)."""
        tot: Dict[str, float] = {}
        for e, self_s in _self_times(self.ops[0] if self.ops else [],
                                     self.window):
            key = short_name(e.name)
            tot[key] = tot.get(key, 0.0) + self_s
        return sorted(tot.items(), key=lambda kv: kv[1], reverse=True)[:top]

    # -- serialisation -------------------------------------------------------
    def to_json(self) -> Dict:
        def evs(lst):
            return [[e.name, e.start, e.dur] for e in lst]
        return {"ops": [evs(d) for d in self.ops],
                "modules": [evs(d) for d in self.modules],
                "host": evs(self.host), "window": list(self.window)}

    @classmethod
    def from_json(cls, d: Dict) -> "TraceView":
        def evs(lst):
            return [Event(n, float(s), float(u)) for n, s, u in lst]
        return cls(ops=[evs(x) for x in d["ops"]],
                   modules=[evs(x) for x in d["modules"]],
                   host=evs(d["host"]), window=tuple(d["window"]))

    def save(self, path: str) -> None:
        with gzip.open(path, "wt") as f:
            json.dump(self.to_json(), f)

    @classmethod
    def read(cls, path: str) -> "TraceView":
        with gzip.open(path, "rt") as f:
            return cls.from_json(json.load(f))


def load(trace_dir: str) -> TraceView:
    """Read the one ``.xplane.pb`` under ``trace_dir`` into a view; raises
    when there is none, no TPU plane or no ``bench/window`` span."""
    from jax.profiler import ProfileData
    files = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    if len(files) != 1:
        raise RuntimeError(f"expected one xplane.pb under {trace_dir}, "
                           f"found {len(files)}")
    pd = ProfileData.from_file(files[0])
    ops, modules, host = [], [], []
    devices = sorted((p for p in pd.planes
                      if p.name.startswith("/device:TPU:")),
                     key=lambda p: int(p.name.rsplit(":", 1)[1]))
    for plane in devices:
        lines = {ln.name: ln for ln in plane.lines}
        ops.append(_events(lines.get(OPS_LINE)))
        modules.append(_events(lines.get(MODULES_LINE)))
    for plane in pd.planes:
        if plane.name.startswith("/host:"):
            for ln in plane.lines:
                host.extend(e for e in _events(ln)
                            if e.name.startswith("bench/"))
    if not devices:
        raise RuntimeError("the trace holds no TPU plane")
    win = [e for e in host if e.name == WINDOW]
    if len(win) != 1:
        raise RuntimeError(f"expected one {WINDOW} span, found {len(win)}")
    host.sort(key=lambda e: e.start)
    return TraceView(ops, modules, host, (win[0].start, win[0].end))


# an operation's name is its whole HLO instruction; the head of it (result
# and operand shapes, parameter names) is all the readers look at
NAME_CHARS = 400


def _events(line) -> List[Event]:
    if line is None:
        return []
    return [Event(e.name[:NAME_CHARS], e.start_ns * 1e-9, e.duration_ns * 1e-9)
            for e in line.events]


def short_name(name: str) -> str:
    """``%packed_domination.13 = u32[...] custom-call(...)`` ->
    ``packed_domination``."""
    head = name.split(" = ", 1)[0].lstrip("%")
    return re.sub(r"\.\d+$", "", head)


def _self_times(events: Sequence[Event], win: Interval):
    """``(event, self seconds inside win)`` for events of one timeline, where
    an event's self time excludes the events nested inside it."""
    evs = sorted(events, key=lambda e: (e.start, -e.dur))
    child = [0.0] * len(evs)
    stack: List[int] = []
    for i, e in enumerate(evs):
        while stack and evs[stack[-1]].end <= e.start:
            stack.pop()
        if stack:
            child[stack[-1]] += _overlap(e, win)
        stack.append(i)
    for i, e in enumerate(evs):
        yield e, max(_overlap(e, win) - child[i], 0.0)


def _union(events) -> List[Interval]:
    ivs = sorted((e.start, e.end) for e in events)
    out: List[Interval] = []
    for a, b in ivs:
        if out and a <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], b))
        else:
            out.append((a, b))
    return out


def _clip(ivs: Sequence[Interval], win: Interval) -> List[Interval]:
    return [(max(a, win[0]), min(b, win[1])) for a, b in ivs
            if b > win[0] and a < win[1]]


def _length(ivs: Sequence[Interval]) -> float:
    return sum(b - a for a, b in ivs)


def _overlap(e: Event, win: Interval) -> float:
    return max(0.0, min(e.end, win[1]) - max(e.start, win[0]))
