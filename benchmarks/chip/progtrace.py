"""The program's own spans and scopes in a traced run.

Beside what ``devtrace`` reduces, a traced run's ``.xplane.pb`` holds:

* the program's host phases: ``jax.profiler.TraceAnnotation`` spans whose
  names start with ``search/`` (``repro.obs.phase``), on the device trace's
  clock;
* each device operation's scope: the ``tf_op`` stat in the event metadata
  of the ``/device:TPU:<i>`` plane, the path ``jax.named_scope`` builds
  (``jit(run)/while/body/rank/peel/while/body/add:``).  ``ProfileData``
  does not show event metadata, so :func:`_tf_ops` reads it from the
  file's protobuf wire format.

:func:`view` loads both once a process from the trace ``run.py`` has just
reduced (still under ``.bench_trace`` while the readers run), clipped to
the traced window, as a :class:`ProgTrace`; a run's inputs may carry one
under ``"progtrace"`` instead (the tests' recorded traces).  A fusion
carries the scope of its root operation, so a phase's time is that of the
operations XLA rooted in it.
"""

from __future__ import annotations

import dataclasses
import glob
import gzip
import json
import os
from typing import Dict, Iterator, List, Optional, Sequence, Tuple

from benchmarks.chip import layout
from benchmarks.chip.devtrace import (MODULES_LINE, OPS_LINE, Event,
                                      Interval, _clip, _length, _union)
from benchmarks.chip.searchtrace import RUNNER

# where run.py keeps the trace of a traced run
TRACE_DIR = os.path.join(layout.ROOT, ".bench_trace")

SPAN_PREFIX = "search/"
ENTRY = "search/entry"
DEVICE = "search/device"
# the compiled loop's per-generation scopes (``core.nsga2_jax._make_run``);
# operations under ``init`` rank the initial population once per search
PHASES = ("offspring", "evaluate", "rank/pack", "rank/peel", "rank/tail",
          "crowding", "select")
INIT = "init"


@dataclasses.dataclass
class ProgTrace:
    """The program's spans and per-phase device time in one window."""
    spans: List[Event]                  # host ``search/`` spans, by start
    phases: Dict[str, List[Interval]]   # device 0, inside runner executions

    def phase_s(self, name: str) -> float:
        """Seconds in which an operation of the phase ran on device 0."""
        return _length(self.phases.get(name, []))

    def entries(self) -> List[Tuple[Event, Event]]:
        """``(search/entry, search/device)`` pairs, one per search whose
        entry holds a device span."""
        out = []
        devices = [s for s in self.spans if s.name == DEVICE]
        for e in self.spans:
            if e.name != ENTRY:
                continue
            inner = [d for d in devices
                     if d.start >= e.start and d.end <= e.end]
            if inner:
                out.append((e, inner[0]))
        return out

    def to_json(self) -> Dict:
        return {"spans": [[e.name, e.start, e.dur] for e in self.spans],
                "phases": {k: [list(iv) for iv in v]
                           for k, v in self.phases.items()}}

    @classmethod
    def from_json(cls, d: Dict) -> "ProgTrace":
        return cls([Event(n, float(s), float(u)) for n, s, u in d["spans"]],
                   {k: [tuple(iv) for iv in v]
                    for k, v in d["phases"].items()})


# -- what the readers call -----------------------------------------------------

_LOADED: Dict[Tuple[str, Interval], ProgTrace] = {}


def view(run) -> Optional[ProgTrace]:
    """The run's :class:`ProgTrace`: ``run["progtrace"]`` when given, else
    loaded (once a process) from the trace under ``TRACE_DIR``; None when
    there is no trace."""
    if "progtrace" in run:
        return run["progtrace"]
    files = glob.glob(os.path.join(TRACE_DIR, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    if len(files) != 1:
        return None
    key = (files[0], tuple(run["trace"].window))
    if key not in _LOADED:
        _LOADED[key] = load(files[0], key[1])
    return _LOADED[key]


def generations(run) -> int:
    """Generations the compiled loop ran in the window's searches, by the
    program's own count (0 when the program does not count them)."""
    counts = [getattr(s.result, "counts", None)
              for s in run.get("searches") or []]
    if not counts or not all(counts):
        return 0
    return sum(int(c["generations"]) for c in counts)


def phase_ms_per_generation(run, phase: str) -> Optional[float]:
    """Device time of a generation phase, in ms a generation run."""
    pt = view(run)
    gens = generations(run)
    if pt is None or not gens or pt.phase_s(phase) <= 0:
        return None
    return 1e3 * pt.phase_s(phase) / gens


# -- loading -------------------------------------------------------------------

def load(path: str, window: Interval) -> ProgTrace:
    """Read one ``.xplane.pb``: the ``search/`` host spans inside
    ``window``, and device 0's busy intervals under each of
    :data:`PHASES`, inside the runner's executions in ``window``."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    spans, ops, runs = [], [], []
    for plane in pd.planes:
        if plane.name.startswith("/host:"):
            for ln in plane.lines:
                spans.extend(Event(e.name, e.start_ns * 1e-9,
                                   e.duration_ns * 1e-9)
                             for e in ln.events
                             if e.name.startswith(SPAN_PREFIX))
        elif plane.name == "/device:TPU:0":
            for ln in plane.lines:
                if ln.name == OPS_LINE:
                    ops = [(e.name, e.start_ns * 1e-9, e.end_ns * 1e-9)
                           for e in ln.events]
                elif ln.name == MODULES_LINE:
                    runs = [(e.start_ns * 1e-9, e.end_ns * 1e-9)
                            for e in ln.events if e.name.startswith(RUNNER)]
    spans = sorted((e for e in spans
                    if e.start >= window[0] and e.end <= window[1]),
                   key=lambda e: e.start)
    scopes = {}
    if ops:
        with open(path, "rb") as f:
            scopes = _tf_ops(f.read(), "/device:TPU:0")
    runs = _clip(_union_ivs(runs), window)
    by_phase: Dict[str, List[Interval]] = {p: [] for p in PHASES}
    for name, a, b in ops:
        ph = phase_of(scopes.get(name, ""))
        if ph is not None:
            by_phase[ph].append((a, b))
    phases = {p: _intersect(_union_ivs(v), runs) for p, v in by_phase.items()}
    return ProgTrace(spans, phases)


def phase_of(tf_op: str) -> Optional[str]:
    """The generation phase an operation's scope path lies in, or None
    (outside the loop's phases, or under ``init``)."""
    parts = tf_op.split("/")
    if INIT in parts:
        return None
    for p in PHASES:
        want = p.split("/")
        for i in range(len(parts) - len(want) + 1):
            if parts[i:i + len(want)] == want:
                return p
    return None


def _union_ivs(ivs: Sequence[Interval]) -> List[Interval]:
    return _union([Event("", a, b - a) for a, b in ivs])


def _intersect(a: Sequence[Interval], b: Sequence[Interval]
               ) -> List[Interval]:
    """Intersection of two sorted, disjoint interval lists."""
    out, i, j = [], 0, 0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if lo < hi:
            out.append((lo, hi))
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return out


# -- idle time by span (for the breakdown) -------------------------------------

def idle_by_span(trace, pt: ProgTrace) -> Dict[str, float]:
    """Device 0's idle seconds in the window, by the innermost ``search/``
    span over each idle stretch (``"none"`` outside every span)."""
    out: Dict[str, float] = {}
    for a, b in _idle(trace):
        cuts = sorted({a, b} | {t for s in pt.spans for t in (s.start, s.end)
                                if a < t < b})
        for lo, hi in zip(cuts, cuts[1:]):
            name = _innermost(pt.spans, (lo + hi) / 2)
            out[name] = out.get(name, 0.0) + hi - lo
    return out


def named_gaps(trace, pt: ProgTrace, top: int = 10
               ) -> List[Tuple[str, float]]:
    """The longest idle gaps on device 0, each named by the innermost
    ``search/`` span over its midpoint."""
    gaps = sorted(_idle(trace), key=lambda g: g[1] - g[0], reverse=True)
    return [(_innermost(pt.spans, (a + b) / 2), b - a) for a, b in gaps[:top]]


def _idle(trace) -> List[Interval]:
    busy = _clip(_union(trace.ops[0]), trace.window) if trace.ops else []
    gaps, t = [], trace.window[0]
    for a, b in busy:
        if a > t:
            gaps.append((t, a))
        t = max(t, b)
    if t < trace.window[1]:
        gaps.append((t, trace.window[1]))
    return gaps


def _innermost(spans: Sequence[Event], t: float) -> str:
    inner = None
    for s in spans:
        if s.start <= t < s.end and (inner is None or s.dur < inner.dur):
            inner = s
    return inner.name if inner is not None else "none"


# -- the protobuf wire format of an XSpace -------------------------------------
# XSpace.planes = 1; XPlane: name = 2, event_metadata = 4 (map<int64,
# XEventMetadata>), stat_metadata = 5 (map<int64, XStatMetadata>);
# XEventMetadata: name = 2, stats = 5; XStat: metadata_id = 1, str_value = 5,
# ref_value = 7 (the name of a stat metadata); XStatMetadata: name = 2.

def _tf_ops(space: bytes, plane_name: str) -> Dict[str, str]:
    """Operation name -> ``tf_op`` scope path, from the event metadata of
    the named plane of a serialized XSpace."""
    for field, plane in _fields(memoryview(space)):
        if field != 1:
            continue
        meta, stat_names, name = [], {}, ""
        for pf, pv in _fields(plane):
            if pf == 2:
                name = bytes(pv).decode()
            elif pf == 4:
                meta.append(_map_entry(pv)[1])
            elif pf == 5:
                sid, sm = _map_entry(pv)
                stat_names[sid] = _string(sm, 2)
        if name != plane_name:
            continue
        out = {}
        for em in meta:
            op, tf_op = "", ""
            for f, v in _fields(em):
                if f == 2:
                    op = bytes(v).decode("utf-8", "replace")
                elif f == 5:
                    stat = dict(_fields(v))
                    if stat_names.get(stat.get(1)) == "tf_op":
                        tf_op = (bytes(stat[5]).decode() if 5 in stat
                                 else stat_names.get(stat.get(7), ""))
            if tf_op:
                out[op] = tf_op
        return out
    return {}


def _map_entry(b) -> Tuple[int, memoryview]:
    key, val = 0, memoryview(b"")
    for f, v in _fields(b):
        if f == 1:
            key = v
        elif f == 2:
            val = v
    return key, val


def _string(b, field: int) -> str:
    for f, v in _fields(b):
        if f == field:
            return bytes(v).decode("utf-8", "replace")
    return ""


def _fields(b) -> Iterator[Tuple[int, object]]:
    """``(field number, value)`` of one message: an int for varints, a
    memoryview for length-delimited and fixed-width fields."""
    i, n = 0, len(b)
    while i < n:
        key, i = _varint(b, i)
        wire = key & 7
        if wire == 0:
            v, i = _varint(b, i)
        elif wire == 2:
            ln, i = _varint(b, i)
            v, i = b[i:i + ln], i + ln
        elif wire in (1, 5):
            w = 8 if wire == 1 else 4
            v, i = b[i:i + w], i + w
        else:
            raise ValueError(f"unsupported protobuf wire type {wire}")
        yield key >> 3, v


def _varint(b, i: int) -> Tuple[int, int]:
    out = shift = 0
    while True:
        c = b[i]
        i += 1
        out |= (c & 0x7F) << shift
        shift += 7
        if c < 0x80:
            return out, i


# -- recorded traces -----------------------------------------------------------

def save(path: str, record: Dict) -> None:
    with gzip.open(path, "wt") as f:
        json.dump(record, f)


def read(path: str) -> Dict:
    with gzip.open(path, "rt") as f:
        return json.load(f)

