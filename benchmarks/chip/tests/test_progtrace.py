"""The program's spans, scopes and counts, reduced to the five metrics that
read them: on hand-made traces, on a hand-encoded ``.xplane.pb``, and on
program traces recorded from short runs on one TPU v5e (``data/``)."""

import glob
import math
import os
import types

import pytest

from benchmarks.chip import layout, progtrace
from benchmarks.chip.devtrace import Event, TraceView
from benchmarks.chip.progtrace import ProgTrace

DATA = os.path.join(layout.HERE, "data")
METRICS = ("entry_prepare_ms", "entry_finish_ms", "evaluate_ms", "peel_ms",
           "peel_passes")


def searches(*counts):
    return [types.SimpleNamespace(result=types.SimpleNamespace(counts=c))
            for c in counts]


def hand_prog():
    # two searches: entry [0, 10) device [4, 8); entry [10, 16) device
    # [12, 15); a generation loop of 2 + 2 generations
    spans = [Event("search/entry", 0.0, 10.0), Event("search/tables", 1.0, 3.0),
             Event("search/device", 4.0, 4.0), Event("search/front", 8.0, 1.5),
             Event("search/entry", 10.0, 6.0), Event("search/device", 12.0, 3.0)]
    phases = {"evaluate": [(4.5, 5.0), (6.0, 6.5), (12.5, 13.0)],
              "rank/peel": [(5.0, 6.0)]}
    return ProgTrace(spans, phases)


def hand_run():
    return {"progtrace": hand_prog(),
            "searches": searches({"generations": 2, "peel_passes": 30},
                                 {"generations": 2, "peel_passes": 10})}


def test_readers_on_a_hand_made_trace():
    run = hand_run()
    got = {k: layout.metric_reader(k).read(run) for k in METRICS}
    assert got == pytest.approx({
        "entry_prepare_ms": 1e3 * (4 + 2) / 2,
        "entry_finish_ms": 1e3 * (2 + 1) / 2,
        "evaluate_ms": 1e3 * 1.5 / 4,
        "peel_ms": 1e3 * 1.0 / 4,
        "peel_passes": 40 / 4})


def test_readers_find_nothing_in_a_program_without_spans_or_counts():
    # what a program before this instrumentation leaves: no search/ spans,
    # no scoped operations, results without counts
    bare = types.SimpleNamespace(result=types.SimpleNamespace())
    run = {"progtrace": ProgTrace([Event("bench/search", 0.0, 1.0)], {}),
           "searches": [bare]}
    assert {k: layout.metric_reader(k).read(run) for k in METRICS} == \
        dict.fromkeys(METRICS)


def test_entries_pair_each_entry_with_its_device_span():
    pairs = hand_prog().entries()
    assert [(e.start, d.start) for e, d in pairs] == [(0.0, 4.0), (10.0, 12.0)]


@pytest.mark.parametrize("tf_op,phase", [
    ("jit(run)/while/body/evaluate/jit(eval)/add:", "evaluate"),
    ("jit(run)/while/body/rank/pack/packed_domination/pallas_call:",
     "rank/pack"),
    ("jit(run)/while/body/rank/peel/while/body/population_count:",
     "rank/peel"),
    ("jit(run)/while/body/rank/tail/sort:", "rank/tail"),
    ("jit(run)/while/body/crowding/scatter-add:", "crowding"),
    ("jit(run)/while/body/select/gather:", "select"),
    ("jit(run)/while/body/offspring/jit(_randint)/add:", "offspring"),
    ("jit(run)/init/rank/peel/while/body/add:", None),
    ("jit(run)/init/evaluate/add:", None),
    ("jit(run)/while/body/concatenate:", None),
    ("", None),
])
def test_phase_of_scope_paths(tf_op, phase):
    assert progtrace.phase_of(tf_op) == phase


def test_intersect():
    a = [(0.0, 2.0), (3.0, 5.0), (6.0, 9.0)]
    b = [(1.0, 4.0), (8.0, 10.0)]
    assert progtrace._intersect(a, b) == [(1.0, 2.0), (3.0, 4.0), (8.0, 9.0)]


def test_idle_named_by_innermost_search_span():
    ops = [Event("%a = f32[] fusion()", 4.0, 4.0),
           Event("%b = f32[] fusion()", 12.0, 3.0)]
    trace = TraceView([ops], [[]], [], (0.0, 17.0))
    idle = progtrace.idle_by_span(trace, hand_prog())
    assert idle == pytest.approx({"search/entry": 1.0 + 0.5 + 2.0 + 1.0,
                                  "search/tables": 3.0,
                                  "search/front": 1.5, "none": 1.0})
    assert progtrace.named_gaps(trace, hand_prog(), top=1) == \
        [("search/tables", pytest.approx(4.0))]


# -- the protobuf wire format ------------------------------------------------------

def _varint(n):
    out = bytearray()
    while True:
        b, n = n & 0x7F, n >> 7
        out.append(b | (0x80 if n else 0))
        if not n:
            return bytes(out)


def _len(num, payload):
    if isinstance(payload, str):
        payload = payload.encode()
    return _varint(num << 3 | 2) + _varint(len(payload)) + payload


def _int(num, v):
    return _varint(num << 3) + _varint(v)


def _plane(name, ops, extra=b""):
    """An XPlane with a ``tf_op`` stat metadata (id 7) and one event
    metadata per ``(op name, tf_op or None)``; a tf_op given as an int is a
    ``ref_value`` to that stat metadata id."""
    body = _len(2, name) + _len(5, _int(1, 7) + _len(2, _int(1, 7)
                                                 + _len(2, "tf_op")))
    body += _len(5, _int(1, 9) + _len(2, _int(1, 9) + _len(2, "jit(r)/x/y:")))
    for i, (op, tf_op) in enumerate(ops, start=1):
        md = _int(1, i) + _len(2, op)
        if isinstance(tf_op, str):
            md += _len(5, _int(1, 7) + _len(5, tf_op))
        elif isinstance(tf_op, int):
            md += _len(5, _int(1, 7) + _int(7, tf_op))
        body += _len(4, _int(1, i) + _len(2, md))
    return _len(1, body + extra)


def test_tf_ops_from_a_hand_encoded_xspace():
    space = (_plane("/host:CPU", [("search/entry", None)])
             + _plane("/device:TPU:0",
                      [("%add.1 = f32[] add()", "jit(run)/evaluate/add:"),
                       ("%copy.2 = f32[] copy()", None),
                       ("%mul.3 = f32[] multiply()", 9)],
                      extra=_int(1, 42) + b"\x09" + b"\0" * 8))
    assert progtrace._tf_ops(space, "/device:TPU:0") == {
        "%add.1 = f32[] add()": "jit(run)/evaluate/add:",
        "%mul.3 = f32[] multiply()": "jit(r)/x/y:"}
    assert progtrace._tf_ops(space, "/device:TPU:1") == {}


# -- traces recorded on the chip -----------------------------------------------------

def _recorded():
    return sorted(os.path.basename(p)[: -len(".progtrace.json.gz")]
                  for p in glob.glob(os.path.join(DATA,
                                                  "*.progtrace.json.gz")))


def test_recorded_program_traces_are_there():
    assert set(_recorded()) >= {"search-effb0-pop32k", "drift-effb0-pop2k"}


@pytest.mark.parametrize("cell", _recorded())
def test_recorded_program_trace_reduces_to_recorded_numbers(cell):
    rec = progtrace.read(os.path.join(DATA, f"{cell}.progtrace.json.gz"))
    run = {"progtrace": ProgTrace.from_json(rec["progtrace"]),
           "searches": searches(*rec["counts"])}
    listed = {m["name"] for m in layout.metrics_for(cell, "per_layer")}
    assert set(METRICS) <= listed
    assert set(rec["metrics"]) == set(METRICS)
    for k, v in rec["metrics"].items():
        got = layout.metric_reader(k).read(run)
        assert math.isfinite(got) and got > 0, k
        assert got == pytest.approx(v, rel=1e-9), k
    # every search ran its whole budget, and its entry holds its device span
    assert len(run["progtrace"].entries()) == len(rec["counts"])
    assert len({c["generations"] for c in rec["counts"]}) == 1
