"""The reduction from traces to numbers, on a hand-made trace and on traces
recorded from short runs on one TPU v5e (``data/``)."""

import gzip
import json
import os
import types

import pytest

from benchmarks.chip import devtrace, layout
from benchmarks.chip.devtrace import Event, TraceView

DATA = os.path.join(layout.HERE, "data")


def hand_view():
    # device 0: a loop [0, 10) holding two kernels, then a gap, then an op
    ops = [Event("%while.1 = (s32[]) while(...)", 0.0, 10.0),
           Event("%packed_domination.3 = u32[8,8] custom-call(...)", 1.0, 4.0),
           Event("%popcnt_fusion.2 = u32[8] fusion(...)", 6.0, 2.0),
           Event("%copy.7 = f32[4] copy(...)", 14.0, 2.0)]
    host = [Event("bench/window", 0.0, 20.0), Event("bench/search", 0.0, 10.5),
            Event("bench/drain", 10.5, 9.5)]
    mods = [Event("jit_run(1)", 0.0, 10.0), Event("jit_other(2)", 14.0, 2.0)]
    return TraceView([ops], [mods], host, (0.0, 20.0))


def test_busy_idle_and_gaps():
    v = hand_view()
    assert v.busy_s() == pytest.approx(12.0)
    assert v.idle_share() == pytest.approx(40.0)
    gaps = v.idle_gaps()
    assert gaps[0] == ("bench/drain", pytest.approx(4.0))
    assert gaps[1] == ("bench/drain", pytest.approx(4.0))


def test_self_times_and_short_names():
    top = dict(hand_view().top_ops())
    assert top == {"while": pytest.approx(4.0),
                   "packed_domination": pytest.approx(4.0),
                   "popcnt_fusion": pytest.approx(2.0),
                   "copy": pytest.approx(2.0)}


def test_round_trip(tmp_path):
    p = str(tmp_path / "v.json.gz")
    hand_view().save(p)
    v = TraceView.read(p)
    assert v.to_json() == hand_view().to_json()


def _recorded():
    out = []
    for f in sorted(os.listdir(DATA)) if os.path.isdir(DATA) else []:
        if f.endswith(".inputs.json.gz"):
            out.append(f[: -len(".inputs.json.gz")])
    return out


def _inputs(rec):
    """Rebuild the readers' inputs from their recorded JSON form."""
    inp = dict(rec["inputs"])
    if "searches" in inp:
        inp["searches"] = [types.SimpleNamespace(**s) for s in inp["searches"]]
    return inp


@pytest.mark.parametrize("cell", _recorded())
def test_recorded_trace_reduces_to_recorded_numbers(cell):
    with gzip.open(os.path.join(DATA, f"{cell}.inputs.json.gz"), "rt") as f:
        rec = json.load(f)
    view = TraceView.read(os.path.join(DATA, f"{cell}.trace.json.gz"))
    assert view.busy_s() == pytest.approx(rec["busy_s"], rel=1e-9)
    assert view.window_s == pytest.approx(rec["window_s"], rel=1e-9)
    run = dict(_inputs(rec), trace=view, peaks=layout.peaks("TPU v5 lite"))
    listed = {m["name"] for m in layout.metrics_for(cell, "per_layer")}
    assert listed <= rec["metrics"].keys()
    got = {k: layout.metric_reader(k).read(run) for k in rec["metrics"]}
    for k, v in rec["metrics"].items():
        assert got[k] == pytest.approx(v, rel=1e-9), k
    for k in ("rank_roofline", "search_mfu"):
        if k in got:
            assert 0 < got[k] <= 100, (k, got[k])


def test_recorded_data_is_there():
    assert set(_recorded()) >= {"search-effb0-pop32k", "drift-effb0-pop2k"}


def test_devtrace_reads_only_bench_spans():
    assert devtrace.WINDOW.startswith("bench/")
