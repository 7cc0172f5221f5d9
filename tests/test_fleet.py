"""The fleet orchestration runtime (`repro.fleet`): manifest state machine
and atomic claims, worker loop + bounded retries, deterministic shard merge
(edge cases: empty shard set, duplicate-cell conflicts, failed-cell
placeholders), resume-without-recompute, and merged-vs-serial report
identity on a multi-model × multi-system sweep.  Plus the declarative
accuracy satellite (`AccuracySpec` / measured-oracle registry)."""

import dataclasses
import json
import os

import pytest

from repro.core.accuracy import (MeasuredAccuracy, ProxyAccuracy,
                                 register_accuracy_measure)
from repro.explore import (AccuracySpec, Campaign, ExplorationSpec, LinkSpec,
                           ModelRef, PlatformSpec, SearchSettings, SweepSpec,
                           SystemSpec, run_spec)
from repro.fleet import (Manifest, ManifestError, ReportMergeError,
                         merge_manifest, merge_shards, report_fingerprint)
from repro.fleet.worker import run_cell, run_worker

TWO_PLATFORM = SystemSpec(
    platforms=(PlatformSpec("A", "eyr", bits=16),
               PlatformSpec("B", "smb", bits=8)),
    links=("gige",), name="AB")

SLOW_LINK = SystemSpec(
    platforms=(PlatformSpec("A", "eyr", bits=16),
               PlatformSpec("B", "smb", bits=8)),
    links=(LinkSpec(base="gige", rate_bps=1e8),), name="AB-slow")

SPEC = ExplorationSpec(
    model=ModelRef("cnn", "squeezenet11", {"in_hw": 64}),
    system=TWO_PLATFORM,
    objectives=("latency", "energy"),
    search=SearchSettings(strategy="nsga2", seed=0, pop_size=32, n_gen=6))


def make_campaign(n_models=2, systems=(TWO_PLATFORM,)):
    names = ("squeezenet11", "vgg16", "regnetx_400mf")[:n_models]
    return Campaign(SPEC,
                    models=[ModelRef("cnn", n, {"in_hw": 64})
                            for n in names],
                    systems=list(systems))


# -- SweepSpec ----------------------------------------------------------------

def test_sweep_spec_roundtrip_and_hash():
    sweep = make_campaign(2).to_sweep()
    s2 = SweepSpec.from_json(sweep.to_json())
    assert s2 == sweep
    assert s2.spec_hash() == sweep.spec_hash()
    assert sweep.cells() == (("squeezenet11", "AB"), ("vgg16", "AB"))
    # a different seed is a different sweep
    other = SweepSpec(template=dataclasses.replace(
        SPEC, search=dataclasses.replace(SPEC.search, seed=7)),
        models=sweep.models, systems=sweep.systems)
    assert other.spec_hash() != sweep.spec_hash()


def test_sweep_defaults_to_template_model_system():
    sweep = SweepSpec(template=SPEC)
    assert sweep.models == (SPEC.model,)
    assert sweep.systems == (SPEC.system,)
    assert sweep.cells() == (("squeezenet11", "AB"),)


# -- manifest state machine ---------------------------------------------------

def test_manifest_create_load_and_claims(tmp_path):
    d = str(tmp_path / "m")
    m = make_campaign(2).to_manifest(d)
    assert len(m.cells) == 2
    assert all(m.cell_state(c.id) == "pending" for c in m.cells)

    cid = m.cells[0].id
    assert m.claim(cid, "w1")
    assert not m.claim(cid, "w2")          # exclusive
    assert m.cell_state(cid) == "running"
    m.release(cid)
    assert m.cell_state(cid) == "pending"

    # idempotent reopen; different sweep refuses
    m2 = make_campaign(2).to_manifest(d)
    assert m2.spec_hash == m.spec_hash
    with pytest.raises(ManifestError, match="different sweep"):
        make_campaign(1).to_manifest(d)
    assert Manifest.load(d).status()["cells"] == 2


def test_manifest_retry_budget_and_terminal_failure(tmp_path):
    m = make_campaign(1).to_manifest(str(tmp_path / "m"), max_retries=1)
    cid = m.cells[0].id
    assert m.record_failure(cid, "w", "boom 1") == 1
    assert m.cell_state(cid) == "pending"      # one retry left
    assert m.record_failure(cid, "w", "boom 2") == 2
    assert m.cell_state(cid) == "failed"       # budget spent
    assert m.pending_cells() == []
    assert m.complete()
    errs = m.failure_records(cid)
    assert len(errs) == 2 and "boom 2" in errs[-1]["error"]


def _backdate(path, by_s=60.0):
    """Age a claim file past the reclaim grace period."""
    t = os.stat(path).st_mtime - by_s
    os.utime(path, (t, t))


def test_reclaim_stale_only_dead_pids(tmp_path):
    m = make_campaign(2).to_manifest(str(tmp_path / "m"))
    a, b = m.cells[0].id, m.cells[1].id
    m.claim(a, "live")                          # our own (live) pid
    m.claim(b, "dead")
    # rewrite b's claim with a dead pid
    with open(m._claim_path(b), "w") as f:
        json.dump({"worker": "dead", "pid": 2 ** 22 + 12345,
                   "host": __import__("socket").gethostname(),
                   "time": 0}, f)
    # claims inside the grace window are never touched, even with force
    assert m.reclaim_stale() == []
    assert m.reclaim_stale(force=True) == []
    _backdate(m._claim_path(a))
    _backdate(m._claim_path(b))
    assert m.reclaim_stale() == [b]
    assert m.cell_state(a) == "running"
    assert m.cell_state(b) == "pending"
    assert m.reclaim_stale(force=True) == [a]


def test_lease_ttl_reclaims_hung_worker(tmp_path):
    """A claim held by a *live* pid whose lease expired (hung worker) is
    reclaimed with ``lease_ttl_s``; a refreshed lease survives."""
    m = make_campaign(2).to_manifest(str(tmp_path / "m"))
    a, b = m.cells[0].id, m.cells[1].id
    m.claim(a, "hung")                 # our own pid: provably alive
    m.claim(b, "slow-but-live")
    _backdate(m._claim_path(a), by_s=60.0)
    _backdate(m._claim_path(b), by_s=60.0)
    # pid probing alone never touches live-pid claims, however old
    assert m.reclaim_stale() == []
    # b's worker heartbeats; a's lease stays expired
    assert m.refresh_claim(b)
    assert m.reclaim_stale(lease_ttl_s=30.0) == [a]
    assert m.cell_state(a) == "pending"
    assert m.cell_state(b) == "running"
    # the reclaimed claim is gone, so a further refresh reports it
    assert not m.refresh_claim(a)
    with pytest.raises(ValueError, match="lease_ttl_s"):
        m.reclaim_stale(lease_ttl_s=0.0)


def test_lease_heartbeat_refreshes_until_claim_released(tmp_path):
    """The worker's heartbeat thread keeps bumping the claim's mtime and
    exits on its own once the claim disappears."""
    import threading
    import time as _time

    import repro.fleet.worker as W
    m = make_campaign(1).to_manifest(str(tmp_path / "m"))
    cid = m.cells[0].id
    m.claim(cid, "w")
    _backdate(m._claim_path(cid), by_s=60.0)
    before = os.stat(m._claim_path(cid)).st_mtime
    stop = threading.Event()
    th = threading.Thread(target=W._lease_heartbeat,
                          args=(m, cid, 0.3, stop), daemon=True)
    th.start()
    _time.sleep(0.4)                   # >= one heartbeat period (lease/3)
    assert os.stat(m._claim_path(cid)).st_mtime > before
    m.release(cid)                     # claim vanishes mid-heartbeat
    th.join(timeout=3.0)
    assert not th.is_alive()
    stop.set()


def test_run_worker_validates_lease(tmp_path):
    d = str(tmp_path / "m")
    make_campaign(1).to_manifest(d)
    with pytest.raises(ValueError, match="lease_s"):
        run_worker(d, lease_s=0.0)


# -- merge edge cases ---------------------------------------------------------

def test_merge_empty_shard_set_raises(tmp_path):
    m = make_campaign(2).to_manifest(str(tmp_path / "m"))
    with pytest.raises(ReportMergeError, match="without a shard"):
        merge_manifest(m)


def test_merge_empty_sweep_yields_empty_report():
    rep = merge_shards({"t": 1}, [], [])
    assert rep.entries == [] and rep.wall_s == 0.0


def test_merge_duplicate_cell_conflict():
    cells = [("c0", "m", "s")]
    e1 = {"model": "m", "system": "s", "wall_s": 1.0, "pareto": [1]}
    e2 = {"model": "m", "system": "s", "wall_s": 2.0, "pareto": [1]}
    e3 = {"model": "m", "system": "s", "wall_s": 1.0, "pareto": [2]}
    # identical payloads (timing-stripped) dedupe silently
    rep = merge_shards({}, cells, [("c0", e1), ("c0", e2)])
    assert len(rep.entries) == 1
    # diverging payloads are a hard conflict
    with pytest.raises(ReportMergeError, match="conflicting shards"):
        merge_shards({}, cells, [("c0", e1), ("c0", e3)])
    # shard for a cell outside the sweep is rejected
    with pytest.raises(ReportMergeError, match="unknown cell"):
        merge_shards({}, cells, [("cX", e1)])


def test_merge_failed_cell_placeholder(tmp_path):
    m = make_campaign(2).to_manifest(str(tmp_path / "m"), max_retries=0)
    good, bad = m.cells
    m.write_shard(good.id, run_cell(m, good), "w")
    m.record_failure(bad.id, "w", "ValueError: kaput")
    # without allow_failed the merge refuses to pose as complete
    with pytest.raises(ReportMergeError, match="without a shard"):
        merge_manifest(m)
    rep = merge_manifest(m, allow_failed=True)
    assert len(rep.entries) == 2
    ph = rep.entries[1]
    assert ph["failed"] and "kaput" in ph["error"]
    assert ph["model"] == bad.model and ph["system"] == bad.system
    assert ph["pareto"] == [] and ph["selected"] is None
    # placeholder still JSON-serializable through CampaignReport
    assert json.loads(rep.to_json())["entries"][1]["failed"]


# -- merged == serial ---------------------------------------------------------

def test_fleet_merge_equals_serial_3x2():
    """3 models × 2 systems: in-process worker sweep merges to a report
    fingerprint-identical to the serial Campaign.run (same seeds)."""
    camp = make_campaign(3, systems=(TWO_PLATFORM, SLOW_LINK))
    serial = camp.run().report
    import tempfile
    with tempfile.TemporaryDirectory() as d:
        m = camp.to_manifest(d)
        assert len(m.cells) == 6
        stats = run_worker(d)
        assert stats == {"done": 6, "failed": 0}
        merged = merge_manifest(d)
    assert report_fingerprint(merged) == report_fingerprint(serial)
    # order is serial (model-major), not shard-arrival
    assert [(e["model"], e["system"]) for e in merged.entries] == \
           [(e["model"], e["system"]) for e in serial.entries]


def test_resume_does_not_recompute_done_cells(tmp_path):
    """Kill-and-resume semantics: cells finished before a crash keep their
    shards byte-identical; only pending work runs again."""
    d = str(tmp_path / "m")
    camp = make_campaign(2)
    m = camp.to_manifest(d)
    first, second = m.cells
    m.write_shard(first.id, run_cell(m, first), "w0")   # "pre-crash" work
    before = open(m._shard_path(first.id)).read()
    mtime = os.stat(m._shard_path(first.id)).st_mtime_ns
    # crashed worker left a claim on the second cell with a dead pid
    m.claim(second.id, "dead")
    with open(m._claim_path(second.id), "w") as f:
        json.dump({"worker": "dead", "pid": 2 ** 22 + 999,
                   "host": __import__("socket").gethostname(), "time": 0}, f)
    _backdate(m._claim_path(second.id))
    # resume: reclaim + one worker finishes only the pending cell
    assert m.reclaim_stale() == [second.id]
    stats = run_worker(d)
    assert stats == {"done": 1, "failed": 0}
    assert open(m._shard_path(first.id)).read() == before
    assert os.stat(m._shard_path(first.id)).st_mtime_ns == mtime
    merged = merge_manifest(d)
    assert report_fingerprint(merged) == \
           report_fingerprint(camp.run().report)


def test_worker_retries_transient_failure(tmp_path, monkeypatch):
    """A cell that fails once and then succeeds ends done, within budget."""
    d = str(tmp_path / "m")
    make_campaign(1).to_manifest(d, max_retries=2)
    import repro.fleet.worker as W
    real = W.run_cell
    calls = {"n": 0}

    def flaky(manifest, cell, caches=None):
        calls["n"] += 1
        if calls["n"] == 1:
            raise RuntimeError("transient")
        return real(manifest, cell, caches)

    monkeypatch.setattr(W, "run_cell", flaky)
    stats = W.run_worker(d)
    assert stats == {"done": 1, "failed": 1}
    m = Manifest.load(d)
    assert m.cell_state(m.cells[0].id) == "done"
    assert m.attempts(m.cells[0].id) == 1


# -- declarative accuracy (satellite) -----------------------------------------

def test_accuracy_spec_proxy_knobs_roundtrip():
    spec = dataclasses.replace(
        SPEC, objectives=("latency", "accuracy"),
        accuracy=AccuracySpec(kind="proxy", base_accuracy=0.9,
                              noise_scale=2.0))
    s2 = ExplorationSpec.from_json(spec.to_json())
    assert s2 == spec
    res = run_spec(spec)
    assert res.selected is not None
    # knobs actually reach the oracle: accuracy capped by base_accuracy
    assert all(e.accuracy <= 0.9 + 1e-9 for e in res.pareto)


def test_accuracy_spec_validation():
    with pytest.raises(ValueError, match="kind"):
        AccuracySpec(kind="magic")
    with pytest.raises(ValueError, match="measure"):
        AccuracySpec(kind="measured")
    # a measure name with the default/typo'd proxy kind would silently run
    # the wrong oracle — rejected instead
    with pytest.raises(ValueError, match="mean kind='measured'"):
        AccuracySpec(kind="proxy", measure="cnn_fakequant")
    with pytest.raises(ValueError, match="unknown accuracy measure"):
        AccuracySpec(kind="measured", measure="no-such").build(
            None, [], None)


def test_measured_accuracy_declarative_path():
    """A registered measured oracle drives the NumPy strategies through the
    spec; per-cut caching comes from MeasuredAccuracy."""
    calls = []

    def factory(graph=None, schedule=None, system=None, *, bonus=0.0):
        assert schedule is not None and system is not None

        def measure(cuts):
            calls.append(tuple(cuts))
            return 0.5 + bonus

        return measure

    register_accuracy_measure("test_const", factory, override=True)
    spec = dataclasses.replace(
        SPEC, objectives=("latency", "accuracy"),
        search=SearchSettings(strategy="exhaustive"),
        accuracy=AccuracySpec(kind="measured", measure="test_const",
                              options={"bonus": 0.25}))
    res = run_spec(spec)
    assert calls, "measured oracle was never invoked"
    assert all(abs(e.accuracy - 0.75) < 1e-9 for e in res.pareto)
    # built oracle is the caching wrapper
    built = spec.accuracy.build(None, [], TWO_PLATFORM.build())
    assert isinstance(built, MeasuredAccuracy)


def test_measured_table_oracle_builtin():
    acc = AccuracySpec(kind="measured", measure="table",
                       options={"table": {"3": 0.91, "-1": 0.4},
                                "default": 0.1})
    fn = acc.build(None, [], TWO_PLATFORM.build())
    assert fn((3,)) == 0.91 and fn((-1,)) == 0.4 and fn((7,)) == 0.1


def test_jit_path_falls_back_on_measured_accuracy():
    """jit_nsga2 + measured oracle + accuracy objective: documented
    fallback to the NumPy strategy, not a crash or silent drop."""
    register_accuracy_measure(
        "test_half", lambda graph=None, schedule=None, system=None:
        (lambda cuts: 0.5), override=True)
    spec = dataclasses.replace(
        SPEC, objectives=("latency", "accuracy"),
        search=SearchSettings(strategy="jit_nsga2", seed=0, pop_size=16,
                              n_gen=2),
        accuracy=AccuracySpec(kind="measured", measure="test_half"))
    with pytest.warns(UserWarning, match="falling back"):
        res = run_spec(spec)
    assert res.selected is not None
    assert all(abs(e.accuracy - 0.5) < 1e-9 for e in res.pareto)


def test_default_accuracy_unchanged():
    """No accuracy field -> the default ProxyAccuracy oracle (seed parity
    with pre-AccuracySpec reports)."""
    res_default = run_spec(SPEC)
    res_explicit = run_spec(dataclasses.replace(
        SPEC, accuracy=AccuracySpec(kind="proxy")))
    assert [e.cuts for e in res_default.pareto] == \
           [e.cuts for e in res_explicit.pareto]
    assert isinstance(ProxyAccuracy([], TWO_PLATFORM.build()), ProxyAccuracy)


# -- one worker per accelerator host -------------------------------------------

def _fake_popen(started):
    class FakePopen:
        def __init__(self, cmd, env=None):
            started.append(cmd)
    return FakePopen


@pytest.mark.parametrize("backend,want", [("cpu", 3), ("tpu", 1)])
def test_start_workers_one_per_accelerator_host(tmp_path, monkeypatch,
                                                backend, want):
    """Every JAX process takes all of its host's chips, so on an
    accelerator the launcher starts a single worker; on the CPU it starts
    as many as asked."""
    import warnings

    import repro.fleet.launch as L
    started = []
    monkeypatch.setattr(L, "worker_backend", lambda env: backend)
    monkeypatch.setattr(L.subprocess, "Popen", _fake_popen(started))
    with warnings.catch_warnings(record=True) as caught:
        warnings.simplefilter("always")
        L.start_workers(str(tmp_path), 3)
    assert len(started) == want
    assert bool(caught) == (want == 1)


def test_worker_backend_skips_probe_on_cpu(monkeypatch):
    import repro.fleet.launch as L

    def no_probe(*a, **kw):
        raise AssertionError("probed although JAX_PLATFORMS=cpu")
    monkeypatch.setattr(L.subprocess, "run", no_probe)
    assert L.worker_backend({"JAX_PLATFORMS": "cpu"}) == "cpu"


def test_launcher_imports_initialise_no_backend():
    """The fleet parent imports the campaign and launcher modules before it
    starts workers; importing them must not claim a device."""
    import subprocess
    import sys
    code = ("import repro.explore.campaign, repro.fleet.launch, "
            "repro.fleet.__main__\n"
            "from jax._src import xla_bridge\n"
            "assert not xla_bridge._backends, xla_bridge._backends\n"
            "print('NO_BACKEND')")
    src = os.path.join(os.path.dirname(__file__), "..", "src")
    env = dict(os.environ, PYTHONPATH=src, JAX_PLATFORMS="cpu")
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=300)
    assert out.returncode == 0, out.stderr
    assert "NO_BACKEND" in out.stdout
