"""Flight-recorder test suite (PR 6).

Four layers of protection around ``repro.obs``:

  * **invisibility** — every serving scenario replays bit-identically
    with the recorder enabled vs absent: observing a run must never
    change a placement, a price or a shed decision;
  * **golden trace** — a tiny deterministic run's Perfetto export is
    pinned byte-for-byte (parsed-JSON equality) against a committed
    fixture and schema-validated (ph/ts/dur/pid/tid, one complete span
    per lifecycle phase, stage spans nested in their request envelope);
  * **audit** — plan records carry the cache regime and search effort,
    dispatch/completion back-fill predicted-vs-realized latencies, the
    calibration block surfaces through ``Telemetry.summary()``, and the
    event-sparse emulator's skips are logged with their certificate;
  * **telemetry edge cases** — empty/single-bucket histogram
    percentiles, histogram merge ≡ recording the union (property test),
    shed precision with zero scorable sheds, attainment with zero
    injected, and ``format_table`` rendering of None metrics.
"""
import json
import pathlib

import numpy as np
import pytest

try:
    from hypothesis import given, settings, strategies as st
except ImportError:                      # optional dep: fall back to the
    from _hypothesis_fallback import (   # vendored deterministic sampler
        given, settings, strategies as st)

from repro.cluster.emulator import ClusterSim
from repro.core.profiles import PAPER_FUNCTIONS, ProfileTable
from repro.core.scheduler import ESGScheduler
from repro.core.workflows import PAPER_APPS
from repro.obs import (NULL_RECORDER, AuditLog, MetricsBus, PlanRecord,
                       Recorder, SpanTracer)
from repro.obs.validate import (validate_metrics, validate_nesting,
                                validate_trace)
from repro.serving import Gateway, get_autoscaler, get_scenario
from repro.serving.telemetry import (LatencyHistogram, Telemetry,
                                     format_table)
from repro.serving.traces import SCENARIOS

APPS = list(PAPER_APPS)
HERE = pathlib.Path(__file__).resolve().parent
GOLDEN = HERE / "fixtures" / "golden_trace_mmpp_n6.json"
N_REQ = 24


@pytest.fixture(scope="module")
def tables():
    return {n: ProfileTable.build(p) for n, p in PAPER_FUNCTIONS.items()}


def _run(tables, scenario, n=N_REQ, seed=0, slo_mult=1.0, recorder=None,
         placement="locality", autoscaler="ewma", shed=True, **sim_kw):
    sched = ESGScheduler(PAPER_APPS, tables, placement=placement)
    sim = ClusterSim(PAPER_APPS, tables, PAPER_FUNCTIONS, sched,
                     seed=seed, count_overhead=False,
                     autoscaler=get_autoscaler(autoscaler),
                     recorder=recorder, **sim_kw)
    gw = Gateway(sim, shed_doomed=shed)
    sc = get_scenario(scenario, app_names=APPS)
    gw.inject(sc, n, seed=seed + 1, slo_mult=slo_mult)
    tel = gw.run()
    return tel, sim


def _timeline(sim):
    tasks = [(t.start_ms, t.end_ms, t.exec_start_ms, t.invoker, t.stage,
              t.func, t.config, t.tier, t.cold, t.cost, t.quota_slices,
              t.penalty_ms, t.full_penalty_ms)
             for t in sim.tasks]
    done = [(i.uid, i.arrival_ms, i.finish_ms) for i in sim.completed]
    shed = [i.uid for i in sim.shed]
    return tasks, done, shed, sim.total_cost, sim.cold_starts, \
        sim.remote_transfers


# ---------------------------------------------------------------------------
# invisibility: the recorder never changes a run
# ---------------------------------------------------------------------------
def test_default_recorder_is_the_shared_null_object(tables):
    sched = ESGScheduler(PAPER_APPS, tables)
    sim = ClusterSim(PAPER_APPS, tables, PAPER_FUNCTIONS, sched, seed=0)
    assert sim.recorder is NULL_RECORDER
    assert not sim.recorder.enabled


@pytest.mark.parametrize("scenario", sorted(SCENARIOS))
def test_recorder_replays_every_scenario_bit_identically(tables, scenario):
    tel_off, sim_off = _run(tables, scenario)
    tel_on, sim_on = _run(tables, scenario, recorder=Recorder())
    assert _timeline(sim_on) == _timeline(sim_off)
    assert sim_on.slo_hit_rate() == sim_off.slo_hit_rate()
    s_on, s_off = tel_on.summary(), tel_off.summary()
    # the only summary difference the recorder may make is *adding* the
    # calibration block it alone can compute
    s_on.pop("predicted_vs_realized")
    s_off.pop("predicted_vs_realized")
    assert s_on == s_off


def test_recorder_invisible_under_memory_pressure_and_overlap(tables):
    kw = dict(n=40, hbm_per_vgpu_mb=256.0, shared_weights=True,
              overlap=True, prefetch=True, placement="memory")
    _, sim_off = _run(tables, "mmpp", **kw)
    rec = Recorder()
    _, sim_on = _run(tables, "mmpp", recorder=rec, **kw)
    assert _timeline(sim_on) == _timeline(sim_off)
    # the congested config exercises the device tracks: PCIe copies and
    # HBM demotions land on per-device pids
    doc = {"displayTimeUnit": "ms", "traceEvents": rec.tracer.events()}
    cats = validate_trace(doc, required=("request", "queue", "exec",
                                         "pcie"))
    validate_nesting(doc)
    assert cats["pcie"] > 0
    assert any(e["ph"] == "i" and e["cat"] == "hbm"
               for e in doc["traceEvents"])
    assert rec.metrics.total("demotions") > 0
    assert rec.metrics.total("xfer_demand_ms") > 0


# ---------------------------------------------------------------------------
# golden Perfetto trace
# ---------------------------------------------------------------------------
def _golden_doc(tables, tmp_path):
    rec = Recorder()
    _run(tables, "mmpp", n=6, recorder=rec)
    path = tmp_path / "trace.json"
    return rec.export(str(path), None, None), \
        json.loads(path.read_text())


def test_golden_trace_fixture_matches_and_validates(tables, tmp_path):
    written, doc = _golden_doc(tables, tmp_path)
    assert written == {"trace": str(tmp_path / "trace.json")}
    cats = validate_trace(doc)
    validate_nesting(doc)
    assert cats["request"] == 6
    assert doc["displayTimeUnit"] == "ms"
    golden = json.loads(GOLDEN.read_text())
    assert doc == golden, (
        "exported trace drifted from the committed golden fixture; "
        "if the change is intentional regenerate it with "
        "tests/test_observability.py::_golden_doc")


def test_trace_lanes_never_overlap(tables, tmp_path):
    _, doc = _golden_doc(tables, tmp_path)
    lanes: dict[tuple, list[tuple[float, float]]] = {}
    for e in doc["traceEvents"]:
        if e["ph"] == "X" and e["cat"] != "request":
            lanes.setdefault((e["pid"], e["tid"]), []).append(
                (e["ts"], e["ts"] + e["dur"]))
    for spans in lanes.values():
        spans.sort()
        for (a0, a1), (b0, b1) in zip(spans, spans[1:]):
            assert b0 >= a1 - 1e-6, "slices overlap on one lane"


def test_validate_trace_rejects_malformed():
    with pytest.raises(ValueError, match="traceEvents"):
        validate_trace({})
    with pytest.raises(ValueError, match="missing dur"):
        validate_trace({"traceEvents": [
            {"ph": "X", "ts": 0, "pid": 1, "tid": 0, "cat": "request"}]})
    with pytest.raises(ValueError, match="lifecycle"):
        validate_trace({"traceEvents": []})
    ok = {"traceEvents": [
        {"ph": "X", "ts": 0.0, "dur": 1.0, "pid": 10_000, "tid": 0,
         "cat": c, "name": c} for c in ("request", "queue", "exec")]}
    assert validate_trace(ok) == {"request": 1, "queue": 1, "exec": 1}
    bad = {"traceEvents": ok["traceEvents"] + [
        {"ph": "X", "ts": 50.0, "dur": 1.0, "pid": 10_000, "tid": 1,
         "cat": "exec", "name": "escape"}]}
    with pytest.raises(ValueError, match="escapes"):
        validate_nesting(bad)


def test_tracer_end_request_is_idempotent():
    tr = SpanTracer()
    tr.begin_request(7, "a", 0.0)
    tr.end_request(7, 10.0, 100.0)
    tr.end_request(7, 12.0, 100.0)       # multi-sink DAG second completion
    spans = [e for e in tr.events() if e["ph"] == "X"]
    assert len(spans) == 1 and spans[0]["dur"] == 10.0 * 1e3


# ---------------------------------------------------------------------------
# planner decision audit
# ---------------------------------------------------------------------------
def test_audit_records_regimes_and_calibration(tables, tmp_path):
    rec = Recorder()
    tel, sim = _run(tables, "mmpp", recorder=rec)
    audit = rec.audit
    assert len(audit.plans) == len(sim.tasks) >= 1
    regimes = audit.regimes()
    assert set(regimes) <= {"floor", "budget-free", "exact", "miss",
                            "nocache", "sunk"}
    assert regimes.get("miss", 0) > 0    # cold caches always miss first
    # every dispatched plan was back-filled at completion
    filled = [p for p in audit.plans if p.task_tid is not None]
    assert filled and all(p.predicted_ms is not None
                          and p.realized_ms is not None for p in filled)
    cal = audit.calibration()
    assert cal["n"] == len(filled) > 0
    assert cal["p90_abs_err"] >= 0.0
    assert all(v["n"] > 0 for v in cal["per_stage"].values())
    # the same block surfaces through the run telemetry
    assert tel.summary()["predicted_vs_realized"]["n"] == cal["n"]
    # JSONL export: one parseable typed record per line
    path = tmp_path / "audit.jsonl"
    n = audit.export_jsonl(str(path))
    lines = [json.loads(l) for l in path.read_text().splitlines()]
    assert len(lines) == n == len(audit.plans) + len(audit.skips)
    assert all(r["type"] in ("plan", "skip") for r in lines)


def test_audit_logs_sparse_skips_with_certificates(tables):
    # the config test_planner_fastpath pins for sparse_skips > 0: a
    # flash crowd on a starved fleet with wide slack and no shedding
    rec = Recorder()
    _, sim = _run(tables, "flash-crowd", n=100, slo_mult=8.0, shed=False,
                  n_invokers=2, recorder=rec)
    assert sim.sparse_skips > 0
    assert len(rec.audit.skips) == sim.sparse_skips
    assert all(s.certificate for s in rec.audit.skips)
    assert rec.metrics.total("sparse_skips") == sim.sparse_skips


def test_audit_unit_lifecycle():
    audit = AuditLog()
    rec = PlanRecord(t_ms=1.0, app="a", stage="s", n_jobs=2, g_slo_ms=100.0,
                     regime="miss", expansions=5, pruned_time=1,
                     pruned_cost=2, est_time_ms=80.0, est_job_cost=0.5,
                     slack_ms=20.0, n_candidates=3)
    audit.on_plan(rec)
    audit.on_dispatch("a", "s", tid=42, config="c", predicted_ms=80.0)
    audit.on_complete(42, realized_ms=88.0)
    assert rec.task_tid == 42 and rec.realized_ms == 88.0
    cal = audit.calibration()
    assert cal["n"] == 1
    assert cal["p50_err"] == pytest.approx(0.1)
    # unmatched dispatches and completions are ignored, not errors
    audit.on_dispatch("a", "other", tid=7, config="c", predicted_ms=1.0)
    audit.on_complete(999, 5.0)
    assert audit.calibration()["n"] == 1
    assert AuditLog().calibration() == {
        "n": 0, "mean_err": 0.0, "mean_abs_err": 0.0, "p50_err": 0.0,
        "p90_abs_err": 0.0, "per_stage": {}}


# ---------------------------------------------------------------------------
# metrics bus
# ---------------------------------------------------------------------------
def test_metrics_bus_windows_and_kinds(tmp_path):
    m = MetricsBus(window_ms=100.0)
    m.inc("c", 10.0)
    m.inc("c", 99.0, 2.0)
    m.inc("c", 150.0)
    m.gauge("g", 10.0, 5.0)
    m.gauge("g", 20.0, 7.0)              # same window: last value wins
    m.observe("h", 50.0, 3.0)
    m.observe("h", 60.0, 9.0)
    assert m.points("c") == [(0.0, 3.0), (100.0, 1.0)]
    assert m.total("c") == 4.0
    assert m.points("g") == [(0.0, 7.0)]
    assert m.points("h") == [(0.0, [2, 12.0, 3.0, 9.0])]
    with pytest.raises(ValueError, match="is a counter"):
        m.gauge("c", 0.0, 1.0)
    with pytest.raises(ValueError, match="not a counter"):
        m.total("g")
    with pytest.raises(ValueError, match="positive"):
        MetricsBus(window_ms=0.0)
    doc = m.to_json(str(tmp_path / "m.json"))
    assert validate_metrics(doc) == 3
    assert json.loads((tmp_path / "m.json").read_text()) == doc
    series = doc["series"]
    assert series["c"] == {"kind": "counter",
                           "points": [[0.0, 3.0], [100.0, 1.0]]}
    assert series["g"] == {"kind": "gauge", "points": [[0.0, 7.0]]}
    assert series["h"] == {"kind": "hist",
                           "points": [[0.0, 2, 12.0, 3.0, 9.0]]}


def test_recorder_exports_all_three_artifacts(tables, tmp_path):
    rec = Recorder()
    _run(tables, "mmpp", recorder=rec)
    out = rec.export(str(tmp_path / "t.json"), str(tmp_path / "m.json"),
                     str(tmp_path / "a.jsonl"))
    assert set(out) == {"trace", "metrics", "audit"}
    validate_trace(json.loads((tmp_path / "t.json").read_text()))
    assert validate_metrics(json.loads((tmp_path / "m.json").read_text())) \
        == len(rec.metrics.series)
    assert (tmp_path / "a.jsonl").read_text().strip()
    # metrics carry the headline serving series
    names = set(rec.metrics.series)
    assert {"tasks", "jobs", "plans", "queue_wait_ms", "exec_ms",
            "queue_depth", "slice_util", "hbm_used_mb",
            "admitted"} <= names
    assert rec.metrics.total("tasks") == len(_run(tables, "mmpp")[1].tasks)


# ---------------------------------------------------------------------------
# telemetry edge cases (satellites)
# ---------------------------------------------------------------------------
def test_histogram_empty_and_single_bucket_percentiles():
    h = LatencyHistogram()
    assert h.percentile(50) == 0.0 and h.mean == 0.0
    assert h.to_dict()["n"] == 0
    h.record(5.0)
    idx = int(np.searchsorted(h.bounds, 5.0, side="right"))
    lo, hi = h.bounds[idx - 1], h.bounds[idx]
    for p in (50.0, 100.0):
        assert lo <= h.percentile(p) <= hi
    assert 0.0 <= h.percentile(0.0) <= hi   # rank 0: underflow edge
    assert h.mean == 5.0 and h.max_ms == 5.0


def test_histogram_cumsum_cache_invalidated_by_record():
    h = LatencyHistogram()
    h.record(10.0)
    p95_before = h.percentile(95)
    assert h._cum is not None            # cached by the percentile call
    h.record(10_000.0)
    assert h._cum is None                # record() must invalidate
    assert h.percentile(95) > p95_before


@settings(max_examples=25, deadline=None)
@given(st.integers(0, 60), st.integers(0, 60), st.integers(0, 2 ** 16))
def test_histogram_merge_equals_recording_the_union(n_a, n_b, seed):
    rng = np.random.default_rng(seed)
    xs = list(10 ** rng.uniform(-1, 6, n_a))
    ys = list(10 ** rng.uniform(-1, 6, n_b))
    a, b, ref = LatencyHistogram(), LatencyHistogram(), LatencyHistogram()
    for v in xs:
        a.record(v)
        ref.record(v)
    for v in ys:
        b.record(v)
        ref.record(v)
    out = a.merge(b)
    assert out is a
    assert np.array_equal(a.counts, ref.counts)
    assert a.n == ref.n and a.max_ms == ref.max_ms
    assert a.total == pytest.approx(ref.total)
    for p in (0, 25, 50, 90, 99, 100):
        assert a.percentile(p) == ref.percentile(p)


def test_histogram_merge_rejects_layout_mismatch():
    with pytest.raises(ValueError, match="bucket layouts"):
        LatencyHistogram().merge(LatencyHistogram(buckets_per_decade=4))


def test_shed_precision_none_with_zero_scorable_sheds():
    tel = Telemetry()
    assert tel.shed_precision() is None
    tel.on_shed("app")                   # counted but not scorable
    assert tel.shed_precision() is None
    assert tel.summary()["shed_precision"] is None


def test_slo_attainment_with_zero_injected():
    tel = Telemetry()
    assert tel.slo_attainment() == 0.0
    assert tel.cost_per_1k() == 0.0
    assert tel.summary()["slo_attainment"] == 0.0


def test_format_table_renders_none_as_dash():
    row = Telemetry().summary()
    row["scenario"] = "empty"
    out = format_table([row], extra_cols=[
        ("shed_precision", "shed_prec", "{:.2f}"),
        ("prefetch_hit_rate", "pf_hit", "{:.2f}"),
        ("missing_key", "mk", "{:.1f}")])
    line = out.splitlines()[2]
    assert line.split()[-3:] == ["-", "-", "-"]
    assert "None" not in out
