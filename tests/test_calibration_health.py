"""Closed-loop observability test suite (PR 7).

Four layers of protection around ``repro.obs.calibrate`` and
``repro.obs.health``:

  * **dormancy** — the entire feedback layer defaults off: a recorder
    carrying a health engine (unwired to any consumer) replays every
    serving scenario bit-identically, and an *attached* calibrator whose
    predictions exactly match reality (``noise_sigma=0``) never
    publishes a factor, so the run stays bit-identical too;
  * **calibrator semantics** — EWMA no-op at predicted == realized,
    warmup gating, convergence to the true ratio under injected
    multiplicative skew, outlier clipping, clamping, publish
    hysteresis, conservative headroom, and parameter validation;
  * **health engine** — multi-window burn-rate alerts fire on a
    synthetic miss burst and clear on recovery (sheds spend budget),
    drift/queue/spike detectors transition correctly, alert exports
    round-trip through ``repro.obs.validate``;
  * **plumbing** — plan-cache keys grow the factor axis exactly when a
    factor is published (stale plans become unreachable), the gateway
    sheds earlier under a firing alert, the vertical autoscaler
    withholds opportunistic grows, and ``Telemetry.summary()`` carries
    the calibration and health blocks.
"""
import dataclasses
import json
import pathlib
from types import SimpleNamespace

import numpy as np
import pytest

from repro.cluster.emulator import ClusterSim
from repro.core.profiles import PAPER_FUNCTIONS, ProfileTable
from repro.core.scheduler import ESGScheduler
from repro.core.workflows import PAPER_APPS
from repro.obs import (AuditLog, HealthEngine, PlanRecord,
                       ProfileCalibrator, Recorder)
from repro.obs.calibrate import RATIO_CLIP
from repro.obs.health import (ALERT_KINDS, CAL_DRIFT, CLEARED, COLD_SPIKE,
                              FIRING, PREFETCH_WASTE, QUEUE_BUILDUP,
                              SLO_BURN)
from repro.obs.validate import (main as validate_main, validate_audit,
                                validate_health, validate_metrics)
from repro.serving import Gateway, get_autoscaler, get_scenario
from repro.serving.autoscaler import AUTOSCALERS
from repro.serving.traces import SCENARIOS

APPS = list(PAPER_APPS)
N_REQ = 24


@pytest.fixture(scope="module")
def tables():
    return {n: ProfileTable.build(p) for n, p in PAPER_FUNCTIONS.items()}


def _run(tables, scenario, n=N_REQ, seed=0, slo_mult=1.0, recorder=None,
         calibrator=None, **sim_kw):
    sched = ESGScheduler(PAPER_APPS, tables)
    if calibrator is not None:
        sched.calibrator = calibrator.attach(recorder.audit)
    sim = ClusterSim(PAPER_APPS, tables, PAPER_FUNCTIONS, sched,
                     seed=seed, count_overhead=False,
                     autoscaler=get_autoscaler("ewma"),
                     recorder=recorder, **sim_kw)
    gw = Gateway(sim)
    gw.inject(get_scenario(scenario, app_names=APPS), n, seed=seed + 1,
              slo_mult=slo_mult)
    tel = gw.run()
    return tel, sim, sched


def _timeline(sim):
    tasks = [(t.start_ms, t.end_ms, t.exec_start_ms, t.invoker, t.stage,
              t.func, t.config, t.tier, t.cold, t.cost, t.quota_slices,
              t.penalty_ms, t.full_penalty_ms)
             for t in sim.tasks]
    done = [(i.uid, i.arrival_ms, i.finish_ms) for i in sim.completed]
    shed = [i.uid for i in sim.shed]
    return tasks, done, shed, sim.total_cost, sim.cold_starts, \
        sim.remote_transfers


def _rec(app="image_classification", stage="0:super_resolution",
         raw=None, exec_ms=None, predicted=None, realized=None, t=0.0):
    """A PlanRecord carrying only the fields the feedback layer reads."""
    return PlanRecord(t, app, stage, 1, 100.0, "exact", 0, 0, 0,
                      None, None, None, 1,
                      predicted_ms=predicted, realized_ms=realized,
                      predicted_raw_ms=raw, realized_exec_ms=exec_ms)


# ---------------------------------------------------------------------------
# dormancy: calibration off (or unpublished) never changes a run
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("scenario", sorted(SCENARIOS))
def test_health_carrying_recorder_replays_bit_identically(tables, scenario):
    """A recorder with a health engine attached — but no consumer wired
    — observes every scenario without changing a single decision."""
    _, sim_off, _ = _run(tables, scenario)
    rec = Recorder(health=HealthEngine())
    _, sim_on, _ = _run(tables, scenario, recorder=rec)
    assert _timeline(sim_on) == _timeline(sim_off)


@pytest.mark.parametrize("scenario", ["mmpp", "flash-crowd"])
def test_attached_calibrator_is_noop_when_predictions_exact(tables,
                                                            scenario):
    """With zero execution noise, predicted == realized for every stage:
    an *attached* calibrator consumes the whole stream yet never
    publishes, and the schedule stays bit-identical."""
    _, sim_off, _ = _run(tables, scenario, noise_sigma=0.0)
    cal = ProfileCalibrator()
    _, sim_on, _ = _run(tables, scenario, noise_sigma=0.0,
                        recorder=Recorder(trace=False), calibrator=cal)
    assert cal.observations > 0
    assert cal.updates == 0 and cal.version == 0
    assert all(f == 1.0 for f in
               cal.factors(APPS[0], ("0:super_resolution",)))
    assert _timeline(sim_on) == _timeline(sim_off)


# ---------------------------------------------------------------------------
# calibrator unit semantics
# ---------------------------------------------------------------------------
def test_calibrator_rejects_bad_params():
    with pytest.raises(ValueError):
        ProfileCalibrator(alpha=0.0)
    with pytest.raises(ValueError):
        ProfileCalibrator(alpha=1.5)
    with pytest.raises(ValueError):
        ProfileCalibrator(clamp=(0.0, 4.0))
    with pytest.raises(ValueError):
        ProfileCalibrator(clamp=(0.5, 0.9))
    with pytest.raises(ValueError):
        ProfileCalibrator(headroom=0.9)


def test_calibrator_noop_on_exact_predictions():
    cal = ProfileCalibrator()
    for i in range(50):
        cal.observe(_rec(raw=100.0, exec_ms=100.0, t=float(i)))
    assert cal.observations == 50
    assert cal.updates == 0 and cal.version == 0
    assert cal.factor("image_classification", "0:super_resolution") == 1.0


def test_calibrator_warmup_gate_then_publish():
    cal = ProfileCalibrator(min_samples=5)
    for i in range(4):
        cal.observe(_rec(raw=100.0, exec_ms=130.0, t=float(i)))
    assert cal.factor("image_classification", "0:super_resolution") == 1.0
    assert cal.version == 0
    cal.observe(_rec(raw=100.0, exec_ms=130.0, t=4.0))
    f = cal.factor("image_classification", "0:super_resolution")
    assert f == pytest.approx(1.3)
    assert cal.version == 1 and cal.updates == 1


def test_calibrator_converges_under_noisy_ratio():
    """Alternating 1.2/1.4 ratios: the EWMA settles near the 1.3 mean."""
    cal = ProfileCalibrator(alpha=0.2, min_samples=5)
    for i in range(80):
        realized = 120.0 if i % 2 == 0 else 140.0
        cal.observe(_rec(raw=100.0, exec_ms=realized, t=float(i)))
    assert cal.factor("image_classification",
                      "0:super_resolution") == pytest.approx(1.3, abs=0.05)
    assert cal.samples("image_classification", "0:super_resolution") == 80


def test_calibrator_clamps_extreme_factors():
    lo, hi = 0.25, 4.0
    cal = ProfileCalibrator(min_samples=1, clamp=(lo, hi))
    cal.observe(_rec(raw=100.0, exec_ms=700.0))
    assert cal.factor("image_classification", "0:super_resolution") == hi
    cal2 = ProfileCalibrator(min_samples=1, clamp=(lo, hi))
    cal2.observe(_rec(raw=1000.0, exec_ms=1.0))
    assert cal2.factor("image_classification", "0:super_resolution") == lo


def test_calibrator_clips_outlier_ratio_before_ewma():
    cal = ProfileCalibrator(alpha=0.2, min_samples=1)
    for i in range(20):
        cal.observe(_rec(raw=100.0, exec_ms=100.0, t=float(i)))
    cal.observe(_rec(raw=100.0, exec_ms=1e9, t=20.0))
    # a single pathological record moves the EWMA at most
    # alpha * (RATIO_CLIP.hi - 1), not to the clamp ceiling
    f = cal.factor("image_classification", "0:super_resolution")
    assert f <= 1.0 + 0.2 * (RATIO_CLIP[1] - 1.0) + 1e-9


def test_calibrator_publish_hysteresis():
    cal = ProfileCalibrator(alpha=1.0, min_samples=1,
                            publish_rel_step=0.02)
    cal.observe(_rec(raw=100.0, exec_ms=130.0))
    assert cal.version == 1
    # a sub-2% wiggle updates the working EWMA but not the factor
    cal.observe(_rec(raw=100.0, exec_ms=131.0, t=1.0))
    assert cal.version == 1
    assert cal.factor("image_classification",
                      "0:super_resolution") == pytest.approx(1.3)
    # a real move republishes and bumps the version again
    cal.observe(_rec(raw=100.0, exec_ms=160.0, t=2.0))
    assert cal.version == 2
    assert cal.factor("image_classification",
                      "0:super_resolution") == pytest.approx(1.6)


def test_calibrator_headroom_is_a_deliberate_overcorrection():
    cal = ProfileCalibrator(min_samples=3, headroom=1.10)
    for i in range(3):
        cal.observe(_rec(raw=100.0, exec_ms=100.0, t=float(i)))
    # even a perfect profile gets the configured conservative margin
    assert cal.factor("image_classification",
                      "0:super_resolution") == pytest.approx(1.10)


def test_calibrator_ignores_incomplete_records():
    cal = ProfileCalibrator(min_samples=1)
    cal.observe(_rec(raw=None, exec_ms=100.0))
    cal.observe(_rec(raw=100.0, exec_ms=None))
    cal.observe(_rec(raw=0.0, exec_ms=100.0))
    cal.observe(_rec(raw=100.0, exec_ms=-5.0))
    assert cal.observations == 0
    assert cal.factor("image_classification", "0:super_resolution") == 1.0


def test_calibrator_summary_structure():
    cal = ProfileCalibrator(min_samples=1)
    cal.observe(_rec(raw=100.0, exec_ms=130.0))
    s = cal.summary()
    assert s["observations"] == 1 and s["updates"] == 1
    block = s["per_stage"]["image_classification/0:super_resolution"]
    assert block["n"] == 1
    assert block["factor"] == pytest.approx(1.3)
    assert block["ewma"] == pytest.approx(1.3)


def test_convergence_under_injected_multiplicative_skew(tables):
    """Controller tables 30% slow on every function: the learned factors
    converge to ~1/1.3 and the audit error collapses vs uncalibrated."""
    skewed = {n: ProfileTable.build(
        dataclasses.replace(p, t1_ms=p.t1_ms * 1.3))
        for n, p in PAPER_FUNCTIONS.items()}
    rec_off = Recorder(trace=False)
    _run(skewed, "uniform-normal", n=120, recorder=rec_off)
    # hot tracking config (mirrors the calibration sweep arm): the
    # shipped defaults trade convergence speed for plan-cache
    # friendliness and need a longer run than this test injects
    cal = ProfileCalibrator(min_samples=5, publish_rel_step=0.02)
    rec_on = Recorder(trace=False)
    _run(skewed, "uniform-normal", n=120, recorder=rec_on, calibrator=cal)
    published = [v for v in cal._published.values()]
    assert published, "no factor ever published under a 30% skew"
    true = 1.0 / 1.3
    for f in published:
        assert f == pytest.approx(true, abs=0.08)
    err_off = rec_off.audit.calibration()["mean_abs_err"]
    err_on = rec_on.audit.calibration()["mean_abs_err"]
    assert err_on < err_off / 2.0


# ---------------------------------------------------------------------------
# health engine
# ---------------------------------------------------------------------------
def test_burn_rate_fires_on_burst_and_clears_on_recovery():
    eng = HealthEngine(default_target=0.9, min_requests=10)
    for i in range(20):                          # healthy baseline
        eng.on_request("app_a", 100.0 * i, ok=True)
    assert not eng.firing()
    for i in range(15):                          # synthetic miss burst
        eng.on_request("app_a", 5000.0 + 50.0 * i, ok=False)
    active = eng.firing(kind=SLO_BURN, app="app_a")
    assert len(active) == 1
    assert active[0].state == FIRING
    assert active[0].value >= eng.burn_threshold
    assert eng.early_warning("app_a")
    # recovery: the short window ages the burst out and the alert clears
    eng.on_request("app_a", 17_000.0, ok=True)
    assert not eng.firing()
    assert not eng.early_warning("app_a")
    states = [a.state for a in eng.alerts if a.kind == SLO_BURN]
    assert states == [FIRING, CLEARED]


def test_burn_rate_min_requests_gate():
    eng = HealthEngine(default_target=0.9, min_requests=10)
    for i in range(5):
        eng.on_request("app_a", 100.0 * i, ok=False)
    assert not eng.firing()                      # evidence too thin to page


def test_sheds_spend_error_budget():
    eng = HealthEngine(default_target=0.9, min_requests=10)
    for i in range(12):
        eng.on_shed("app_a", 100.0 * i)
    assert eng.firing(kind=SLO_BURN, app="app_a")


def test_burn_rate_query():
    eng = HealthEngine(default_target=0.9)
    assert eng.burn_rate("ghost", 0.0) == (0.0, 0.0)
    for i in range(10):
        eng.on_request("app_a", float(i), ok=(i % 2 == 0))
    s, l = eng.burn_rate("app_a", 10.0)
    assert s == pytest.approx(0.5 / 0.1)         # half missing, 10% budget


def test_calibration_drift_detector_fires_on_regime_change():
    eng = HealthEngine(drift_min_samples=10)
    for i in range(30):                          # well-calibrated regime
        eng.observe_calibration(_rec(predicted=100.0, realized=100.0,
                                     t=float(i)))
    assert not eng.firing(kind=CAL_DRIFT)
    for i in range(30):                          # profiles start drifting
        eng.observe_calibration(_rec(predicted=100.0, realized=160.0,
                                     t=100.0 + i))
    assert eng.firing(kind=CAL_DRIFT, app="image_classification")


def test_queue_buildup_needs_sustained_depth():
    eng = HealthEngine(queue_depth_limit=64, queue_sustain=3)
    eng.on_window(1000.0, queue_depth=100, cold_starts=0,
                  prefetch_wasted=0)
    eng.on_window(2000.0, queue_depth=100, cold_starts=0,
                  prefetch_wasted=0)
    assert not eng.firing(kind=QUEUE_BUILDUP)    # two windows: not yet
    eng.on_window(3000.0, queue_depth=100, cold_starts=0,
                  prefetch_wasted=0)
    assert eng.firing(kind=QUEUE_BUILDUP)
    assert eng.early_warning("any_app")          # cluster-scoped alert
    eng.on_window(4000.0, queue_depth=0, cold_starts=0, prefetch_wasted=0)
    assert not eng.firing(kind=QUEUE_BUILDUP)


def test_spike_detectors_compare_against_trailing_baseline():
    eng = HealthEngine(spike_mult=4.0, spike_floor=8.0)
    for i in range(5):                           # quiet baseline
        eng.on_window(1000.0 * i, queue_depth=0, cold_starts=1,
                      prefetch_wasted=1)
    assert not eng.firing()
    eng.on_window(6000.0, queue_depth=0, cold_starts=50,
                  prefetch_wasted=40)
    assert eng.firing(kind=COLD_SPIKE)
    assert eng.firing(kind=PREFETCH_WASTE)
    # back to baseline clears both
    eng.on_window(7000.0, queue_depth=0, cold_starts=1, prefetch_wasted=1)
    assert not eng.firing()


def test_quiet_run_cannot_spike_from_zero():
    eng = HealthEngine(spike_mult=4.0, spike_floor=8.0)
    for i in range(10):
        eng.on_window(1000.0 * i, queue_depth=0, cold_starts=2,
                      prefetch_wasted=3)
    assert not eng.firing()                      # 2-3 << the absolute floor


def test_early_warning_scoping():
    eng = HealthEngine(default_target=0.9, min_requests=10)
    for i in range(12):
        eng.on_request("app_a", 100.0 * i, ok=False)
    assert eng.early_warning("app_a")
    assert not eng.early_warning("app_b")        # someone else's pager
    assert eng.early_warning()                   # cluster view sees it


def test_alert_export_roundtrips_through_validate(tmp_path):
    eng = HealthEngine(default_target=0.9, min_requests=10,
                       queue_depth_limit=64, queue_sustain=1)
    for i in range(12):
        eng.on_request("app_a", 100.0 * i, ok=False)
    eng.on_window(2000.0, queue_depth=100, cold_starts=0,
                  prefetch_wasted=0)
    eng.on_request("app_a", 20_000.0, ok=True)
    path = tmp_path / "health.jsonl"
    n = eng.export_jsonl(str(path))
    records = [json.loads(l) for l in path.read_text().splitlines()]
    assert len(records) == n == len(eng.alerts)
    counts = validate_health(records, str(path))
    assert counts[SLO_BURN] == 2                 # fired, then cleared
    assert counts[QUEUE_BUILDUP] == 1
    assert all(r["kind"] in ALERT_KINDS for r in records)
    assert validate_main([str(path)]) == 0       # CLI sniffs .jsonl alerts


def test_health_summary_counts_transitions():
    eng = HealthEngine(default_target=0.9, min_requests=10)
    for i in range(12):
        eng.on_request("app_a", 100.0 * i, ok=False)
    s = eng.summary()
    assert s["alerts_total"] == 1
    assert s["active"] == ["slo_burn_rate[app_a]"]
    assert s["transitions"] == {"slo_burn_rate:firing": 1}


def test_health_requires_metrics_feed():
    with pytest.raises(ValueError):
        Recorder(metrics=False, health=HealthEngine())


# ---------------------------------------------------------------------------
# plumbing: scheduler, plan cache, gateway, autoscaler, telemetry
# ---------------------------------------------------------------------------
def test_profile_table_scaled(tables):
    t = tables["classification"]
    s = t.scaled(1.3)
    assert np.allclose(s.times, t.times * 1.3)
    assert np.allclose(s.job_costs, t.job_costs * 1.3)
    assert s.configs == t.configs
    assert t.scaled(1.0) is t                    # identity fast path
    with pytest.raises(ValueError):
        t.scaled(0.0)


def test_scheduler_factor_gating_and_cache_reset(tables):
    cal = ProfileCalibrator(alpha=1.0, min_samples=1)
    sched = ESGScheduler(PAPER_APPS, tables, calibrator=cal)
    stages = ("0:super_resolution", "1:segmentation", "2:classification")
    # cold calibrator: the uncorrected path (factors None, 4-tuple keys)
    assert sched._factors("image_classification", stages) is None
    cal.observe(_rec(raw=100.0, exec_ms=130.0))
    f = sched._factors("image_classification", stages)
    assert f == (pytest.approx(1.3), 1.0, 1.0)
    # a published change drops the memoized scaled tables
    sched._scaled[("sentinel",)] = ["stale"]
    cal.observe(_rec(raw=100.0, exec_ms=200.0, t=1.0))
    sched._factors("image_classification", stages)
    assert ("sentinel",) not in sched._scaled


def test_plan_cache_keys_grow_factor_axis_on_publish(tables):
    """Calibrated runs key cached plans under the factor tuple: a factor
    publish makes every stale plan unreachable instead of evicting it."""
    skewed = {n: ProfileTable.build(
        dataclasses.replace(p, t1_ms=p.t1_ms * 1.3))
        for n, p in PAPER_FUNCTIONS.items()}
    _, _, sched_off = _run(skewed, "mmpp", n=20,
                           recorder=Recorder(trace=False))
    # shape keys: (funcs, bucket, pen_key) uncalibrated
    assert all(len(k) == 3 for k in sched_off.cache._entries)
    cal = ProfileCalibrator(min_samples=3)
    _, _, sched_on = _run(skewed, "mmpp", n=60,
                          recorder=Recorder(trace=False), calibrator=cal)
    keys = list(sched_on.cache._entries)
    assert cal.updates > 0
    assert any(len(k) == 4 for k in keys), \
        "no factor-keyed plan ever cached despite published corrections"
    # the factor axis is the published tuple itself
    four = [k for k in keys if len(k) == 4]
    assert all(isinstance(k[3], tuple) for k in four)


class _AlwaysFiring:
    def early_warning(self, app=None):
        return True


def test_gateway_sheds_earlier_under_firing_alert(tables):
    """The admission check inflates predicted queueing while an alert
    relevant to the app is firing: a request that would squeak in on the
    EWMA alone is shed when the alert says the EWMA is lagging."""
    _, sim, _ = _run(tables, "mmpp", n=6)
    gw = Gateway(sim)
    gw.inject(get_scenario("mmpp", app_names=APPS), 0, seed=1)
    app = sim.apps["image_classification"]
    for stage in app.stages:
        gw._qdelay[(app.name, stage)] = 10.0
    fastest = gw._fastest_ms[app.name]
    inst = SimpleNamespace(app=app,
                           deadline_ms=sim.now + fastest + 100.0)
    assert gw._admit(sim, inst)                  # EWMA says it fits
    gw.health, gw.health_headroom = _AlwaysFiring(), 1e6
    assert not gw._admit(sim, inst)              # alert says it will not


def test_vertical_scaler_withholds_grow_under_alert():
    pol = AUTOSCALERS["vertical"]()
    stub = SimpleNamespace(queues={})             # nothing queued
    pol.health = _AlwaysFiring()
    pol._grow(stub, 0)                            # returns before invokers
    pol.health = None
    with pytest.raises(AttributeError):
        pol._grow(stub, 0)                        # proof it would proceed


def test_telemetry_carries_calibration_and_health_blocks(tables):
    cal = ProfileCalibrator()
    rec = Recorder(trace=False, health=HealthEngine())
    tel, _, _ = _run(tables, "mmpp", recorder=rec, calibrator=cal)
    s = tel.summary()
    assert s["calibration"]["observations"] == cal.observations > 0
    assert s["health"]["alerts_total"] == len(rec.health.alerts)
    # satellite: per-stage blocks carry their sample counts
    per_stage = s["predicted_vs_realized"]["per_stage"]
    assert per_stage
    for block in per_stage.values():
        assert block["n"] >= 1
        if block["n"] < 2:                       # quantiles need 2 samples
            assert block["p50_err"] is None
        else:
            assert block["p50_err"] is not None


def test_audit_per_stage_quantiles_gate_on_sample_count():
    audit = AuditLog()
    audit.on_plan(_rec(raw=100.0))
    audit.on_dispatch("image_classification", "0:super_resolution", 0,
                      None, predicted_ms=100.0, predicted_raw_ms=100.0)
    audit.on_complete(0, 110.0, realized_exec_ms=110.0)
    block = audit.calibration()["per_stage"][
        "image_classification/0:super_resolution"]
    assert block["n"] == 1
    assert block["p50_err"] is None and block["p90_abs_err"] is None
    assert block["mean_err"] == pytest.approx(0.1)


# ---------------------------------------------------------------------------
# validator extensions: offending file and record are always named
# ---------------------------------------------------------------------------
def test_validate_metrics_json_roundtrip_and_errors(tmp_path, tables):
    """The metrics bus exports JSON only: the export round-trips through
    the validator, a corrupted point is named by file, series and point,
    and a long-format CSV is refused as no known artifact."""
    rec = Recorder(trace=False)
    _run(tables, "mmpp", recorder=rec)
    good = tmp_path / "metrics.json"
    rec.export(metrics_path=str(good))
    doc = json.loads(good.read_text())
    assert validate_metrics(doc, str(good)) == len(rec.metrics.series) > 0
    name = next(iter(doc["series"]))
    doc["series"][name]["points"][0][-1] = "not_a_number"
    bad = tmp_path / "corrupt.json"
    bad.write_text(json.dumps(doc))
    with pytest.raises(ValueError) as ei:
        validate_main([str(bad)])
    assert "corrupt.json" in str(ei.value) and "point 0" in str(ei.value)
    assert repr(name) in str(ei.value)
    old = tmp_path / "metrics.csv"
    old.write_text("series,kind,window_start_ms,value,count,sum,min,max\n")
    with pytest.raises(ValueError):
        validate_main([str(old)])


def test_validate_audit_names_offending_record(tmp_path, tables):
    rec = Recorder(trace=False)
    _run(tables, "mmpp", recorder=rec)
    path = tmp_path / "audit.jsonl"
    rec.export(audit_path=str(path))
    records = [json.loads(l) for l in path.read_text().splitlines()]
    counts = validate_audit(records, str(path))
    assert counts["plan"] > 0
    records[3]["t_ms"] = "yesterday"
    with pytest.raises(ValueError) as ei:
        validate_audit(records, str(path))
    assert "audit.jsonl" in str(ei.value) and "record 3" in str(ei.value)


def _retry_rec(**over):
    rec = {"type": "retry", "t_ms": 5.0, "app": "image_classification",
           "stage": "0:super_resolution", "uid": 7, "invoker": 2,
           "attempt": 1, "action": "retry", "backoff_ms": 250.0,
           "lost_ms": 12.5}
    rec.update(over)
    return rec


def test_validate_audit_counts_retry_records():
    recs = [_retry_rec(), _retry_rec(attempt=2, action="resume"),
            _retry_rec(attempt=3, action="shed", backoff_ms=0.0)]
    assert validate_audit(recs, "audit.jsonl")["retry"] == 3


def test_validate_audit_rejects_bad_retry_action():
    recs = [_retry_rec(), _retry_rec(action="requeue")]
    with pytest.raises(ValueError) as ei:
        validate_audit(recs, "audit.jsonl")
    msg = str(ei.value)
    assert "audit.jsonl" in msg and "record 1" in msg
    assert "requeue" in msg and "retry" in msg


def test_validate_audit_rejects_bad_retry_attempt():
    for attempt in (0, -1, 1.5, True, "first"):
        with pytest.raises(ValueError) as ei:
            validate_audit([_retry_rec(attempt=attempt)], "audit.jsonl")
        msg = str(ei.value)
        assert "record 0" in msg and "attempt" in msg


def test_validate_audit_rejects_negative_retry_costs():
    for field in ("backoff_ms", "lost_ms"):
        with pytest.raises(ValueError) as ei:
            validate_audit([_retry_rec(**{field: -1.0})], "audit.jsonl")
        msg = str(ei.value)
        assert "record 0" in msg and field in msg


def test_validate_audit_names_missing_retry_fields():
    rec = _retry_rec()
    del rec["uid"], rec["action"]
    with pytest.raises(ValueError) as ei:
        validate_audit([rec], "bad_audit.jsonl")
    msg = str(ei.value)
    assert "bad_audit.jsonl" in msg and "record 0" in msg
    assert "uid" in msg and "action" in msg


def test_validate_audit_bad_type_mentions_retry():
    with pytest.raises(ValueError, match=r"plan\|skip\|retry"):
        validate_audit([{"type": "redo", "t_ms": 1.0}], "audit.jsonl")


def test_validate_health_rejects_double_fire(tmp_path):
    recs = [{"type": "alert", "t_ms": 1.0, "kind": SLO_BURN, "app": "a",
             "state": FIRING, "value": 3.0, "threshold": 2.0},
            {"type": "alert", "t_ms": 2.0, "kind": SLO_BURN, "app": "a",
             "state": FIRING, "value": 4.0, "threshold": 2.0}]
    with pytest.raises(ValueError) as ei:
        validate_health(recs, "health.jsonl")
    assert "health.jsonl" in str(ei.value) and "record 1" in str(ei.value)
    recs[1]["state"] = CLEARED
    assert validate_health(recs, "health.jsonl") == {SLO_BURN: 2}


def test_validate_cli_dispatches_all_artifacts(tmp_path, tables):
    rec = Recorder(health=HealthEngine())
    _run(tables, "mmpp", recorder=rec)
    trace = tmp_path / "trace.json"
    metrics = tmp_path / "metrics.json"
    audit = tmp_path / "audit.jsonl"
    health = tmp_path / "health.jsonl"
    rec.export(str(trace), str(metrics), str(audit),
               health_path=str(health))
    assert validate_main([str(trace), str(metrics), str(audit),
                          str(health)]) == 0
