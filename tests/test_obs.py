"""Observability stack (PR 10): columnar trace recorder, metrics
registry, P² percentiles, the double-entry carbon ledger, the
conservation self-checks, geo overload surfacing, and the solver's
candidate-table explainer.

The load-bearing contract is *bit-identity*: attaching the flight
recorder must only observe — every traced ``run_day`` here is asserted
equal, field by field, to its untraced twin."""
import numpy as np
import pytest

from repro.core.carbon import CarbonModel
from repro.core.controller import GreenCacheController
from repro.core.profiler import Profile, ProfileCell
from repro.obs import (CarbonLedger, LedgerError, MetricsRegistry,
                       StreamingPercentiles, TraceRecorder,
                       exact_partition)
from repro.obs.trace import HIT_KIND_CODES
from repro.serving.perfmodel import SERVING_MODELS
from repro.serving.regions import GeoOverloadWarning, Region
from repro.workloads import ReplicaFailure
from repro.workloads.conversations import ConversationWorkload

M = SERVING_MODELS["llama3-70b"]
CM = CarbonModel()


def synth_profile(sizes=(0, 4), rates=(0.2, 0.5, 1.0, 1.5, 2.0)):
    prof = Profile("m", "t", rates=list(rates), sizes=list(sizes))
    for r in rates:
        for s in sizes:
            slo = float(np.clip(1.1 - 0.25 * r + 0.02 * s, 0.0, 1.0))
            prof.cells[(r, s)] = ProfileCell(
                rate=r, cache_tb=s, avg_ttft=0.5 + 0.5 * r, p90_ttft=1 + r,
                avg_tpot=0.05, p90_tpot=0.08, slo_frac=slo,
                hit_rate=min(0.1 * s, 0.8),
                energy_per_req_kwh=2e-4 * (1 + 1 / max(r, 0.1)),
                duration_per_req_s=1.0 / max(r, 0.1), avg_power_w=800.0,
                slo_ttft_frac=min(slo * 1.05, 1.0),
                slo_tpot_frac=min(slo * 1.1, 1.0), avg_out_tokens=400.0)
    return prof


def _controller(**kw):
    return GreenCacheController(M, synth_profile(), CM, "conversation",
                                policy="lcs_chat", warm_requests=400,
                                max_requests_per_hour=100, seed=7,
                                mode="greencache", **kw)


RATES = np.array([0.8, 1.2, 1.5])
CIS = np.array([10.0, 500.0, 10.0])


def _wf(s):
    return ConversationWorkload(seed=s)


def _fingerprint(res):
    return [(h.carbon_g, h.operational_g, h.embodied_cache_g,
             h.embodied_compute_g, h.slo_frac, h.hit_rate,
             h.num_requests, h.cache_tb, h.plan, h.p90_ttft,
             h.p50_ttft, h.p95_ttft, h.p99_ttft, h.p99_tpot)
            for h in res.hours]


# ------------------------------------------------------------------ #
# MetricsRegistry
# ------------------------------------------------------------------ #
def test_metrics_counter_gauge_histogram():
    m = MetricsRegistry()
    c = m.counter("reqs_total", "requests", ("region",))
    c.labels(region="eu").inc()
    c.labels(region="eu").inc(2.0)
    c.labels(region="us").inc()
    g = m.gauge("depth", "queue depth", ())
    g.labels().set(7.0)
    h = m.histogram("lat_seconds", "latency", (), buckets=(0.1, 1.0))
    h.labels().observe_many(np.array([0.05, 0.5, 5.0]))
    snap = m.snapshot()
    assert snap["reqs_total"]["region=eu"] == 3.0
    assert snap["reqs_total"]["region=us"] == 1.0
    assert snap["depth"][""] == 7.0
    assert snap["lat_seconds"][""]["count"] == 3
    text = m.expose_text()
    assert '# TYPE reqs_total counter' in text
    assert 'reqs_total{region="eu"} 3' in text
    assert 'lat_seconds_bucket{le="+Inf"} 3' in text
    assert "lat_seconds_count 3" in text


def test_metrics_reregistration_is_idempotent_but_kind_checked():
    m = MetricsRegistry()
    c1 = m.counter("x_total", "x", ("a",))
    c2 = m.counter("x_total", "x", ("a",))
    assert c1 is c2
    with pytest.raises((ValueError, TypeError)):
        m.gauge("x_total", "x", ("a",))
    with pytest.raises(ValueError):
        m.counter("x_total", "x", ("b",))


def test_counters_are_monotone():
    m = MetricsRegistry()
    c = m.counter("y_total", "y", ())
    with pytest.raises(ValueError):
        c.labels().inc(-1.0)


# ------------------------------------------------------------------ #
# P² streaming percentiles
# ------------------------------------------------------------------ #
def test_p2_tracks_true_percentiles():
    rng = np.random.default_rng(3)
    xs = rng.lognormal(0.0, 0.6, size=8000)
    sp = StreamingPercentiles()
    for chunk in np.array_split(xs, 24):     # fed hour-by-hour
        sp.extend(chunk)
    est = sp.values()
    for q in (50, 95, 99):
        true = float(np.percentile(xs, q))
        assert est[f"p{q}"] == pytest.approx(true, rel=0.08), q


def test_p2_small_sample_is_exact_order_statistic():
    sp = StreamingPercentiles()
    sp.extend([3.0, 1.0, 2.0])
    assert sp.values()["p50"] == 2.0


# ------------------------------------------------------------------ #
# TraceRecorder
# ------------------------------------------------------------------ #
def _record_some(rec, k=5, region="eu"):
    rec.record_window(
        rids=np.arange(k), arrival=np.linspace(0, 10, k),
        ttft=np.full(k, 0.5), tpot=np.full(k, 0.05),
        prefill_s=np.full(k, 0.3), kv_load_s=np.full(k, 0.1),
        queue_s=np.full(k, 0.1), prompt_tokens=np.full(k, 100),
        output_tokens=np.full(k, 50), matched_tokens=np.full(k, 20),
        hit_kind=np.full(k, HIT_KIND_CODES["partial"], dtype=np.int8),
        energy_j_per_req=np.full(k, 3.6e6), ci_g_per_kwh=100.0,
        region=region)


def test_recorder_grows_and_sums():
    rec = TraceRecorder(capacity=16)
    for _ in range(10):
        _record_some(rec)
    assert rec.n == 50
    assert rec.capacity >= 50
    # 1 kWh per request at 100 g/kWh -> 100 g each
    assert rec.column("carbon_g").sum() == pytest.approx(5000.0)
    assert rec.percentile("ttft_s", 99) == 0.5


def test_recorder_jsonl_and_chrome_roundtrip(tmp_path):
    rec = TraceRecorder()
    _record_some(rec, k=3)
    rec.record_event("transition", 42.0, region="eu", detail="1tb->2tb")
    j = tmp_path / "t.jsonl"
    c = tmp_path / "t.trace.json"
    rec.write_jsonl(str(j))
    rec.write_chrome(str(c))
    import json
    rows = [json.loads(x) for x in j.read_text().splitlines()]
    assert sum(r["type"] == "request" for r in rows) == 3
    ev = [r for r in rows if r["type"] == "event"]
    assert ev[0]["kind"] == "transition" and ev[0]["ts"] == 42.0
    assert rows[0]["hit_kind"] == "partial"
    assert rows[0]["region"] == "eu"
    chrome = json.loads(c.read_text())
    spans = [e for e in chrome["traceEvents"] if e["ph"] == "X"]
    assert spans and all(e["pid"] == "eu" for e in spans)
    # per-span energy split re-sums to the request total
    by_rid = {}
    for e in spans:
        by_rid.setdefault(e["args"]["rid"], 0.0)
        by_rid[e["args"]["rid"]] += e["args"]["energy_j"]
    assert all(v == pytest.approx(3.6e6) for v in by_rid.values())


def test_record_request_fills_one_row_beside_windows():
    rec = TraceRecorder(capacity=16)
    _record_some(rec, k=16)
    rec.record_request(rid=7, arrival_s=2.5, hit_kind="hit",
                       matched_tokens=96, prompt_tokens=128,
                       output_tokens=4, prefill_s=0.01, decode_s=0.04)
    assert rec.n == 17 and rec.capacity >= 17
    row = list(rec.rows())[-1]
    assert row["rid"] == 7 and row["arrival_s"] == 2.5
    assert row["hit_kind"] == "hit" and row["hit_tier"] == -1
    assert (row["matched_tokens"], row["prompt_tokens"],
            row["output_tokens"]) == (96, 128, 4)
    assert (row["prefill_s"], row["decode_s"]) == (0.01, 0.04)
    # what the engine cannot know stays 0
    assert all(row[k] == 0 for k in ("queue_s", "kv_load_s", "ttft_s",
                                     "tpot_s", "energy_j", "carbon_g"))
    assert row["tier"] == "" and row["tenant"] == ""


# ------------------------------------------------------------------ #
# exact_partition / CarbonLedger
# ------------------------------------------------------------------ #
def test_exact_partition_reconciles_float_dust():
    total = 0.1 + 0.2 + 0.3
    parts = {"a": 0.3, "b": 0.2, "c": 0.1}    # re-associated
    out = exact_partition(total, parts)
    assert sum(out.values()) == total


def test_exact_partition_sterbenz_tie_case():
    # regression from the disagg gauntlet: no value of the *largest*
    # part lands the fold exactly on the total (round-to-even tie), so
    # the reconciliation must rebuild through the smallest part
    total = 84.34890780664956
    parts = {"operational": 73.22716311877181,
             "embodied_cache": 0.0,
             "embodied_compute": 11.121744687877758}
    out = exact_partition(total, parts)
    s = 0.0
    for v in out.values():
        s += v
    assert s == total


def test_exact_partition_rejects_corruption():
    with pytest.raises(LedgerError):
        exact_partition(10.0, {"a": 5.0, "b": 4.0})    # a whole gram gone
    with pytest.raises(LedgerError):
        exact_partition(1.0, {})


def test_ledger_add_hour_and_day_cuts():
    led = CarbonLedger()
    led.add_hour(0, 10.0, category={"operational": 7.0,
                                    "embodied_cache": 3.0})
    led.add_hour(1, 5.0, region={"west": 2.0, "east": 3.0})
    led.verify(expected_total=15.0)
    assert sum(led.by("category").values()) == 15.0
    assert set(led.by("region")) == {"site", "west", "east"}


def test_ledger_from_run_catches_corrupt_tenant_partition():
    """PR-8 bug class: a tenant chargeback that loses a gram must raise
    at the conservation check, not produce a quietly-wrong bill."""
    ctl = _controller(tiers={"gold": 0.5, "standard": 0.5})
    res = ctl.run_day(_wf, RATES, CIS)
    assert res.ledger is not None           # self-check ran and passed
    # corrupt one hour's chargeback by a whole gram
    h = next(h for h in res.hours if h.tenants)
    victim = next(iter(h.tenants))
    h.tenants[victim]["carbon_g"] += 1.0
    with pytest.raises(LedgerError):
        CarbonLedger.from_run(res)


# ------------------------------------------------------------------ #
# run_day bit-identity: traced == untraced
# ------------------------------------------------------------------ #
@pytest.mark.parametrize("kw", [
    dict(),                                             # flat engine
    dict(plans=["cache=auto fleet=l40:2"]),             # cluster
    dict(plans=["cache=auto prefill=l40:1 decode=l40:2"]),  # disagg
    dict(storage=["dram:0.25tb+nvme_gen4:4tb"]),        # tiered
    dict(prefix_caching=True),                          # radix
], ids=["flat", "cluster", "disagg", "tiered", "radix"])
def test_trace_off_bit_reproduces(kw):
    prefix = bool(kw.get("prefix_caching"))
    wf = lambda s: ConversationWorkload(seed=s, prefix=prefix)
    base = _controller(**kw).run_day(wf, RATES, CIS)
    ctl = _controller(trace=True, metrics=True, **kw)
    traced = ctl.run_day(wf, RATES, CIS)
    assert _fingerprint(base) == _fingerprint(traced)
    assert base.total_carbon_g == traced.total_carbon_g
    assert ctl.trace.n == sum(h.num_requests for h in base.hours)
    # estimators differ, the day still reports both ways
    assert base.latency["estimator"] == "p2"
    assert traced.latency["estimator"] == "trace"
    snap = ctl.metrics.snapshot()
    assert sum(snap["requests_total"].values()) == ctl.trace.n


def test_trace_off_bit_reproduces_geo():
    regions = [Region.make("west", cis=[10.0, 500.0, 10.0],
                           rtt_ms={"na": 10.0, "eu": 120.0}),
               Region.make("east", cis=[500.0, 10.0, 500.0],
                           rtt_ms={"na": 120.0, "eu": 10.0})]
    kw = dict(plans=["cache=auto fleet=l40:2"])
    with pytest.warns(GeoOverloadWarning):
        base = _controller(**kw).run_day(_wf, RATES, CIS,
                                         regions=regions, geo="green")
    ctl = _controller(trace=True, metrics=True, **kw)
    with pytest.warns(GeoOverloadWarning):
        traced = ctl.run_day(_wf, RATES, CIS, regions=regions,
                             geo="green")
    assert _fingerprint(base) == _fingerprint(traced)
    for name in ("west", "east"):
        assert _fingerprint(base.regions[name]) \
            == _fingerprint(traced.regions[name])
    # per-region span attribution partitions the request stream
    reg_col = ctl.trace.column("region")
    labels = ctl.trace.regions.labels
    n_by = {lab: int((reg_col == i).sum())
            for i, lab in enumerate(labels)}
    for name in ("west", "east"):
        assert n_by[name] == sum(h.num_requests
                                 for h in base.regions[name].hours)


def test_geo_overload_surfaced_on_forecast_miss():
    """Anti-phase CI traces swing the green split between regions each
    hour while the per-region plans were sized for the *forecast* split
    — the realized overload must surface as a structured warning, a
    counter, and a ``last_overloads`` record, not a silent SLO miss."""
    regions = [Region.make("west", cis=[10.0, 500.0, 10.0],
                           rtt_ms={"na": 10.0, "eu": 120.0}),
               Region.make("east", cis=[500.0, 10.0, 500.0],
                           rtt_ms={"na": 120.0, "eu": 10.0})]
    ctl = _controller(metrics=True, plans=["cache=auto fleet=l40:2"])
    with pytest.warns(GeoOverloadWarning):
        ctl.run_day(_wf, RATES, CIS, regions=regions, geo="green")
    assert ctl.last_overloads
    ov = ctl.last_overloads[0]
    assert ov["realized_rate"] > ov["capacity_rate"]
    assert ov["region"] in ("west", "east")
    snap = ctl.metrics.snapshot()
    assert sum(snap["geo_overload_hours_total"].values()) \
        == len(ctl.last_overloads)
    # and the knob exists to silence it
    ctl2 = _controller(overload_warnings=False,
                       plans=["cache=auto fleet=l40:2"])
    import warnings as W
    with W.catch_warnings():
        W.simplefilter("error", GeoOverloadWarning)
        ctl2.run_day(_wf, RATES, CIS, regions=regions, geo="green")
    assert not ctl2.last_overloads


# ------------------------------------------------------------------ #
# mid-hour event splits (satellite d)
# ------------------------------------------------------------------ #
def test_event_split_spans_and_ledger_merge_consistently():
    """A mid-hour ``ReplicaFailure`` splits the hour into segments that
    merge through ``combine_results``: the traced day must still cover
    every request exactly once, reproduce the untraced day bit-for-bit,
    and keep every carbon partition exact."""
    kw = dict(plans=["cache=auto fleet=l40:2"],
              tiers={"gold": 0.5, "standard": 0.5})
    sc = ReplicaFailure(hour=1, frac=0.5, replica=0)
    base = _controller(**kw).run_day(_wf, RATES, CIS, scenario=sc)
    ctl = _controller(trace=True, metrics=True, **kw)
    traced = ctl.run_day(_wf, RATES, CIS, scenario=sc)
    assert _fingerprint(base) == _fingerprint(traced)
    # every request exactly once, even across the segment boundary
    assert ctl.trace.n == sum(h.num_requests for h in base.hours)
    rids = ctl.trace.column("rid")
    assert len(np.unique(rids)) == len(rids)
    # the failure event itself is on the control-plane record
    kinds = [e["kind"] for e in ctl.trace.events]
    assert "fail_replica" in kinds
    # ledger invariants hold through the merge (incl. tier/tenant cuts)
    assert base.ledger is not None
    base.ledger.verify(expected_total=base.total_carbon_g)
    ev_snap = ctl.metrics.snapshot()["scenario_events_total"]
    assert sum(ev_snap.values()) == 1


# ------------------------------------------------------------------ #
# solver explainability
# ------------------------------------------------------------------ #
def test_solve_result_explain_and_prune_stats():
    from repro.core.solver import solve_cluster_schedule
    from repro.serving.perfmodel import SLOS
    res = solve_cluster_schedule(
        synth_profile(), [0.8, 1.2, 1.5], [10.0, 500.0, 10.0],
        SLOS[("llama3-70b", "chat")], CM, sizes_tb=[0, 4],
        replicas=[1, 2], use_ilp=False)
    txt = res.explain()
    assert "chosen" in txt and "hour 00" in txt
    assert "g/req" in txt
    ps = res.prune_stats()
    assert ps is not None and 0.0 <= ps["prune_ratio"] <= 1.0
    # hours filter and row cap
    short = res.explain(hours=[0], top=1)
    assert "hour 01" not in short and "more options" in short


def test_run_day_stashes_last_solve():
    ctl = _controller(plans=["cache=auto fleet=l40:2"])
    ctl.run_day(_wf, RATES, CIS)
    assert ctl.last_solve is not None
    assert "chosen" in ctl.last_solve.explain(hours=[0])


# ------------------------------------------------------------------ #
# conservation self-checks are on by default
# ------------------------------------------------------------------ #
def test_run_day_attaches_verified_ledger_by_default():
    res = _controller().run_day(_wf, RATES, CIS)
    assert res.ledger is not None
    assert res.ledger.total_g == res.total_carbon_g
    by_cat = res.ledger.by("category")
    assert sum(by_cat.values()) == res.total_carbon_g
    res2 = _controller(conservation_check=False).run_day(_wf, RATES, CIS)
    assert res2.ledger is None
    assert _fingerprint(res) == _fingerprint(res2)  # check is read-only
