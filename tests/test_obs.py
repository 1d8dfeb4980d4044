"""Unified telemetry subsystem (src/repro/obs/).

The two load-bearing guarantees, straight from the design:

* **Zero cost when disabled** — the default recorder is one shared
  no-op object; an instrumented FL run with telemetry off emits no
  events and lands the exact same ledger totals / model state as the
  pre-instrumentation code path (bitwise).
* **Health monitors tell the truth** — the per-round ``health`` events
  match norms recomputed independently (numpy, float64) from the very
  state pytrees the simulator returns, and a forced-NaN broadcast trips
  an ``anomaly`` event immediately.

Plus the contract of each part: registry semantics (counter/gauge
high-water/histogram, labels, kind clashes), versioned event schema,
span nesting, exporters, the report CLI, and the serve-side allocator
peak tracking.
"""

import json
import math
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro.obs as obs
from repro.core import CompressionConfig
from repro.fl import FLConfig, FLSimulator
from repro.obs import events as obs_events
from repro.obs import export as obs_export
from repro.obs import health as obs_health
from repro.obs import metrics as obs_metrics
from repro.obs import report as obs_report
from repro.obs import trace as obs_trace


@pytest.fixture(autouse=True)
def _reset_recorder():
    """Every test starts and ends with the disabled (NOOP) recorder."""
    obs.shutdown()
    yield
    obs.shutdown()


# ---------------------------------------------------------------------------
# Registry semantics
# ---------------------------------------------------------------------------


def test_counter_accumulates_per_label_set():
    reg = obs_metrics.Registry()
    c = reg.counter("comm.bytes")
    c.inc(10.0)
    c.inc(5.0)
    c.inc(3.0, wire="int8")
    assert c.value() == 15.0
    assert c.value(wire="int8") == 3.0
    assert reg.counter("comm.bytes") is c  # idempotent


def test_gauge_high_water_mark():
    g = obs_metrics.Registry().gauge("serve.active_slots")
    for v in (1, 3, 2, 0):
        g.set(v)
    assert g.value() == 0.0       # last value
    assert g.high_water() == 3.0  # peak — replaces ad-hoc max() bookkeeping


def test_histogram_summary_and_percentiles():
    h = obs_metrics.Registry().histogram("round_ms")
    for v in range(1, 101):
        h.observe(float(v))
    s = h.summary()
    assert s["count"] == 100 and s["min"] == 1.0 and s["max"] == 100.0
    assert s["mean"] == pytest.approx(50.5)
    assert h.percentile(50) == pytest.approx(50.0, abs=1)
    assert h.percentile(99) == pytest.approx(99.0, abs=1)


def test_registry_kind_clash_raises():
    reg = obs_metrics.Registry()
    reg.counter("x")
    with pytest.raises(ValueError, match="already registered"):
        reg.gauge("x")


# ---------------------------------------------------------------------------
# Disabled path: shared no-op object, no behavioural difference
# ---------------------------------------------------------------------------


def test_disabled_recorder_is_shared_noop_object():
    assert obs.get() is obs_metrics.NOOP
    assert not obs.enabled()
    # every operation is a pass — nothing to flush, nothing recorded
    obs.get().counter_add("a", 1.0)
    obs.get().gauge_set("b", 2.0)
    obs.get().observe("c", 3.0)
    obs.get().event("round", round=0)
    # a disabled span keeps no path stack
    with obs_trace.span("x"):
        assert obs_trace.current_path() == ""


D_IN, D_OUT = 6, 3


class _TinyTask:
    def __init__(self, num_clients, samples=8, seed=0):
        rng = np.random.default_rng(seed)
        self.x = jnp.asarray(
            rng.normal(size=(num_clients, samples, D_IN)).astype(np.float32))
        self.y = jnp.asarray(rng.integers(0, D_OUT, size=(num_clients, samples)))

    def init_fn(self, key):
        return {"w": 0.1 * jax.random.normal(key, (D_IN, D_OUT)),
                "b": jnp.zeros((D_OUT,))}

    def loss_fn(self, params, batch):
        x, y = batch
        logp = jax.nn.log_softmax(x @ params["w"] + params["b"], axis=-1)
        return -jnp.mean(jnp.take_along_axis(logp, y[..., None], axis=-1))

    def provider(self):
        def p(t, ids, rng):
            return (self.x[ids], self.y[ids])
        return p


def _run_sim(backend="vmap", scheme="dgcwgmf", rounds=4, **fl_kw):
    task = _TinyTask(4)
    fl = FLConfig(num_clients=4, rounds=rounds, clients_per_round=2,
                  learning_rate=0.5, seed=0, backend=backend, **fl_kw)
    sim = FLSimulator(fl, CompressionConfig(scheme=scheme, rate=0.5, tau=0.4),
                      task.init_fn, task.loss_fn)
    sim.run(task.provider())
    return sim


def test_disabled_run_bitwise_identical_and_emits_nothing(tmp_path):
    """The acceptance criterion: telemetry off is a no-op object, not a
    code path — ledger totals and model params land bitwise identical to
    an instrumented run, and nothing is written anywhere."""
    before = set(os.listdir(tmp_path))
    off = _run_sim()                      # recorder is NOOP (fixture)
    assert set(os.listdir(tmp_path)) == before

    obs.configure(str(tmp_path / "obs"))
    on = _run_sim()
    obs.shutdown()

    assert off.ledger.upload_bytes == on.ledger.upload_bytes
    assert off.ledger.download_bytes == on.ledger.download_bytes
    assert off.ledger.summary() == on.ledger.summary()
    for a, b in zip(jax.tree_util.tree_leaves(off.params),
                    jax.tree_util.tree_leaves(on.params), strict=True):
        assert np.array_equal(np.asarray(a), np.asarray(b))
    # and the enabled run did emit the per-round series
    evs = obs_events.read_events(str(tmp_path / "obs" / "events.jsonl"))
    kinds = [e["kind"] for e in evs]
    assert kinds.count("round") == 4 and kinds.count("health") == 4


# ---------------------------------------------------------------------------
# Health monitors: ground truth + anomaly tripping
# ---------------------------------------------------------------------------


def _np_l2(tree):
    leaves = jax.tree_util.tree_leaves(tree)
    return math.sqrt(sum(float(np.sum(np.square(
        np.asarray(x, np.float64)))) for x in leaves))


@pytest.mark.parametrize("scheme", ["dgcwgmf", "fetchsgd"])
def test_health_events_match_recomputed_norms(tmp_path, scheme):
    """The last health event must match norms recomputed independently
    (numpy float64) from the state pytrees the simulator returns."""
    obs.configure(str(tmp_path))
    sim = _run_sim(scheme=scheme)
    obs.shutdown()
    evs = obs_events.read_events(str(tmp_path / "events.jsonl"))
    last = [e["data"] for e in evs if e["kind"] == "health"][-1]
    assert last["round"] == 3
    assert last["residual_u_norm"] == pytest.approx(_np_l2(sim.cstates.u), abs=1e-6)
    assert last["residual_v_norm"] == pytest.approx(_np_l2(sim.cstates.v), abs=1e-6)
    assert last["momentum_m_norm"] == pytest.approx(_np_l2(sim.cstates.m), abs=1e-6)
    assert last["server_momentum_norm"] == pytest.approx(
        _np_l2(sim.sstate.momentum), abs=1e-6)
    assert last["broadcast_norm"] == pytest.approx(_np_l2(sim.gbar_prev), abs=1e-6)
    assert last["broadcast_finite"] is True
    assert last["compression_target_rate"] == 0.5


def test_async_health_reports_server_held_gmom(tmp_path):
    obs.configure(str(tmp_path))
    sim = _run_sim(backend="async", scheme="async_dgcwgmf", rounds=5)
    obs.shutdown()
    evs = obs_events.read_events(str(tmp_path / "events.jsonl"))
    last = [e["data"] for e in evs if e["kind"] == "health"][-1]
    assert last["global_momentum_norm"] == pytest.approx(
        _np_l2(sim.engine._gmom), abs=1e-6)
    # async runs also carry flush events with per-payload staleness gaps
    flushes = [e["data"] for e in evs if e["kind"] == "flush"]
    assert flushes and all("staleness_gaps" in f for f in flushes)


def test_forced_nan_broadcast_trips_anomaly_event(tmp_path):
    """One NaN in the broadcast must trip an anomaly event the round it
    happens, not surface as a flat accuracy curve 50 rounds later."""
    rec = obs.configure(str(tmp_path))
    sim = _run_sim(rounds=2)
    bad = jax.tree_util.tree_map(lambda x: x, sim.gbar_prev)
    bad["w"] = bad["w"].at[0, 0].set(jnp.nan)
    block = obs_health.record_round_health(
        rec, round_idx=2, cstates=sim.cstates, sstate=sim.sstate, bcast=bad,
        upload_nnz_mean=9.0, total_params=float(D_IN * D_OUT + D_OUT),
        target_rate=0.5)
    assert block["broadcast_finite"] is False
    assert rec.registry.counter("health.anomalies").value() == 1.0
    obs.shutdown()
    evs = obs_events.read_events(str(tmp_path / "events.jsonl"))
    anomalies = [e["data"] for e in evs if e["kind"] == "anomaly"]
    assert anomalies == [{"round": 2, "what": "non-finite broadcast",
                          "broadcast_norm": anomalies[0]["broadcast_norm"]}]


def test_compression_ratio_and_staleness_percentiles():
    r = obs_health.compression_ratio(50.0, 1000.0, 0.1)
    assert r["compression_achieved_rate"] == pytest.approx(0.05)
    assert r["compression_rate_ratio"] == pytest.approx(0.5)
    p = obs_health.staleness_percentiles({0: 5, 1: 3, 4: 2})
    assert p["staleness_p50"] == 0.0
    assert p["staleness_p99"] == 4.0
    assert p["staleness_mean"] == pytest.approx((0 * 5 + 1 * 3 + 4 * 2) / 10)
    assert obs_health.staleness_percentiles({}) == {}


# ---------------------------------------------------------------------------
# CommLedger publishes through the registry (and only when enabled)
# ---------------------------------------------------------------------------


def test_ledger_publishes_comm_series_when_enabled(tmp_path):
    rec = obs.configure(str(tmp_path))
    sim = _run_sim()
    reg = rec.registry
    assert reg.counter("comm.upload_bytes").value() == sim.ledger.upload_bytes
    assert reg.counter("comm.download_bytes").value() == sim.ledger.download_bytes
    assert reg.counter("comm.rounds").value() == float(sim.ledger.rounds)


@pytest.mark.parametrize("scheme,gauges", [
    ("dgcwgmf", {"fl.select_groups": 2.0, "fl.select_leaves": 2.0}),
    ("fetchsgd", {}),
])
def test_select_plan_gauges_when_enabled(tmp_path, scheme, gauges):
    """The per-tensor top-k's plan (one threshold search per leaf size) is
    published once, where the scheme selects that way; the tiny task's two
    leaves differ in size."""
    reg = obs.configure(str(tmp_path)).registry
    _run_sim(scheme=scheme, rounds=1)
    got = {n: reg.gauge(n).value() for n in reg.names() if n.startswith("fl.select_")}
    assert got == gauges


# ---------------------------------------------------------------------------
# Spans
# ---------------------------------------------------------------------------


def test_spans_nest_and_record_path_labelled_durations():
    rec = obs.configure()
    with obs_trace.span("round"):
        assert obs_trace.current_path() == "round"
        with obs_trace.span("aggregate"):
            assert obs_trace.current_path() == "round/aggregate"
    assert obs_trace.current_path() == ""
    h = rec.registry.histogram("trace.span_ms")
    assert h.summary(span="round")["count"] == 1
    assert h.summary(span="round/aggregate")["count"] == 1


def _profiled(tmp_path, body):
    """Events of the host's threads in a CPU profile taken around ``body``:
    name -> list of stats dicts."""
    d = str(tmp_path / "trace")
    jax.profiler.start_trace(d)
    try:
        body()
    finally:
        jax.profiler.stop_trace()
    (pb,) = [os.path.join(r, f) for r, _, fs in os.walk(d) for f in fs
             if f.endswith(".xplane.pb")]
    events = {}
    for plane in jax.profiler.ProfileData.from_file(pb).planes:
        if plane.name == "/host:CPU":
            for line in plane.lines:
                for e in line.events:
                    events.setdefault(e.name, []).append(dict(e.stats))
    return events


def test_disabled_span_is_a_profiler_event_and_records_nothing(tmp_path, monkeypatch):
    """With telemetry off a span is still a profiler annotation, named
    bare, its ids as event stats; no recorder method runs."""
    def never(*_, **__):
        raise AssertionError("a disabled span recorded something")

    for method in ("observe", "counter_add", "gauge_set", "event"):
        monkeypatch.setattr(obs_metrics.NOOP, method, never)

    def body():
        for t in range(2):
            with obs_trace.span("fl.dispatch", round=t):
                assert obs_trace.current_path() == ""
                jnp.ones(4).block_until_ready()

    events = _profiled(tmp_path, body)
    assert [s.get("round") for s in events["fl.dispatch"]] == [0, 1]
    assert obs.get() is obs_metrics.NOOP


def test_enabled_span_is_a_profiler_event_and_a_path_labelled_series(tmp_path):
    """With telemetry on a span is the same profiler event and lands in
    ``trace.span_ms`` under its path: the ids are stats, never labels."""
    rec = obs.configure()

    def body():
        with obs_trace.span("fl.round", round=3):
            with obs_trace.span("fl.dispatch", round=3):
                assert obs_trace.current_path() == "fl.round/fl.dispatch"

    events = _profiled(tmp_path, body)
    assert [s.get("round") for s in events["fl.dispatch"]] == [3]
    h = rec.registry.histogram("trace.span_ms")
    assert h.summary(span="fl.round/fl.dispatch")["count"] == 1
    assert h.summary(span="fl.round/fl.dispatch", round=3)["count"] == 0


# ---------------------------------------------------------------------------
# Event schema
# ---------------------------------------------------------------------------


def test_event_schema_validation():
    ok = obs_events.make_event("round", round=0, wall_ms=1.0,
                               upload_bytes=0.0, download_bytes=0.0)
    assert obs_events.validate_event(ok) == []
    # unknown kinds are forward-compatible
    assert obs_events.validate_event(obs_events.make_event("custom", x=1)) == []
    # known kinds must carry their required fields
    missing = obs_events.make_event("round", round=0)
    assert any("required field" in e for e in obs_events.validate_event(missing))
    # future schema versions are rejected, not mis-parsed
    future = dict(ok, v=obs_events.SCHEMA_VERSION + 1)
    assert any("newer than reader" in e
               for e in obs_events.validate_event(future))


# ---------------------------------------------------------------------------
# Exporters + report CLI
# ---------------------------------------------------------------------------


def test_exporters_and_report_cli(tmp_path, capsys):
    obs.configure(str(tmp_path))
    obs.get().event("run_start", run="test", argv=["--x"], backend="vmap")
    _run_sim()
    obs.get().event("summary", rounds=4)
    obs_export.write_all(str(tmp_path))
    obs.shutdown()

    prom = (tmp_path / "metrics.prom").read_text()
    assert "# TYPE repro_comm_upload_bytes counter" in prom
    assert "repro_health_broadcast_norm" in prom
    summary = json.loads((tmp_path / "summary.json").read_text())
    assert summary["comm.rounds"]["kind"] == "counter"

    path = str(tmp_path / "events.jsonl")
    assert obs_events.validate_file(path) == []
    assert obs_report.main([path, "--strict"]) == 0
    out = capsys.readouterr().out
    assert "obs report: test run" in out
    assert "compensation-state health" in out


def test_report_rejects_schema_errors(tmp_path, capsys):
    p = tmp_path / "events.jsonl"
    p.write_text(json.dumps({"v": 99, "ts": 0.0, "kind": "round",
                             "data": {}}) + "\n")
    assert obs_report.main([str(p)]) == 1


# ---------------------------------------------------------------------------
# Serve-side peaks: allocator high-water, engine gauge-backed metrics
# ---------------------------------------------------------------------------


def test_block_allocator_tracks_live_and_peak():
    from repro.serve.cache import BlockAllocator

    a = BlockAllocator(9)  # 8 usable pages (page 0 is scratch)
    p1 = a.alloc(3)
    p2 = a.alloc(4)
    assert a.num_live == 7 and a.peak_live == 7
    a.free(p2)
    assert a.num_live == 3
    a.alloc(2)
    assert a.peak_live == 7  # peak survives frees
    assert a.num_free == 8 - 5
    assert p1  # allocated pages are real (non-scratch) ids
