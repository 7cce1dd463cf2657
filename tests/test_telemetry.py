"""Unified telemetry: events, metrics, spans, in-graph step metrics.

Covers the ISSUE 7 acceptance contract: one versioned event schema
across the resilience driver and the campaign service, warm-path
invariants readable from the EXPORTED metrics surface, spans that
export as Perfetto-loadable Chrome trace JSON, and in-graph step
metrics that ride the health probe's one all-reduce (zero extra
collectives / zero extra wire bytes — proven by registry targets, with
a negative control).
"""

import json
import threading
import urllib.request
from pathlib import Path

import numpy as np
import pytest

from stencil_tpu.telemetry import (EVENT_SCHEMA_VERSION, EventLog,
                                   JsonlSink, ListSink, MetricsRegistry,
                                   MetricsServer, RingSink, StepMetrics,
                                   Tracer, metric_value,
                                   parse_prometheus_text,
                                   render_snapshot_text, snapshot_value,
                                   validate_chrome_trace,
                                   validate_events)

FIXTURES = Path(__file__).parent / "fixtures" / "lint"


# ---------------------------------------------------------------------------
# the versioned event schema + sinks


def test_event_log_stamps_schema_run_and_monotonic_seq():
    got = []
    log = EventLog(sinks=(ListSink(got),), clock=lambda: 123.0)
    log.emit("a", step=1)
    log.emit("b", span="r/0", nested={"k": "v"})
    assert [e["seq"] for e in got] == [0, 1]
    assert all(e["run"] == log.run_id for e in got)
    assert all(e["schema"] == EVENT_SCHEMA_VERSION for e in got)
    assert got[0] == {"event": "a", "time": 123.0, "run": log.run_id,
                      "seq": 0, "schema": EVENT_SCHEMA_VERSION,
                      "step": 1}
    assert got[1]["span"] == "r/0"
    assert validate_events(got) == []


def test_event_attrs_may_not_shadow_schema_keys():
    """The stamped identity (run/seq/time/schema/event) is what fleet
    scrapers merge on — a colliding attr must raise, not silently
    corrupt it."""
    elog = EventLog(run_id="r")
    # ("span" binds to emit()'s named parameter, the supported way to
    # set it — it can never arrive through **attrs)
    for key in ("run", "seq", "time", "schema", "event"):
        with pytest.raises(ValueError, match="schema keys"):
            elog.emit("tick", **{key: "boom"})
    # nothing was emitted and seq did not advance
    assert elog.emit("tick")["seq"] == 0


def test_validate_events_flags_bad_records():
    assert validate_events([{"event": "x"}])  # missing run/seq/...
    ok = {"event": "x", "time": 1.0, "run": "r", "seq": 1, "schema": 1}
    assert validate_events([ok]) == []
    # non-monotonic seq within one run
    again = dict(ok)
    problems = validate_events([ok, again])
    assert problems and "not increasing" in problems[0]
    # float seqs (an external serializer may write 1.0) get the same
    # monotonicity check as ints
    f1 = dict(ok, seq=1.0)
    f2 = dict(ok, seq=3.0)
    f3 = dict(ok, seq=2.0)
    assert validate_events([f1, f2]) == []
    problems = validate_events([f1, f2, f3])
    assert problems and "not increasing" in problems[0]


def test_event_log_survives_a_failing_sink():
    # a dead sink (disk full, closed stream) must neither kill the
    # instrumented loop nor starve later sinks of the record
    class Boom:
        def emit(self, record):
            raise OSError("disk full")

        def close(self):
            pass

    ring = RingSink(capacity=8)
    log = EventLog(sinks=(Boom(), ring))
    rec = log.emit("tick", i=1)
    assert rec["event"] == "tick"
    assert [r["i"] for r in ring.records()] == [1]


def test_ring_sink_bounds_memory_and_counts_drops():
    ring = RingSink(capacity=3)
    log = EventLog(sinks=(ring,))
    for i in range(10):
        log.emit("tick", i=i)
    records = ring.records()
    assert len(records) == 3
    assert [r["i"] for r in records] == [7, 8, 9]
    assert ring.dropped == 7


def test_jsonl_sink_writes_one_record_per_line(tmp_path):
    path = tmp_path / "events.jsonl"
    sink = JsonlSink(str(path))
    log = EventLog(sinks=(sink,))
    log.emit("a")
    log.emit("b", x=2)
    sink.close()
    lines = [json.loads(ln) for ln in path.read_text().splitlines()]
    assert [r["event"] for r in lines] == ["a", "b"]
    assert validate_events(lines) == []


# ---------------------------------------------------------------------------
# the metrics registry + exposition


def test_counter_gauge_histogram_exposition_and_parse():
    reg = MetricsRegistry()
    c = reg.counter("req_total", "requests")
    c.inc(tenant="a")
    c.inc(2, tenant="b")
    with pytest.raises(ValueError):
        c.inc(-1)
    g = reg.gauge("depth")
    g.set(5)
    g.dec(2)
    h = reg.histogram("lat_seconds", buckets=(0.1, 1.0))
    h.observe(0.05, op="admit")
    h.observe(3.0, op="admit")
    text = reg.to_prometheus_text()
    assert "# TYPE req_total counter" in text
    assert metric_value(text, "req_total", tenant="b") == 2
    assert metric_value(text, "depth") == 3
    parsed = parse_prometheus_text(text)
    assert metric_value(parsed, "lat_seconds_bucket", op="admit",
                        le="0.1") == 1
    assert metric_value(parsed, "lat_seconds_bucket", op="admit",
                        le="+Inf") == 2
    assert metric_value(parsed, "lat_seconds_count", op="admit") == 2
    # absent series read as 0 (the Prometheus convention)
    assert metric_value(text, "req_total", tenant="nobody") == 0.0


def test_label_values_escape_and_round_trip():
    """Tenant-controlled label values with quotes/commas/backslashes
    must not corrupt the exposition surface: values are escaped per
    format 0.0.4 and the parser round-trips them exactly."""
    reg = MetricsRegistry()
    c = reg.counter("req_total")
    hostile = ('acme"corp', "acme,corp", "a\\b", "two\nlines")
    for t in hostile:
        c.inc(tenant=t)
    text = reg.to_prometheus_text()
    # no raw quote/newline inside a label value on the wire
    for line in text.splitlines():
        assert "\n" not in line
        assert 'tenant="acme"corp"' not in line
    for t in hostile:
        assert metric_value(text, "req_total", tenant=t) == 1, t
    # and every series is still individually addressable
    assert len(parse_prometheus_text(text)["req_total"]) == len(hostile)


def test_counter_seeded_to_zero_exports_explicit_sample():
    # inc(0) births the unlabeled series: the exposition carries an
    # explicit `name 0` line, so "== 0" gates (CI warm path) assert a
    # sample that exists rather than the absent-series 0.0 default
    reg = MetricsRegistry()
    c = reg.counter("seeded_total", "seeded at registration")
    c.inc(0)
    text = reg.to_prometheus_text()
    assert "seeded_total 0" in text
    assert metric_value(text, "seeded_total") == 0
    assert snapshot_value(reg.snapshot(), "seeded_total") == 0
    assert reg.snapshot()["metrics"]["seeded_total"]["samples"]


def test_registry_idempotent_and_kind_checked():
    reg = MetricsRegistry()
    c1 = reg.counter("foo_total")
    assert reg.counter("foo_total") is c1
    with pytest.raises(ValueError):
        reg.gauge("foo_total")
    # histogram re-registration: same buckets fine, different raise
    # (silently keeping the first bounds would misbin observations)
    h1 = reg.histogram("lat_seconds", buckets=(0.1, 1.0))
    assert reg.histogram("lat_seconds", buckets=(1.0, 0.1)) is h1
    with pytest.raises(ValueError, match="buckets"):
        reg.histogram("lat_seconds", buckets=(0.5,))
    # no-preference re-declaration (buckets omitted) stays idempotent
    # even though the first registration chose custom bounds — only an
    # explicit conflicting choice raises
    assert reg.histogram("lat_seconds") is h1
    assert h1.buckets == (0.1, 1.0)
    # histograms have no single value — count()/sum() are the readers
    with pytest.raises(TypeError, match="count"):
        h1.value()
    # HELP text is escaped per format 0.0.4
    reg.counter("esc_total", "two\nlines \\ slash").inc()
    text = reg.to_prometheus_text()
    assert r"# HELP esc_total two\nlines \\ slash" in text
    assert all(line.startswith(("#", "esc_total", "foo_total",
                                "lat_seconds"))
               for line in text.splitlines())


def test_snapshot_round_trips_through_render():
    reg = MetricsRegistry()
    reg.counter("a_total", "help a").inc(3, k="v")
    reg.histogram("h_seconds", buckets=(1.0,)).observe(0.5)
    snap = reg.snapshot()
    assert snap["schema"] == 1
    assert snapshot_value(snap, "a_total", k="v") == 3
    text = render_snapshot_text(snap)
    assert metric_value(text, "a_total", k="v") == 3
    assert metric_value(text, "h_seconds_count") == 1
    # one renderer serves both surfaces: the re-rendered snapshot IS
    # the live scrape, byte for byte
    assert text == reg.to_prometheus_text()


def test_metrics_http_endpoint():
    reg = MetricsRegistry()
    reg.counter("hits_total").inc(7)
    with MetricsServer(reg, port=0) as srv:
        base = f"http://127.0.0.1:{srv.port}"
        text = urllib.request.urlopen(f"{base}/metrics").read().decode()
        assert metric_value(text, "hits_total") == 7
        snap = json.loads(
            urllib.request.urlopen(f"{base}/metrics.json").read())
        assert snapshot_value(snap, "hits_total") == 7
        with pytest.raises(urllib.error.HTTPError):
            urllib.request.urlopen(f"{base}/nope")


# ---------------------------------------------------------------------------
# spans + Chrome trace export


def test_tracer_span_tree_and_chrome_export(tmp_path):
    t = Tracer(run_id="testrun")
    with t.span("campaign", tenant="a"):
        with t.span("segment", steps=4):
            pass
        with t.span("checkpoint"):
            pass
    spans = t.finished()
    by_name = {s.name: s for s in spans}
    assert by_name["segment"].parent_id == by_name["campaign"].span_id
    assert by_name["checkpoint"].parent_id == by_name["campaign"].span_id
    assert by_name["campaign"].parent_id is None
    assert all(s.span_id.startswith("testrun/") for s in spans)
    assert by_name["segment"].attrs == {"steps": 4}

    path = tmp_path / "trace.json"
    t.export_chrome_trace(str(path))
    assert validate_chrome_trace(str(path)) == []
    data = json.loads(path.read_text())
    ev = {e["name"]: e for e in data["traceEvents"]}
    assert ev["segment"]["ph"] == "X"
    assert ev["segment"]["args"]["parent_id"] == \
        ev["campaign"]["args"]["span_id"]
    assert data["otherData"]["dropped_spans"] == 0


def test_tracer_rejects_identity_key_attrs():
    # same contract as EventLog.RESERVED: an attr named span_id or
    # parent_id would clobber the exported trace's parent links
    t = Tracer()
    for key in ("span_id", "parent_id"):
        with pytest.raises(ValueError, match="identity keys"):
            with t.span("seg", **{key: "forged"}):
                pass
    assert t.finished() == []


def test_tracer_ring_counts_dropped_spans():
    t = Tracer(capacity=3)
    for i in range(5):
        with t.span(f"s{i}"):
            pass
    assert [s.name for s in t.finished()] == ["s2", "s3", "s4"]
    assert t.dropped == 2
    assert t.chrome_trace()["otherData"]["dropped_spans"] == 2
    t.clear()
    assert t.dropped == 0


def test_tracer_threads_keep_independent_stacks():
    t = Tracer()
    seen = {}

    def worker():
        with t.span("worker-root") as sp:
            seen["worker_parent"] = sp.parent_id

    with t.span("main-root"):
        th = threading.Thread(target=worker)
        th.start()
        th.join()
    # the worker thread's span is NOT parented under main's stack
    assert seen["worker_parent"] is None


def test_validate_chrome_trace_flags_garbage(tmp_path):
    assert validate_chrome_trace({"nope": 1})
    assert validate_chrome_trace(
        {"traceEvents": [{"name": 3, "ph": "X"}]})
    bad = tmp_path / "bad.json"
    bad.write_text("{not json")
    assert validate_chrome_trace(str(bad))


def test_span_named_scope_reaches_traced_ops():
    """A telemetry span wraps utils.profiling.scope: ops traced inside
    it carry the span name on their name stack (-> XLA op metadata)."""
    import jax
    import jax.numpy as jnp

    t = Tracer()

    def fn(x):
        with t.span("telemetry-span-label"):
            return x * 2.0

    closed = jax.make_jaxpr(fn)(jnp.ones(4))
    stacks = [str(eqn.source_info.name_stack)
              for eqn in closed.jaxpr.eqns]
    assert any("telemetry-span-label" in s for s in stacks), stacks


def _spans_of(tracer, drive):
    from stencil_tpu.telemetry import set_tracer

    prev = set_tracer(tracer)
    try:
        with tracer.span("campaign") as root:
            drive()
    finally:
        set_tracer(prev)
    return root, tracer.finished()


@pytest.mark.parametrize("model", ["jacobi", "astaroth"])
def test_solver_entry_points_record_spans(model):
    """run()/block() open their host spans under the caller's span,
    run() with its ``iters``; opening them neither retraces the step
    program nor puts the host span's name on its ops."""
    import jax

    from stencil_tpu.telemetry.spans import BLOCK, RUN

    if model == "jacobi":
        from stencil_tpu.models.jacobi import Jacobi3D
        m = Jacobi3D(8, 8, 8, mesh_shape=(1, 1, 1), dtype=np.float32,
                     devices=jax.devices()[:1], kernel="xla")
        prog = m._step_n
    else:
        from stencil_tpu.models.astaroth import Astaroth
        m = Astaroth(8, 8, 8, mesh_shape=(1, 1, 1), dtype=np.float32,
                     devices=jax.devices()[:1], kernel="xla")
        prog = m._iter_n

    def drive():
        m.run(3)
        m.step()
        m.run(2)
        m.block()

    root, spans = _spans_of(Tracer(run_id="solver"), drive)
    runs = [s for s in spans if s.name == RUN]
    assert [s.attrs for s in runs] == [{"iters": 3}, {"iters": 2}]
    for name in (RUN, BLOCK):
        got = [s for s in spans if s.name == name]
        assert got and all(s.parent_id == root.span_id for s in got), name
        assert all(s.end_s >= s.start_s for s in got)
    assert sorted({s.name for s in spans}) == sorted({"campaign", RUN, BLOCK})
    assert prog._cache_size() == 1


def test_span_duration_survives_a_wall_clock_step():
    """A span starts on the wall clock but is timed on the monotonic
    one: the wall clock stepping back mid-span leaves its duration and
    the exported trace valid."""
    from unittest import mock

    wall = iter(range(10 ** 12, 0, -10 ** 9))   # 1 s back per reading
    t = Tracer()
    with mock.patch("stencil_tpu.telemetry.spans.time.time_ns",
                    lambda: next(wall)):
        with t.span("outer"):
            with t.span("inner"):
                pass
    spans = t.finished()
    assert [s.name for s in spans] == ["inner", "outer"]
    assert all(s.duration_s >= 0 for s in spans)
    assert validate_chrome_trace(t.chrome_trace()) == []


def test_span_times_match_the_profiler(tmp_path):
    """A span's exported start and duration agree with the profiler's
    TraceAnnotation event for it (its times are relative to the
    profile's ``profile_start_time``) to well under 1 ms: one clock."""
    import glob
    import time

    import jax
    from jax.profiler import ProfileData

    t = Tracer()
    jax.profiler.start_trace(str(tmp_path))
    try:
        with t.span("stencil.block"):
            time.sleep(0.005)
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(f"{tmp_path}/**/*.xplane.pb", recursive=True)
    data = ProfileData.from_file(path)
    start0 = None
    events = []
    for plane in data.planes:
        if plane.name == "Task Environment":
            start0 = {k: v for k, v in plane.stats}["profile_start_time"]
        if plane.name.startswith("/host:"):
            events += [e for line in plane.lines for e in line.events
                       if e.name == "stencil.block"]
    assert start0 is not None and len(events) == 1
    (exported,) = t.chrome_trace()["traceEvents"]
    assert abs((start0 + events[0].start_ns) / 1e3 - exported["ts"]) < 1000
    assert abs(events[0].duration_ns / 1e3 - exported["dur"]) < 1000


# ---------------------------------------------------------------------------
# in-graph step metrics: ride the probe's one all-reduce


def make_jacobi():
    from stencil_tpu.models.jacobi import Jacobi3D

    j = Jacobi3D(16, 16, 16, mesh_shape=(2, 2, 2), dtype=np.float32)
    j.init()
    return j


def test_step_metrics_ride_the_health_probe():
    from stencil_tpu.resilience import HealthSentinel

    j = make_jacobi()
    sm = StepMetrics(j.dd)
    assert sm.bytes_per_step == pytest.approx(
        j.dd.exchange_bytes_amortized_per_step())
    s = HealthSentinel(j.dd, metrics=sm)
    s.probe(j.dd.curr, 3)
    (r,) = s.poll(block=True)
    assert not r.tripped
    # health stats untouched by the extra columns
    assert r.max_abs["temp"] == pytest.approx(0.5)
    # the counters decode from the SAME harvested vector
    assert r.metrics["substeps"] == 3
    assert r.metrics["wire_bytes"] == pytest.approx(
        3 * sm.bytes_per_step)
    decoded = sm.decode(r.metrics)
    assert decoded["bytes_per_step_probe"] == pytest.approx(
        sm.bytes_per_step)
    assert decoded["bytes_per_step_model"] == sm.bytes_per_step
    assert r.to_record()["metrics"]["substeps"] == 3


def test_step_metrics_rebase_prices_only_future_steps():
    """A mid-run reconfiguration (degradation ladder) must not
    retroactively reprice traffic already sent: the rebased counter
    carries the old price for steps up to the rebase point and applies
    the new domain's price only beyond it."""
    j = make_jacobi()
    sm = StepMetrics(j.dd)
    old_price = sm.bytes_per_step
    # reconfigure: temporal depth 2 changes the amortized B/step
    from stencil_tpu.models.jacobi import Jacobi3D

    k = Jacobi3D(16, 16, 16, mesh_shape=(2, 2, 2), dtype=np.float32,
                 exchange_every=2)
    k.init()
    sm2 = sm.rebased(k.dd, 6)
    new_price = sm2.bytes_per_step
    assert new_price != pytest.approx(old_price)
    assert sm2.cumulative_bytes(6) == pytest.approx(6 * old_price)
    assert sm2.cumulative_bytes(10) == pytest.approx(
        6 * old_price + 4 * new_price)
    # a rollback below the rebase point never goes negative
    assert sm2.cumulative_bytes(4) == pytest.approx(6 * old_price)
    vals = np.asarray(sm2.values(10))
    assert vals[0] == 10
    assert vals[1] == pytest.approx(6 * old_price + 4 * new_price,
                                    rel=1e-6)


def test_telemetry_registry_targets_prove_zero_added_collectives():
    """Acceptance verbatim: the instrumented production Jacobi step
    passes exact_counts (6 collective_permutes + exactly 1 all_reduce)
    and the exchange byte cross-check stays exact."""
    from stencil_tpu.analysis import run_targets
    from stencil_tpu.analysis.hlo import lowering_supported
    from stencil_tpu.analysis.registry import default_targets

    if not lowering_supported():
        pytest.skip("StableHLO lowering unavailable in this JAX")
    targets = [t for t in default_targets()
               if t.name.startswith("telemetry.")]
    assert len(targets) == 3
    report = run_targets(targets)
    assert report.findings == []
    fused = report.metrics["hlo:telemetry.step+probe+metrics[hlo]"]
    assert fused["collectives"]["all_reduce"]["count"] == 1
    assert fused["collectives"]["collective_permute"]["count"] == 6
    cost = report.metrics["costmodel:telemetry.step+probe+metrics[cost]"]
    assert cost["observed_bytes_per_shard"] == \
        cost["expected_bytes_per_shard"]


def test_separate_metrics_reduce_fixture_flagged():
    from stencil_tpu.analysis import run_targets
    from stencil_tpu.analysis.hlo import lowering_supported
    from stencil_tpu.analysis.registry import load_targets

    if not lowering_supported():
        pytest.skip("StableHLO lowering unavailable in this JAX")
    report = run_targets(load_targets(FIXTURES / "bad_probe_metrics.py"))
    assert len(report.errors) == 1
    assert "exactly 1" in report.errors[0].message


# ---------------------------------------------------------------------------
# one schema across subsystems


def test_resilience_report_events_speak_the_unified_schema(tmp_path):
    from stencil_tpu.resilience import ResiliencePolicy
    from stencil_tpu.resilience.driver import run_resilient

    j = make_jacobi()
    rep = run_resilient(
        j.dd, j.step, 3,
        policy=ResiliencePolicy(check_every=1, ckpt_every=2,
                                sleep=lambda s: None),
        ckpt_dir=str(tmp_path / "ckpt"))
    assert rep.run_id
    assert rep.events and validate_events(rep.events) == []
    assert all(e["run"] == rep.run_id for e in rep.events)
    # events emitted inside the run-loop spans are span-correlated
    # (same shape as the service's event log — one scraper joins the
    # event stream and the chrome trace)
    spans = [e["span"] for e in rep.events if "span" in e]
    assert spans, rep.events
    from stencil_tpu.telemetry import get_tracer
    trace_ids = {s.span_id for s in get_tracer().finished()}
    assert set(spans) <= trace_ids
    # the serialized record keeps the schema-stamped events
    rec = rep.to_record()
    assert rec["run_id"] == rep.run_id
    assert validate_events(rec["events"]) == []


def test_service_events_metrics_and_trace(tmp_path):
    from stencil_tpu.serving import CampaignRequest, CampaignService
    from stencil_tpu.tuning import FakeTimer

    svc = CampaignService(str(tmp_path / "root"), width=4,
                          tuner_timer=FakeTimer(),
                          plan_cache_path=str(tmp_path / "plans.json"),
                          events_capacity=512)
    h = svc.submit(CampaignRequest(tenant="t0", campaign="c0",
                                   grid=(8, 8, 8), n_steps=4,
                                   ckpt_every=2))
    svc.drain()
    assert h.result(timeout=120).steps == 4

    # events: unified schema, one run id, span correlation
    events = svc.events
    assert events and validate_events(events) == []
    assert {e["run"] for e in events} == {svc.run_id}
    in_batch = [e for e in events if e.get("span")]
    assert in_batch, "batch-scoped events must carry span ids"

    # metrics: text and snapshot expose the same numbers
    text = svc.metrics_text()
    snap = svc.metrics_snapshot()
    assert metric_value(text, "stencil_service_batches_total") == 1
    assert snapshot_value(snap, "stencil_service_batches_total") == 1
    assert metric_value(text, "stencil_service_member_steps_total") == 4
    assert metric_value(text, "stencil_service_campaigns_total",
                        tenant="t0", outcome="completed") == 1
    assert metric_value(text, "stencil_service_queue_depth") == 0
    parsed = parse_prometheus_text(text)
    assert metric_value(
        parsed, "stencil_service_admission_latency_seconds_count") == 1

    # spans export as a valid Chrome trace with the expected tree
    trace = tmp_path / "trace.json"
    svc.export_trace(str(trace))
    assert validate_chrome_trace(str(trace)) == []
    names = {s.name for s in svc.tracer.finished()}
    assert {"campaign.batch", "segment", "compile",
            "tune"} <= names

    # the event payload carries schema/run/dropped
    out = tmp_path / "events.json"
    svc.write_events(str(out))
    payload = json.loads(out.read_text())
    assert payload["schema"] == EVENT_SCHEMA_VERSION
    assert payload["run"] == svc.run_id
    assert payload["dropped_events"] == 0


def test_service_event_ring_is_bounded(tmp_path):
    from stencil_tpu.serving import CampaignRequest, CampaignService
    from stencil_tpu.tuning import FakeTimer

    svc = CampaignService(str(tmp_path / "root"), width=2,
                          tuner_timer=FakeTimer(),
                          plan_cache_path=str(tmp_path / "plans.json"),
                          events_capacity=5)
    h = svc.submit(CampaignRequest(tenant="t0", campaign="c0",
                                   grid=(8, 8, 8), n_steps=4,
                                   ckpt_every=1))
    svc.drain()
    assert h.result(timeout=120).steps == 4
    assert len(svc.events) == 5          # flat memory, newest kept
    assert svc._ring.dropped > 0
    svc.write_events(str(tmp_path / "ev.json"))
    payload = json.loads((tmp_path / "ev.json").read_text())
    assert payload["dropped_events"] == svc._ring.dropped
    assert len(payload["events"]) == 5


# ---------------------------------------------------------------------------
# structured-JSON log mode (STENCIL_LOG_FORMAT=json)


def test_log_json_mode_routes_through_event_schema(capsys):
    from stencil_tpu.utils import logging as slog

    slog.set_format("json")
    try:
        slog.LOG_INFO("hello fleet")
        slog.LOG_WARN("watch out")
    finally:
        slog.set_format("text")
    lines = [json.loads(ln)
             for ln in capsys.readouterr().err.splitlines() if ln]
    assert [r["level"] for r in lines] == ["info", "warn"]
    assert all(r["event"] == "log" for r in lines)
    assert all(r["schema"] == EVENT_SCHEMA_VERSION for r in lines)
    assert lines[0]["message"] == "hello fleet"
    assert lines[0]["rank"] == 0
    assert validate_events(lines) == []
    # plain-text default unchanged
    slog.LOG_INFO("plain again")
    err = capsys.readouterr().err
    assert "INFO: plain again" in err


def test_log_set_format_rejects_unknown():
    from stencil_tpu.utils import logging as slog

    with pytest.raises(ValueError):
        slog.set_format("xml")


# ---------------------------------------------------------------------------
# the snapshot / validator CLI


def test_telemetry_cli(tmp_path, capsys):
    from stencil_tpu.telemetry.__main__ import main

    reg = MetricsRegistry()
    reg.counter("x_total").inc(4)
    snap_path = tmp_path / "snap.json"
    reg.write_snapshot(str(snap_path))
    assert main(["snapshot", str(snap_path)]) == 0
    out = capsys.readouterr().out
    assert metric_value(out, "x_total") == 4

    t = Tracer()
    with t.span("a"):
        pass
    trace_path = tmp_path / "trace.json"
    t.export_chrome_trace(str(trace_path))
    assert main(["validate-trace", str(trace_path)]) == 0
    bad = tmp_path / "bad_trace.json"
    bad.write_text(json.dumps({"traceEvents": [{"ph": "X"}]}))
    assert main(["validate-trace", str(bad)]) == 1

    evs = []
    log = EventLog(sinks=(ListSink(evs),))
    log.emit("a")
    log.emit("b")
    ev_path = tmp_path / "events.json"
    ev_path.write_text(json.dumps({"events": evs}))
    assert main(["validate-events", str(ev_path)]) == 0
    ev_path.write_text(json.dumps({"events": [{"event": "x"}]}))
    assert main(["validate-events", str(ev_path)]) == 1
    # JSONL input works too
    jsonl = tmp_path / "events.jsonl"
    jsonl.write_text("\n".join(json.dumps(e) for e in evs))
    assert main(["validate-events", str(jsonl)]) == 0
    # a ONE-line JSONL file is valid JSON on its own — it must parse
    # as a single record, not be rejected as a payload without events
    jsonl.write_text(json.dumps(evs[0]))
    assert main(["validate-events", str(jsonl)]) == 0
