"""obs/ subsystem — tracer, flight recorder, exporters, lock audit.

The e2e causal-chain tests (fault-injected elastic + swap flows) live in
tests/test_obs_e2e.py; this file covers the mechanics: span stack and
thread parenting, disabled-path no-ops, Chrome-trace schema, flight-ring
bounds and JSONL persistence, Prometheus rendering (including the
empty-reservoir / zero-batch edge cases of the satellite fix), the
compiled-program capture hook, and the thread-hammer regression for the
RunCounters/MetricsCollector lock guards.
"""
import json
import threading

import pytest

from transmogrifai_tpu import obs
from transmogrifai_tpu.obs import hlo as obs_hlo


@pytest.fixture(autouse=True)
def _clean_tracing():
    """Every test starts and ends untraced (tracing is process-global)."""
    obs.stop_trace()
    yield
    obs.stop_trace()


class TestTracer:
    def test_disabled_hooks_are_noops(self):
        assert obs.current_tracer() is None
        sp = obs.begin_span("x", cat="t")
        assert sp is None
        obs.end_span(sp)  # must not raise
        obs.record_event("y")  # must not raise
        with obs.span("z") as s:
            assert s is None

    def test_span_nesting_and_trace_id(self):
        tracer = obs.start_trace("unit")
        with obs.span("outer", cat="a") as outer:
            assert obs.current_span() is outer
            with obs.span("inner", cat="b") as inner:
                assert inner.parent_id == outer.span_id
                assert inner.trace_id == tracer.trace_id
        obs.stop_trace()
        spans = tracer.snapshot()
        assert [s.name for s in spans] == ["inner", "outer"]
        assert all(s.dur_s is not None and s.dur_s >= 0 for s in spans)

    def test_end_span_merges_attrs(self):
        tracer = obs.start_trace()
        sp = obs.begin_span("u", cat="t", a=1)
        obs.end_span(sp, b=2)
        obs.stop_trace()
        assert tracer.spans[0].attrs == {"a": 1, "b": 2}

    def test_explicit_parent_crosses_threads(self):
        tracer = obs.start_trace()
        parent = obs.begin_span("root", cat="t")
        seen = {}

        def worker():
            child = obs.begin_span("child", cat="t", parent=parent)
            seen["parent_id"] = child.parent_id
            obs.end_span(child)

        t = threading.Thread(target=worker)
        t.start()
        t.join()
        obs.end_span(parent)
        obs.stop_trace()
        assert seen["parent_id"] == parent.span_id
        assert len(tracer.spans) == 2

    def test_max_spans_bound(self):
        tracer = obs.start_trace(max_spans=3)
        for i in range(5):
            with obs.span(f"s{i}"):
                pass
        obs.stop_trace()
        assert len(tracer.spans) == 3
        assert tracer.dropped == 2

    def test_stop_trace_returns_active_tracer(self):
        t1 = obs.start_trace("a")
        assert obs.stop_trace() is t1
        assert obs.stop_trace() is None

    def test_tracing_context_manager(self):
        with obs.tracing("scoped") as tracer:
            with obs.span("inside"):
                pass
        assert obs.current_tracer() is None
        assert [s.name for s in tracer.spans] == ["inside"]


class TestFlightRecorder:
    def test_ring_bound_and_order(self):
        rec = obs.FlightRecorder(capacity=4)
        obs.install_recorder(rec)
        for i in range(7):
            obs.record_event("k", i=i)
        obs.install_recorder(None)
        events = rec.events()
        assert len(events) == 4
        assert [e["attrs"]["i"] for e in events] == [3, 4, 5, 6]
        assert [e["seq"] for e in events] == [4, 5, 6, 7]
        assert rec.recorded == 7

    def test_span_causality_link(self):
        tracer = obs.start_trace()
        with obs.span("holder") as sp:
            obs.record_event("evt")
        obs.stop_trace()
        [e] = tracer.flight.events()
        assert e["spanId"] == sp.span_id
        assert e["traceId"] == tracer.trace_id

    def test_dump_jsonl_roundtrip(self, tmp_path):
        rec = obs.FlightRecorder()
        obs.install_recorder(rec)
        obs.record_event("a", x=1)
        obs.record_event("b")
        obs.install_recorder(None)
        path = tmp_path / "flight.jsonl"
        assert rec.dump_jsonl(str(path)) == 2
        lines = [json.loads(line) for line in path.read_text().splitlines()]
        assert [e["kind"] for e in lines] == ["a", "b"]

    def test_crash_dump_flushes_ring(self, tmp_path):
        rec = obs.FlightRecorder()
        obs.install_recorder(rec)
        obs.record_event("before_crash")
        path = tmp_path / "crash.jsonl"
        obs.arm_crash_dump(str(path))
        try:
            import sys

            sys.excepthook(ValueError, ValueError("boom"), None)
        finally:
            obs.disarm_crash_dump()
            obs.install_recorder(None)
        kinds = [json.loads(line)["kind"]
                 for line in path.read_text().splitlines()]
        assert kinds == ["before_crash", "crash"]

    def test_kinds_filter(self):
        rec = obs.FlightRecorder()
        obs.install_recorder(rec)
        obs.record_event("elastic.retries")
        obs.record_event("swap.accept")
        obs.record_event("elastic.quarantined")
        obs.install_recorder(None)
        assert [e["kind"] for e in rec.events("elastic.")] == [
            "elastic.retries", "elastic.quarantined"]


class TestChromeExport:
    def _traced(self):
        tracer = obs.start_trace("exp")
        with obs.span("a", cat="run", n=1):
            with obs.span("b", cat="plan"):
                obs.record_event("evt", z=2)
        obs.stop_trace()
        return tracer

    def test_export_validates_and_links(self):
        tracer = self._traced()
        doc = obs.to_chrome_trace(tracer)
        assert obs.validate_chrome_trace(doc) == []
        xs = [e for e in doc["traceEvents"] if e["ph"] == "X"]
        assert {e["name"] for e in xs} == {"a", "b"}
        child = next(e for e in xs if e["name"] == "b")
        parent = next(e for e in xs if e["name"] == "a")
        assert child["args"]["parentId"] == parent["args"]["spanId"]
        instants = [e for e in doc["traceEvents"] if e["ph"] == "i"]
        assert len(instants) == 1 and instants[0]["name"] == "evt"
        assert doc["otherData"]["traceId"] == tracer.trace_id

    def test_validator_rejects_malformed(self):
        assert obs.validate_chrome_trace([]) != []
        assert obs.validate_chrome_trace({"traceEvents": {}}) != []
        bad = {"traceEvents": [{"ph": "X", "name": "x", "ts": -1,
                                "dur": "no", "pid": 0}]}
        assert len(obs.validate_chrome_trace(bad)) == 2

    def test_summary_and_cli(self, tmp_path, capsys):
        tracer = self._traced()
        doc = obs.to_chrome_trace(tracer)
        summary = obs.trace_summary(doc)
        assert "2 spans" in summary and "top spans" in summary
        path = tmp_path / "trace.json"
        path.write_text(json.dumps(doc))
        from transmogrifai_tpu.cli.main import main as cli_main

        assert cli_main(["trace", str(path)]) == 0
        out = capsys.readouterr().out
        assert tracer.trace_id in out
        # an invalid file fails with rc 1
        bad = tmp_path / "bad.json"
        bad.write_text(json.dumps({"traceEvents": [{"ph": "?"}]}))
        assert cli_main(["trace", str(bad)]) == 1


class TestPrometheus:
    def test_empty_server_renders_and_parses(self):
        """Satellite fix: empty reservoir + zero batches must render
        cleanly — TYPE lines present, no None/NaN samples."""
        from transmogrifai_tpu.serving.metrics import ServingMetrics

        snap = ServingMetrics().snapshot()
        # the JSON form also serializes cleanly with the Nones intact
        assert json.loads(json.dumps(snap))["latencyMs"]["p50"] is None
        text = obs.prometheus_text(snap)
        samples = obs.parse_exposition(text)
        assert samples["tmog_serving_requests_total"] == 0
        assert "None" not in text and "NaN" not in text
        # quantile family exists as TYPE only (no samples yet)
        assert "tmog_serving_request_latency_seconds" in text
        assert not any(k.startswith("tmog_serving_request_latency_seconds{")
                       for k in samples)

    def test_populated_server_quantiles_and_buckets(self):
        from transmogrifai_tpu.serving.metrics import ServingMetrics

        m = ServingMetrics()
        m.record_admitted(4)
        m.record_batch(4, 8, 0.002)
        for v in (0.010, 0.020, 0.030):
            m.record_request_latency(v)
        m.record_shed(2)
        text = obs.prometheus_text(m.snapshot())
        samples = obs.parse_exposition(text)
        assert samples['tmog_serving_batches_by_bucket_total{bucket="8"}'] \
            == 1
        assert samples["tmog_serving_shed_total"] == 2
        q50 = samples[
            'tmog_serving_request_latency_seconds{quantile="0.5"}']
        assert q50 == pytest.approx(0.020)

    def test_run_counters_section(self):
        from transmogrifai_tpu.utils.profiling import RunCounters

        c = RunCounters()
        c.launches = 7
        c.elastic = {"retries": 2}
        text = obs.prometheus_text(None, counters=c)
        samples = obs.parse_exposition(text)
        assert samples["tmog_run_launches_total"] == 7
        assert samples['tmog_run_elastic_events_total{kind="retries"}'] == 2

    def test_label_escaping(self):
        from transmogrifai_tpu.utils.profiling import RunCounters

        c = RunCounters()
        c.elastic = {'we"ird': 1}
        text = obs.prometheus_text(None, counters=c)
        obs.parse_exposition(text)  # still parses

    def test_http_endpoint_formats(self):
        """/metrics keeps its JSON default; ?format=prometheus switches
        to text exposition — via the real handler, no server thread."""
        from transmogrifai_tpu.serving.metrics import ServingMetrics

        class _FakeRegistry:
            def maybe_get(self, name):
                return None

            def get(self, name):
                raise KeyError(name)

        class _FakeServer:
            registry = _FakeRegistry()
            name = "x"
            metrics = ServingMetrics()

            def snapshot(self):
                return self.metrics.snapshot()

        import threading
        from http.client import HTTPConnection

        from transmogrifai_tpu.serving.http import make_http_server

        httpd = make_http_server(_FakeServer(), port=0)
        t = threading.Thread(target=httpd.serve_forever, daemon=True)
        t.start()
        try:
            conn = HTTPConnection("127.0.0.1", httpd.server_address[1],
                                  timeout=10)
            conn.request("GET", "/metrics")
            r = conn.getresponse()
            assert r.status == 200
            assert "application/json" in r.getheader("Content-Type")
            json.loads(r.read())
            conn.request("GET", "/metrics?format=prometheus")
            r = conn.getresponse()
            assert r.status == 200
            assert "text/plain" in r.getheader("Content-Type")
            obs.parse_exposition(r.read().decode())
            conn.close()
        finally:
            httpd.shutdown()
            httpd.server_close()


class TestReservoirEdgeCases:
    def test_empty_reservoir_quantile_is_none(self):
        from transmogrifai_tpu.serving.metrics import LatencyReservoir

        r = LatencyReservoir(capacity=8)
        assert r.quantile(0.5) is None
        assert r.quantile(0.99) is None
        assert r.count == 0

    def test_single_observation_all_quantiles(self):
        from transmogrifai_tpu.serving.metrics import LatencyReservoir

        r = LatencyReservoir(capacity=8)
        r.observe(0.5)
        for q in (0.0, 0.5, 0.99, 1.0):
            assert r.quantile(q) == 0.5

    def test_snapshot_with_zero_batches_is_jsonable(self):
        from transmogrifai_tpu.serving.metrics import ServingMetrics

        snap = ServingMetrics().snapshot()
        assert snap["batches"] == 0
        assert snap["batchSizeHistogram"] == {}
        assert snap["latencyObservations"] == 0
        json.dumps(snap)


class TestHloCapture:
    def test_compile_hook_records_features(self):
        import jax
        import jax.numpy as jnp

        assert obs_hlo.arm()
        try:
            mark = obs_hlo.mark()
            jax.jit(lambda x: jnp.tanh(x @ x.T).sum() * 3)(
                jnp.ones((4, 4), jnp.float32))
            entries = obs_hlo.since(mark)
        finally:
            obs_hlo.disarm()
        assert entries, "no compile captured"
        agg = obs_hlo.aggregate(entries)
        assert agg["programs"] >= 1
        assert agg.get("flops", 0) > 0
        assert "ops" in agg and any("dot" in op for op in agg["ops"])

    def test_disarm_restores_compiler(self):
        from jax._src import compiler

        before = compiler.compile_or_get_cached
        obs_hlo.arm()
        obs_hlo.disarm()
        assert compiler.compile_or_get_cached is before
        assert not obs_hlo.is_armed()

    def test_op_histogram(self):
        text = ('%0 = stablehlo.add %a, %b\n'
                '%1 = stablehlo.add %0, %b\n'
                '%2 = stablehlo.dot_general %1, %b\n')
        assert obs_hlo.op_histogram(text) == {"add": 2, "dot_general": 1}

    def test_traced_stage_profiles_carry_hlo(self):
        """A traced in-core train attributes compiled-program features to
        device stages, and they flow through to StageObservation."""
        import numpy as np
        import pandas as pd

        from transmogrifai_tpu import FeatureBuilder, OpWorkflow
        from transmogrifai_tpu.preparators import SanityChecker
        from transmogrifai_tpu.tuning.costmodel import (
            observations_from_profiler)

        rng = np.random.default_rng(0)
        df = pd.DataFrame({"y": rng.random(64).round(),
                           "a": rng.random(64), "b": rng.random(64)})
        y = FeatureBuilder.RealNN("y").as_response()
        from transmogrifai_tpu.ops.transmogrify import transmogrify

        feats = transmogrify([FeatureBuilder.Real("a").as_predictor(),
                              FeatureBuilder.Real("b").as_predictor()])
        checked = SanityChecker().set_input(y, feats).get_output()
        wf = OpWorkflow().set_result_features(checked).set_input_data(df)
        tracer = obs.start_trace()
        try:
            model = wf.train(profile=True)
        finally:
            obs.stop_trace()
        hlo_stages = [sp for sp in model.train_profile.stages if sp.hlo]
        assert hlo_stages, "no stage captured compiled-program features"
        assert hlo_stages[0].to_json()["hlo"]["programs"] >= 1
        observations = observations_from_profiler(model.train_profile)
        assert any(o.hlo for o in observations)
        # and the round trip through history JSON preserves it
        from transmogrifai_tpu.tuning.costmodel import StageObservation

        o = next(o for o in observations if o.hlo)
        assert StageObservation.from_json(o.to_json()).hlo == o.hlo


class TestLockAudit:
    """Satellite fix TM052: concurrent recording into the global
    RunCounters and a shared MetricsCollector must not drop increments."""

    N_THREADS = 8
    N_PER_THREAD = 2000

    def test_run_counters_hammer(self):
        from transmogrifai_tpu.utils import profiling

        profiling.reset_counters()

        def hammer():
            for _ in range(self.N_PER_THREAD):
                profiling.count_launch("hammer")
                profiling.count_upload(8, 0.0)
                profiling.count_fetch(8, 0.0)
                profiling.count_drain(0.0)
                profiling.count_elastic("retries")
                profiling.count_refresh("merged")

        threads = [threading.Thread(target=hammer)
                   for _ in range(self.N_THREADS)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        total = self.N_THREADS * self.N_PER_THREAD
        c = profiling.COUNTERS
        try:
            assert c.launches == total
            assert c.launch_tags["hammer"] == total
            assert c.uploads == total and c.upload_bytes == 8 * total
            assert c.fetches == total and c.fetch_bytes == 8 * total
            assert c.drains == total
            assert c.elastic["retries"] == total
            assert c.refresh["merged"] == total
        finally:
            profiling.reset_counters()

    def test_metrics_collector_hammer(self):
        from transmogrifai_tpu.utils.profiling import (MetricsCollector,
                                                       OpStep)

        coll = MetricsCollector()

        def hammer():
            for _ in range(self.N_PER_THREAD):
                coll.record(OpStep.Serving, 0.001)

        threads = [threading.Thread(target=hammer)
                   for _ in range(self.N_THREADS)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        metrics = coll.finish()
        sm = metrics.step_metrics[OpStep.Serving.name]
        assert sm.count == self.N_THREADS * self.N_PER_THREAD
        assert sm.duration_secs == pytest.approx(
            0.001 * self.N_THREADS * self.N_PER_THREAD)

    def test_serving_metrics_hammer(self):
        from transmogrifai_tpu.serving.metrics import ServingMetrics

        m = ServingMetrics()

        def hammer():
            for _ in range(self.N_PER_THREAD):
                m.record_admitted(1)
                m.record_request_latency(0.001)
                m.record_shed()

        threads = [threading.Thread(target=hammer)
                   for _ in range(self.N_THREADS)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        snap = m.snapshot()
        total = self.N_THREADS * self.N_PER_THREAD
        assert snap["requests"] == total
        assert snap["shed"] == total
        assert snap["latencyObservations"] == total


class TestBenchMeta:
    def test_standard_fields(self):
        meta = obs.bench_meta(wall_s=1.25)
        for key in ("backend", "platform", "deviceKind", "deviceCount",
                    "rssMb", "at", "pid", "runId", "traceId", "jax",
                    "wallSecs"):
            assert key in meta, key
        import jax

        assert meta["platform"] == jax.devices()[0].platform
        assert meta["deviceKind"] == jax.devices()[0].device_kind
        assert meta["deviceCount"] == len(jax.devices())
        assert meta["traceId"] is None
        assert meta["wallSecs"] == 1.25
        json.dumps(meta)

    def test_trace_id_flows_in_when_traced(self):
        tracer = obs.start_trace()
        meta = obs.bench_meta()
        obs.stop_trace()
        assert meta["traceId"] == tracer.trace_id

    def test_overhead_estimator_requires_disabled(self):
        est = obs.estimate_disabled_overhead_s(100, samples=1000)
        assert 0 <= est < 0.1
        obs.start_trace()
        try:
            with pytest.raises(RuntimeError):
                obs.estimate_disabled_overhead_s(100, samples=10)
        finally:
            obs.stop_trace()
