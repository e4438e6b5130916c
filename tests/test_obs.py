"""Observability subsystem (DESIGN.md §13).

Contracts under test:

  1. **Registry semantics** — counter monotonicity, gauge last-write,
     histogram explicit-bucket binning, labeled children, kind conflicts;
  2. **Disabled is free** — a disabled registry hands back the one shared
     NULL sink (no allocation), a disabled tracer without JAX the one shared
     NULL_SPAN, and with JAX a span that opens only a profiler annotation;
  3. **Views** — Prometheus text exposition golden, flat() naming;
  4. **Trace** — span nesting by containment, bounded ring with accounted
     drops, Chrome trace-event JSON schema validity;
  5. **Checkpoint round-trip** — registry state()/load_state() and the
     RoundTimeline survive JSON; the stream checkpoint carries counters so a
     resumed run continues them instead of restarting at zero.
"""

from __future__ import annotations

import json
import os
import random
import subprocess
import sys

import pytest

from repro import obs
from repro.core import OdbConfig
from repro.core.protocol import RoundRecord
from repro.data.datasets import _records_from_lengths
from repro.data.pipeline import PipelinePolicy
from repro.obs import (
    DROPPED_SERIES,
    NULL,
    NULL_SPAN,
    CrossProcessAggregator,
    MetricsRegistry,
    RoundTimeline,
    RunReporter,
    SpanTracer,
)
from repro.obs import trace as trace_mod
from repro.stream import StreamCheckpoint, StreamExecutor

POLICY = PipelinePolicy()


def make_records(n: int, seed: int = 0, lo: int = 16, hi: int = 900):
    rng = random.Random(seed)
    return _records_from_lengths([rng.randint(lo, hi) for _ in range(n)])


def small_cfg(**kw) -> OdbConfig:
    base = dict(l_max=1024, buffer_size=16, prefetch_factor=8, num_workers=1)
    base.update(kw)
    return OdbConfig(**base)


@pytest.fixture(autouse=True)
def clean_defaults():
    """Tests below mutate the process-wide registry/tracer: isolate them."""
    reg, tracer = obs.default_registry(), obs.default_tracer()
    reg.reset()
    reg.enable()
    tracer.reset()
    tracer.disable()
    yield
    reg.reset()
    reg.enable()
    tracer.reset()
    tracer.disable()


class TestRegistry:
    def test_counter_monotone(self):
        reg = MetricsRegistry()
        c = reg.counter("x_total")
        c.inc()
        c.inc(2.5)
        assert c.value == 3.5
        with pytest.raises(ValueError, match=">= 0"):
            c.inc(-1)

    def test_gauge_last_write(self):
        g = MetricsRegistry().gauge("x")
        g.set(4)
        g.inc()
        g.dec(2)
        assert g.value == 3.0

    def test_histogram_binning(self):
        h = MetricsRegistry().histogram("lat", buckets=(1.0, 2.0))
        for v in (0.5, 1.0, 2.0, 4.0):  # le semantics: 1.0 lands in le="1"
            h.observe(v)
        assert h.sample() == {
            "count": 4,
            "sum": 7.5,
            "buckets": {"1": 2, "2": 3, "+Inf": 4},
        }

    def test_histogram_rejects_bad_buckets(self):
        with pytest.raises(ValueError, match="increasing"):
            MetricsRegistry().histogram("h", buckets=(2.0, 1.0))
        with pytest.raises(ValueError, match="increasing"):
            MetricsRegistry().histogram("h2", buckets=(1.0, 1.0))

    def test_labels_make_distinct_children(self):
        reg = MetricsRegistry()
        a = reg.counter("req_total", route="a")
        b = reg.counter("req_total", route="b")
        assert a is not b
        assert reg.counter("req_total", route="a") is a  # stable lookup
        a.inc(2)
        b.inc()
        assert reg.flat() == {
            'req_total{route="a"}': 2.0,
            'req_total{route="b"}': 1.0,
        }

    def test_kind_conflict_raises(self):
        reg = MetricsRegistry()
        reg.counter("x")
        with pytest.raises(ValueError, match="already registered"):
            reg.gauge("x")

    def test_disabled_returns_shared_null_sink(self):
        reg = MetricsRegistry(enabled=False)
        c = reg.counter("x_total")
        assert c is NULL  # zero allocation on the disabled path
        c.inc()
        c.observe(1)
        c.set(5)
        assert c.value == 0.0
        assert reg.snapshot() == {}
        reg.enable()
        assert reg.counter("x_total") is not NULL

    def test_prometheus_text_golden(self):
        reg = MetricsRegistry()
        reg.counter("req_total", help="requests", route="a").inc(3)
        reg.gauge("temp").set(1.5)
        h = reg.histogram("lat_seconds", buckets=(1.0, 2.0), help="latency",
                          unit="seconds")
        for v in (0.5, 2.0, 4.0):
            h.observe(v)
        assert reg.prometheus_text() == (
            "# HELP lat_seconds latency\n"
            "# UNIT lat_seconds seconds\n"
            "# TYPE lat_seconds histogram\n"
            'lat_seconds_bucket{le="1"} 1\n'
            'lat_seconds_bucket{le="2"} 2\n'
            'lat_seconds_bucket{le="+Inf"} 3\n'
            "lat_seconds_sum 6.5\n"
            "lat_seconds_count 3\n"
            "# HELP req_total requests\n"
            "# TYPE req_total counter\n"
            'req_total{route="a"} 3\n'
            "# TYPE temp gauge\n"
            "temp 1.5\n"
        )

    def test_state_round_trip_through_json(self):
        reg = MetricsRegistry()
        reg.counter("a_total", lbl="x").inc(7)
        reg.gauge("g").set(-2.5)
        h = reg.histogram("h_seconds", buckets=(0.1, 1.0))
        h.observe(0.05)
        h.observe(0.5)
        h.observe(3.0)
        blob = json.dumps(reg.state())
        fresh = MetricsRegistry()
        fresh.load_state(json.loads(blob))
        assert fresh.flat() == reg.flat()
        # Per-bin counts (not just the flat cumulative view) must survive.
        restored = fresh.histogram("h_seconds", buckets=(0.1, 1.0))
        assert restored.counts == h.counts
        # load_state is a no-op on a disabled registry (nothing to bind to).
        off = MetricsRegistry(enabled=False)
        off.load_state(json.loads(blob))
        assert off.snapshot() == {}

    def test_state_prefix_filter(self):
        reg = MetricsRegistry()
        reg.counter("odb_x_total").inc()
        reg.counter("train_y_total").inc()
        assert set(reg.state(prefix="odb_")) == {"odb_x_total"}


class TestTracer:
    def test_disabled_span_is_shared_null(self, monkeypatch):
        # Without JAX there is no profiler sink either.
        monkeypatch.setattr(trace_mod, "_profiler_annotation", lambda: None)
        tracer = SpanTracer(enabled=False)
        assert tracer.span("x") is NULL_SPAN
        tracer.complete("x", 0.0, 1.0)
        tracer.instant("x")
        assert tracer.events() == []

    def test_disabled_span_without_profiler_records_nothing(self):
        tracer = SpanTracer(enabled=False)
        with tracer.span("outer", cat="t", k=1) as span:
            span.note(done=True)
            with tracer.span("inner"):
                pass
        assert tracer.events() == [] and tracer.dropped == 0

    def test_span_closes_its_annotation_when_the_body_raises(self):
        tracer = SpanTracer(enabled=True)
        with pytest.raises(KeyError):
            with tracer.span("failing"):
                raise KeyError("x")
        (event,) = tracer.events()
        assert event["name"] == "failing"
        with tracer.span("after"):  # the thread's annotation stack is whole
            pass
        assert [e["name"] for e in tracer.events()] == ["failing", "after"]

    def test_note_attaches_args_at_exit(self):
        tracer = SpanTracer(enabled=True)
        with tracer.span("round", cat="t", round=0) as span:
            span.note(target=4)
        assert tracer.events()[0]["args"] == {"round": 0, "target": 4}

    def test_obs_imports_and_records_without_jax(self):
        code = (
            "import sys; sys.modules['jax'] = None\n"
            "from repro import obs\n"
            "t = obs.default_tracer(); t.enable()\n"
            "with obs.span('a/b') as s:\n"
            "    s.note(n=1)\n"
            "assert [e['name'] for e in t.events()] == ['a/b'], t.events()\n"
        )
        env = dict(os.environ, PYTHONPATH=os.pathsep.join(sys.path))
        proc = subprocess.run([sys.executable, "-c", code], env=env,
                              capture_output=True, text=True, timeout=120)
        assert proc.returncode == 0, proc.stderr

    def test_nesting_by_containment(self):
        tracer = SpanTracer(enabled=True)
        with tracer.span("outer", cat="t"):
            with tracer.span("inner", cat="t", k=1):
                pass
        events = {e["name"]: e for e in tracer.events()}
        outer, inner = events["outer"], events["inner"]
        assert outer["ts"] <= inner["ts"]
        assert inner["ts"] + inner["dur"] <= outer["ts"] + outer["dur"]
        assert inner["args"] == {"k": 1}

    def test_ring_overflow_is_bounded_and_accounted(self):
        tracer = SpanTracer(capacity=4, enabled=True)
        for i in range(10):
            tracer.instant(f"e{i}")
        assert len(tracer.events()) == 4
        assert tracer.dropped == 6
        # Oldest dropped: the tail of the run is what survives.
        assert [e["name"] for e in tracer.events()] == ["e6", "e7", "e8", "e9"]
        assert tracer.export()["otherData"]["dropped_events"] == 6

    def test_chrome_trace_schema(self, tmp_path):
        tracer = SpanTracer(enabled=True)
        with tracer.span("a", cat="test"):
            tracer.instant("mark", cat="test", n=3)
        path = tracer.write(tmp_path / "trace.json")
        doc = json.loads(path.read_text())  # must be valid JSON end-to-end
        assert doc["displayTimeUnit"] == "ms"
        assert isinstance(doc["traceEvents"], list) and doc["traceEvents"]
        for e in doc["traceEvents"]:
            assert {"name", "ph", "ts", "pid", "tid"} <= e.keys()
            assert e["ph"] in ("X", "i")
            if e["ph"] == "X":
                assert e["dur"] >= 0
            else:
                assert e["s"] == "t"

    def test_capacity_validated(self):
        with pytest.raises(ValueError, match="capacity"):
            SpanTracer(capacity=0)


class TestRoundTimeline:
    @staticmethod
    def _record(i, target, statuses, views):
        return RoundRecord(
            round_index=i, statuses=tuple(statuses),
            idx_budgets=tuple(0 for _ in statuses), target=target,
            emitted_views=views, skip_output=False, second_gather=False,
            potential=target,
        )

    def test_straggler_census_and_round_trip(self):
        tl = RoundTimeline(world_size=2)
        tl.record_round(self._record(0, 3, (3, 0), 2), 0.002, iteration=0)
        tl.record_round(self._record(1, 0, (0, 0), 0), 0.0001, iteration=0)
        tl.record_closure("join_all_finished", iteration=0, rounds=2)
        d = tl.as_dict()
        # Rank 1 straggled in round 0; the all-zero round is no straggle.
        assert d["straggler_rounds_per_rank"] == [0, 1]
        assert d["rounds"] == 2 and d["emitted_views"] == 2
        assert d["closures"] == [
            {"event": "join_all_finished", "iteration": 0, "iteration_rounds": 2}
        ]
        restored = RoundTimeline.from_dict(json.loads(json.dumps(d)))
        assert restored.as_dict() == d

    def test_records_window_is_bounded(self):
        tl = RoundTimeline(world_size=1, keep_records=3)
        for i in range(5):
            tl.record_round(self._record(i, 1, (1,), 1), 0.001, iteration=0)
        assert len(tl.records) == 3
        assert tl.records_dropped == 2
        assert [r["round"] for r in tl.records] == [2, 3, 4]
        assert tl.rounds == 5  # aggregates keep counting past the window


class TestCheckpointCarriesTelemetry:
    def test_stream_resume_continues_counters(self):
        """The full persistence path: executor counters + round audit ride the
        stream checkpoint through JSON and resume into a fresh registry."""
        reg = obs.default_registry()
        records = make_records(120, 7)
        full = len(list(StreamExecutor(records, POLICY, 2, small_cfg(), seed=5).steps()))
        reg.reset()

        ex = StreamExecutor(records, POLICY, 2, small_cfg(), seed=5)
        for _ in range(3):
            assert ex.step() is not None
        blob = ex.checkpoint().to_json()
        assert reg.flat()["odb_stream_steps_total"] == 3
        rounds_at_cut = ex.telemetry.rounds
        assert rounds_at_cut > 0

        reg.reset()  # simulate a fresh process after preemption
        resumed = StreamExecutor.resume(
            StreamCheckpoint.from_json(blob), records, POLICY
        )
        flat = reg.flat()
        assert flat["odb_stream_steps_total"] == 3  # restored, not zeroed
        assert flat["odb_protocol_rounds_total"] >= rounds_at_cut
        assert resumed.telemetry.rounds == rounds_at_cut
        tail = list(resumed.steps())
        assert reg.flat()["odb_stream_steps_total"] == 3 + len(tail) == full

    def test_round_timeline_rides_checkpoint_payload(self):
        ex = StreamExecutor(make_records(60, 3), POLICY, 2, small_cfg(), seed=1)
        ex.step()
        payload = ex.checkpoint().payload
        assert payload["telemetry"]["rounds"]["rounds"] == ex.telemetry.rounds
        assert "odb_stream_steps_total" in payload["telemetry"]["counters"]


class TestReporter:
    def test_reporter_writes_all_artifacts(self, tmp_path):
        reg = MetricsRegistry()
        tracer = SpanTracer(enabled=True)
        reg.counter("odb_x_total").inc(4)
        with tracer.span("phase"):
            pass
        tl = RoundTimeline(world_size=1)
        reporter = RunReporter(tmp_path, registry=reg, tracer=tracer)
        paths = reporter.write(round_audit=tl, extra={"arch": "t"})
        assert set(paths) == {"metrics", "prometheus", "trace", "rounds"}
        metrics = json.loads((tmp_path / "metrics.json").read_text())
        assert metrics["flat"]["odb_x_total"] == 4.0
        assert metrics["run"] == {"arch": "t"}
        assert "odb_x_total 4" in (tmp_path / "metrics.prom").read_text()
        trace = json.loads((tmp_path / "trace.json").read_text())
        assert [e["name"] for e in trace["traceEvents"]] == ["phase"]
        assert json.loads((tmp_path / "rounds.json").read_text())["rounds"] == 0

    def test_enable_telemetry_switches_defaults_on(self, tmp_path):
        reg, tracer = obs.default_registry(), obs.default_tracer()
        reg.disable()
        assert not tracer.enabled
        reporter = obs.enable_telemetry(tmp_path)
        assert reg.enabled and tracer.enabled
        assert reporter.registry is reg and reporter.tracer is tracer


class TestModuleConveniences:
    def test_module_level_helpers_hit_defaults(self):
        obs.counter("conv_total").inc()
        obs.gauge("conv_g").set(2)
        obs.histogram("conv_h", buckets=(1.0,)).observe(0.5)
        flat = obs.default_registry().flat()
        assert flat["conv_total"] == 1.0
        assert flat["conv_g"] == 2.0
        assert flat["conv_h_count"] == 1
        obs.default_tracer().enable()
        with obs.span("conv/span"):
            obs.instant("conv/mark")
        names = {e["name"] for e in obs.default_tracer().events()}
        assert {"conv/span", "conv/mark"} <= names


class TestCardinalityBudget:
    def test_cap_drops_new_label_sets(self):
        reg = MetricsRegistry(max_label_children=2)
        a = reg.counter("odb_x_total", shard="a")
        b = reg.counter("odb_x_total", shard="b")
        dropped = reg.counter("odb_x_total", shard="c")
        assert dropped is NULL  # refused, not created
        dropped.inc()  # and safe to use as a sink
        a.inc()
        b.inc(2)
        flat = reg.flat()
        assert flat['odb_x_total{shard="a"}'] == 1.0
        assert flat['odb_x_total{shard="b"}'] == 2.0
        assert flat[DROPPED_SERIES] == 1.0
        assert not any("c" in k for k in flat if k.startswith("odb_x_total"))

    def test_existing_children_survive_past_cap(self):
        reg = MetricsRegistry(max_label_children=1)
        first = reg.counter("odb_y_total", layout="dense")
        assert reg.counter("odb_y_total", layout="packed") is NULL
        # The pre-cap child keeps resolving to the same live instrument.
        again = reg.counter("odb_y_total", layout="dense")
        assert again is first

    def test_unlabeled_series_not_budgeted(self):
        reg = MetricsRegistry(max_label_children=1)
        for name in ("a_total", "b_total", "c_total"):
            assert reg.counter(name) is not NULL
        assert DROPPED_SERIES not in reg.flat()

    def test_cap_applies_per_family(self):
        reg = MetricsRegistry(max_label_children=1)
        assert reg.counter("one_total", k="x") is not NULL
        assert reg.counter("two_total", k="y") is not NULL  # separate family
        assert reg.counter("one_total", k="z") is NULL
        assert reg.flat()[DROPPED_SERIES] == 1.0

    def test_cap_disabled_with_none(self):
        reg = MetricsRegistry(max_label_children=None)
        for i in range(512):
            assert reg.counter("odb_free_total", i=str(i)) is not NULL


class TestCrossProcessAggregator:
    def test_counter_deltas_sum_across_dumps(self):
        parent = MetricsRegistry()
        child = MetricsRegistry()
        agg = CrossProcessAggregator(parent)
        child.counter("odb_w_total", layout="dense").inc(3)
        agg.merge("w0", child.state(), timestamp=1.0)
        child.counter("odb_w_total", layout="dense").inc(2)
        agg.merge("w0", child.state(), timestamp=2.0)  # cumulative re-ship
        assert parent.flat()['odb_w_total{layout="dense"}'] == 5.0

    def test_counter_reship_is_idempotent(self):
        parent, child = MetricsRegistry(), MetricsRegistry()
        agg = CrossProcessAggregator(parent)
        child.counter("odb_w_total").inc(4)
        state = child.state()
        agg.merge("w0", state, timestamp=1.0)
        agg.merge("w0", state, timestamp=2.0)  # same dump twice: no double count
        assert parent.flat()["odb_w_total"] == 4.0

    def test_counter_restart_detected(self):
        parent, child = MetricsRegistry(), MetricsRegistry()
        agg = CrossProcessAggregator(parent)
        child.counter("odb_w_total").inc(10)
        agg.merge("w0", child.state(), timestamp=1.0)
        fresh = MetricsRegistry()  # the worker restarted: counters reset
        fresh.counter("odb_w_total").inc(2)
        agg.merge("w0", fresh.state(), timestamp=2.0)
        assert parent.flat()["odb_w_total"] == 12.0

    def test_counters_sum_across_sources(self):
        parent = MetricsRegistry()
        agg = CrossProcessAggregator(parent)
        for source in ("w0", "w1"):
            child = MetricsRegistry()
            child.counter("odb_w_total").inc(3)
            agg.merge(source, child.state(), timestamp=1.0)
        assert parent.flat()["odb_w_total"] == 6.0

    def test_gauge_last_write_by_timestamp_wins(self):
        parent = MetricsRegistry()
        agg = CrossProcessAggregator(parent)
        early, late = MetricsRegistry(), MetricsRegistry()
        early.gauge("odb_depth").set(1)
        late.gauge("odb_depth").set(9)
        agg.merge("w1", late.state(), timestamp=5.0)
        agg.merge("w0", early.state(), timestamp=3.0)  # stale: must not clobber
        assert parent.flat()["odb_depth"] == 9.0

    def test_histogram_bins_merge_by_delta(self):
        parent, child = MetricsRegistry(), MetricsRegistry()
        agg = CrossProcessAggregator(parent)
        h = child.histogram("odb_h", buckets=(1.0, 10.0))
        h.observe(0.5)
        agg.merge("w0", child.state(), timestamp=1.0)
        h.observe(5.0)
        agg.merge("w0", child.state(), timestamp=2.0)
        merged = parent.histogram("odb_h", buckets=(1.0, 10.0))
        assert merged.count == 2
        assert merged.sum == pytest.approx(5.5)
        assert merged.counts[0] == 1 and merged.counts[1] == 1

    def test_kind_collision_skipped_not_raised(self):
        parent, child = MetricsRegistry(), MetricsRegistry()
        parent.gauge("odb_clash").set(7)
        child.counter("odb_clash").inc(3)
        agg = CrossProcessAggregator(parent)
        agg.merge("w0", child.state(), timestamp=1.0)  # must not raise
        assert parent.flat()["odb_clash"] == 7.0

    def test_disabled_parent_is_noop(self):
        parent, child = MetricsRegistry(), MetricsRegistry()
        parent.disable()
        child.counter("odb_w_total").inc(3)
        CrossProcessAggregator(parent).merge("w0", child.state(), 1.0)
        parent.enable()
        assert "odb_w_total" not in parent.flat()


class TestScrapeEndpoint:
    """Live Prometheus scrape server (satellite of DESIGN.md §17 PR)."""

    def test_serves_registry_text(self):
        import urllib.request

        from repro.obs import ScrapeServer

        reg = MetricsRegistry()
        reg.counter("odb_scrape_test_total").inc(3)
        srv = ScrapeServer(registry=reg, port=0).start()
        try:
            with urllib.request.urlopen(srv.url, timeout=5) as resp:
                assert resp.status == 200
                assert "text/plain" in resp.headers["Content-Type"]
                body = resp.read().decode()
            assert "odb_scrape_test_total 3" in body
        finally:
            srv.stop()

    def test_default_registry_resolved_per_request(self):
        """Instruments created AFTER start() must appear in the scrape —
        the registry is read per request, never captured at construction."""
        import urllib.request

        from repro.obs import start_scrape_server

        srv = start_scrape_server(0)
        try:
            obs.counter("odb_scrape_late_total").inc()
            with urllib.request.urlopen(srv.url, timeout=5) as resp:
                body = resp.read().decode()
            assert "odb_scrape_late_total 1" in body
        finally:
            srv.stop()

    def test_unknown_path_404(self):
        import urllib.error
        import urllib.request

        from repro.obs import ScrapeServer

        srv = ScrapeServer(registry=MetricsRegistry(), port=0).start()
        try:
            with pytest.raises(urllib.error.HTTPError) as err:
                urllib.request.urlopen(
                    f"http://127.0.0.1:{srv.port}/nope", timeout=5
                )
            assert err.value.code == 404
        finally:
            srv.stop()

    def test_stop_joins_thread_and_is_idempotent(self):
        import threading

        from repro.obs import ScrapeServer

        srv = ScrapeServer(registry=MetricsRegistry(), port=0).start()
        thread = srv._thread
        assert thread is not None and thread.daemon
        srv.stop()
        assert not thread.is_alive()
        assert "obs-scrape" not in {t.name for t in threading.enumerate()}
        srv.stop()  # second stop: no-op, no raise
