"""Unified telemetry (obs/): registry semantics, exposition lint, trace
spans, and the instrumentation seams the serving stack feeds.

The registry/trace primitives are pure stdlib, so most tests here are fast
and engine-free; the LOAD-span integration tests at the bottom build one
small engine archive per module.
"""
import json
import threading
import time

import jax
import pytest

from repro.obs import metrics as obs_metrics
from repro.obs import trace as obs_trace
from repro.obs import (LabelCardinalityError, MetricsRegistry, span,
                       lint_exposition, validate_trace)


@pytest.fixture(autouse=True)
def _clean_obs():
    """Every test starts with collection off and zeroed metrics."""
    obs_metrics.disable()
    obs_metrics.reset()
    if obs_trace.active():
        obs_trace.stop()
    yield
    obs_metrics.disable()
    obs_metrics.reset()
    if obs_trace.active():
        obs_trace.stop()


# ---------------------------------------------------------------------------
# registry semantics
# ---------------------------------------------------------------------------
class TestRegistry:
    def test_disabled_mutators_record_nothing(self):
        r = MetricsRegistry()
        c = r.counter("c_total", "c")
        g = r.gauge("g", "g")
        h = r.histogram("h_seconds", "h")
        c.inc()
        g.set(5)
        h.observe(0.1)
        assert c.value() == 0.0
        assert g.value() == 0.0
        assert h.snapshot() == ([0] * (len(h.buckets) + 1), 0.0, 0)
        # no children were even allocated
        assert not c.samples() and not g.samples()

    def test_disabled_path_is_cheap(self):
        """The disabled mutator is one global read + return. The bound here
        is deliberately generous (CI jitter); it exists to catch a rewrite
        that starts allocating label tuples or taking locks when off."""
        c = obs_metrics.counter("cheap_total", "c", ("k",))
        n = 20000
        t0 = time.perf_counter()
        for _ in range(n):
            c.inc(k="v")
        per_op = (time.perf_counter() - t0) / n
        assert per_op < 50e-6, f"disabled inc() costs {per_op * 1e9:.0f}ns"

    def test_enable_disable_scope(self):
        c = obs_metrics.counter("scoped_total", "c")
        with obs_metrics.enabled_scope():
            c.inc()
            assert obs_metrics.enabled()
        assert not obs_metrics.enabled()
        c.inc()  # off again: dropped
        assert c.value() == 1.0

    def test_label_cardinality_cap(self):
        r = MetricsRegistry()
        c = r.counter("explode_total", "c", ("req",), max_label_sets=8)
        obs_metrics.enable()
        for i in range(8):
            c.inc(req=str(i))
        with pytest.raises(LabelCardinalityError):
            c.inc(req="one-too-many")
        # existing label sets still usable after the cap trips
        c.inc(req="3")
        assert c.value(req="3") == 2.0

    def test_undeclared_label_rejected(self):
        c = obs_metrics.counter("strict_total", "c", ("a",))
        obs_metrics.enable()
        with pytest.raises(ValueError):
            c.inc(b="nope")

    def test_redeclare_is_idempotent_but_kind_checked(self):
        r = MetricsRegistry()
        c1 = r.counter("twice_total", "c")
        c2 = r.counter("twice_total", "c")
        assert c1 is c2
        with pytest.raises(ValueError):
            r.gauge("twice_total", "now a gauge")

    def test_counter_rejects_negative(self):
        c = obs_metrics.counter("mono_total", "c")
        obs_metrics.enable()
        with pytest.raises(ValueError):
            c.inc(-1)

    def test_histogram_buckets(self):
        r = MetricsRegistry()
        h = r.histogram("lat_seconds", "h", buckets=(0.1, 1.0, 10.0))
        obs_metrics.enable()
        for v in (0.05, 0.1, 0.5, 2.0, 100.0):
            h.observe(v)
        cum, total, count = h.snapshot()
        # le=0.1 holds 0.05 and the boundary 0.1; le=1.0 adds 0.5;
        # le=10.0 adds 2.0; +Inf adds 100.0
        assert cum == [2, 3, 4, 5]
        assert count == 5
        assert total == pytest.approx(102.65)


# ---------------------------------------------------------------------------
# exposition
# ---------------------------------------------------------------------------
class TestExposition:
    def test_render_parses_clean(self):
        obs_metrics.enable()
        c = obs_metrics.counter("exp_total", "with \"quotes\" and \\slash",
                                ("mode",))
        g = obs_metrics.gauge("exp_gauge", "g", ("fleet",))
        h = obs_metrics.histogram("exp_seconds", "h")
        c.inc(mode="a")
        c.inc(2, mode='we"ird\nvalue')
        g.set(-3.5, fleet="f")
        h.observe(0.01)
        h.observe(999.0)
        text = obs_metrics.render()
        assert lint_exposition(text) == []
        assert 'exp_total{mode="a"} 1' in text
        assert "# TYPE exp_seconds histogram" in text

    def test_lint_catches_corruption(self):
        good = ("# HELP x_total x\n# TYPE x_total counter\n"
                "x_total 1\n")
        assert lint_exposition(good) == []
        assert lint_exposition("x_total 1\nx_total 2\n")  # duplicate series
        assert lint_exposition("junk line !!!\n")
        # histogram without +Inf bucket
        bad_hist = ("# TYPE h histogram\n"
                    'h_bucket{le="1.0"} 1\nh_sum 0.5\nh_count 1\n')
        assert any("+Inf" in f for f in lint_exposition(bad_hist))
        # non-cumulative buckets
        bad_cum = ("# TYPE h histogram\n"
                   'h_bucket{le="1.0"} 5\nh_bucket{le="+Inf"} 3\n'
                   "h_sum 0.5\nh_count 3\n")
        assert any("non-decreasing" in f or "cumulative" in f
                   for f in lint_exposition(bad_cum))

    def test_value_accessor(self):
        obs_metrics.enable()
        c = obs_metrics.counter("acc_total", "c", ("k",))
        c.inc(3, k="x")
        assert obs_metrics.value("acc_total", {"k": "x"}) == 3.0
        assert obs_metrics.value("acc_total", {"k": "never"}) == 0.0
        with pytest.raises(KeyError):
            obs_metrics.value("no_such_metric")


# ---------------------------------------------------------------------------
# trace spans
# ---------------------------------------------------------------------------
class TestTrace:
    def test_span_measures_even_when_off(self):
        with span("work", cat="t") as sp:
            time.sleep(0.001)
        assert sp.seconds >= 0.001
        assert not obs_trace.active()

    def test_span_records_when_on(self):
        obs_trace.start()
        obs_trace.set_thread_name("test.main")
        with span("work", cat="t", tag="x"):
            pass
        obs_trace.instant("marker", cat="t")
        doc = obs_trace.stop().to_dict()
        assert validate_trace(doc) == []
        names = [e["name"] for e in doc["traceEvents"]]
        assert "work" in names and "marker" in names
        work = obs_trace.spans_named(doc, "work")[0]
        assert work["args"]["tag"] == "x"
        assert work["dur"] >= 0

    def test_span_records_exception(self):
        obs_trace.start()
        with pytest.raises(RuntimeError):
            with span("boom", cat="t"):
                raise RuntimeError("no")
        doc = obs_trace.stop().to_dict()
        ev = obs_trace.spans_named(doc, "boom")[0]
        assert "error" in ev["args"]

    def test_concurrent_spans_thread_safe(self):
        obs_trace.start()
        n_threads, n_spans = 8, 200
        # hold every worker at the line until all are alive: get_ident()
        # values are only unique among concurrently-live threads
        gate = threading.Barrier(n_threads)

        def worker(i):
            gate.wait()
            obs_trace.set_thread_name(f"w{i}")
            for j in range(n_spans):
                with span("tick", cat="t", i=i, j=j):
                    pass

        ts = [threading.Thread(target=worker, args=(i,))
              for i in range(n_threads)]
        for t in ts:
            t.start()
        for t in ts:
            t.join()
        doc = obs_trace.stop().to_dict()
        assert validate_trace(doc) == []
        ticks = obs_trace.spans_named(doc, "tick")
        assert len(ticks) == n_threads * n_spans
        assert len({e["tid"] for e in ticks}) == n_threads

    def test_bounded_buffer_drops_not_grows(self):
        col = obs_trace.start(max_events=10)
        for i in range(50):
            obs_trace.instant(f"e{i}")
        assert len(col.events()) == 10
        assert col.dropped == 40
        doc = obs_trace.stop().to_dict()
        assert doc["otherData"]["dropped_events"] == 40

    def test_save_round_trips(self, tmp_path):
        obs_trace.start()
        with span("disk", cat="t"):
            pass
        p = str(tmp_path / "trace.json")
        obs_trace.save(p)
        obs_trace.stop()
        doc = json.loads(open(p).read())
        assert validate_trace(doc) == []
        assert obs_trace.spans_named(doc, "disk")

    def test_span_lands_in_the_profiler_trace(self, tmp_path):
        """Under a profiler session a span is also a profiler event: its
        name stays clean, its args (those given late too) arrive as stats,
        and a child lies inside its parent's interval."""
        from jax.profiler import ProfileData
        with jax.profiler.trace(str(tmp_path)):
            with span("outer.step", cat="t", rows=3) as sp:
                with span("outer.child", cat="t"):
                    jax.numpy.ones(4).block_until_ready()
                sp.set(bucket=4)
        assert not obs_trace.active()  # the JSON sink stayed off
        (path,) = tmp_path.glob("plugins/profile/*/*.xplane.pb")
        found = {}
        for plane in ProfileData.from_file(str(path)).planes:
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith("outer."):
                        found[e.name] = (e.start_ns, e.duration_ns,
                                         dict(e.stats))
        assert set(found) == {"outer.step", "outer.child"}
        (p0, pd, stats), (c0, cd, _) = found["outer.step"], found["outer.child"]
        assert stats == {"rows": 3, "bucket": 4}
        assert p0 <= c0 and c0 + cd <= p0 + pd
        assert sp.seconds * 1e9 <= pd

    def test_set_adds_args_to_the_json_event(self):
        """Args given late through ``set`` join those given at the start,
        and an exception's name is added beside them."""
        obs_trace.start()
        with span("late", cat="t", rows=2) as sp:
            sp.set(bucket=4)
        with pytest.raises(KeyError):
            with span("failed", cat="t") as sp:
                sp.set(bucket=1)
                raise KeyError("x")
        doc = obs_trace.stop().to_dict()
        (late,) = obs_trace.spans_named(doc, "late")
        (failed,) = obs_trace.spans_named(doc, "failed")
        assert late["args"] == {"rows": 2, "bucket": 4}
        assert failed["args"] == {"bucket": 1, "error": "KeyError"}

    def test_span_off_path_is_cheap(self):
        """No profiler session and no tracing: a span is a timer and one
        ``is_enabled()`` call. Best of many short batches, to ride out the
        jitter of a loaded machine."""
        def batch(n=500):
            t0 = time.perf_counter()
            for _ in range(n):
                with span("cheap", cat="t"):
                    pass
            return (time.perf_counter() - t0) / n
        batch(10)
        per_op = min(batch() for _ in range(20))
        assert per_op < 2e-6, f"span costs {per_op * 1e9:.0f}ns off"


# ---------------------------------------------------------------------------
# integration: the serving stack feeds the same numbers it reports
# ---------------------------------------------------------------------------
from repro.configs.registry import get_arch  # noqa: E402
from repro.models.model import Model  # noqa: E402
from repro.serving.engine import ServingEngine  # noqa: E402


def make_engine(**kw):
    cfg = get_arch("smollm-360m").reduced()
    eng = ServingEngine(Model(cfg), max_batch=4, max_seq=64,
                        bucket_mode="pow2", **kw)
    eng.load_weights(rng=jax.random.PRNGKey(7))
    return eng


@pytest.fixture(scope="module")
def saved_archive(tmp_path_factory):
    path = str(tmp_path_factory.mktemp("obs") / "obs.fndry")
    eng = make_engine()
    eng.save_archive(path)
    from repro.core import Archive
    return Archive.load(path)


class TestServingIntegration:
    def test_load_spans_on_distinct_threads(self, saved_archive):
        """A cold start under tracing must show the pipelined LOAD: fetch
        and deserialize spans live on their own stage threads, distinct
        from the install thread."""
        obs_trace.start()
        eng = make_engine()
        eng.cold_start_foundry(saved_archive)
        doc = obs_trace.stop().to_dict()
        assert validate_trace(doc) == []
        fetch = obs_trace.spans_named(doc, "load.fetch")
        deser = obs_trace.spans_named(doc, "load.deserialize")
        install = obs_trace.spans_named(doc, "load.install")
        assert fetch and deser and install
        tids = ({e["tid"] for e in fetch} | {e["tid"] for e in deser}
                | {e["tid"] for e in install})
        assert len(tids) >= 2, "LOAD stages all ran on one thread"

    def test_registry_matches_load_report(self, saved_archive):
        obs_metrics.enable()
        eng = make_engine()
        eng.cold_start_foundry(saved_archive)
        load_rep = eng._load_report  # the LoadReport the registry was fed
        busy = obs_metrics.REGISTRY.get(
            "foundry_load_pipeline_busy_seconds_total")
        for stage in ("fetch", "deserialize", "install"):
            assert busy.value(stage=stage) == pytest.approx(
                load_rep.pipeline[f"{stage}_s"]), stage
        assert obs_metrics.value("engine_cold_starts_total",
                                 {"mode": "foundry"}) == 1.0

    def test_queue_wait_below_ttft_and_observed(self, saved_archive):
        obs_metrics.enable()
        eng = make_engine()
        eng.cold_start_foundry(saved_archive)
        reqs = [eng.submit([5, 9, 2], 4), eng.submit([3, 1], 4)]
        eng.run_until_drained()
        for r in reqs:
            assert r.queue_wait_s is not None
            assert r.ttft is not None
            assert 0 <= r.queue_wait_s <= r.ttft
        h = obs_metrics.REGISTRY.get("serving_queue_wait_seconds")
        assert h.snapshot()[2] == len(reqs)
        tpot = obs_metrics.REGISTRY.get("serving_tpot_seconds")
        assert tpot.snapshot()[2] > 0, "no decode-step TPOT observed"

    def test_step_spans_in_order_inside_the_parent(self, saved_archive):
        """One step of a paged engine is one engine.step span over its six
        children, in the order they run, on the same thread."""
        eng = make_engine()
        eng.cold_start_foundry(saved_archive)
        eng.submit([5, 9, 2], 4)
        obs_trace.start()
        assert eng.step() == 1
        doc = obs_trace.stop().to_dict()
        assert validate_trace(doc) == []
        (step,) = obs_trace.spans_named(doc, "engine.step")
        # the bucket the program ran at: a template may pad-serve bucket 1
        assert step["args"] == {"rows": 1, "bucket": eng.pool.cur_bucket,
                                "fill_rows": 1}
        kids = sorted((e for e in doc["traceEvents"] if e.get("ph") == "X"
                       and e["name"].startswith("engine.")
                       and e is not step), key=lambda e: e["ts"])
        assert [e["name"] for e in kids] == [
            "engine.admit", "engine.pool_sync", "engine.tokens",
            "engine.dispatch", "engine.readback", "engine.finish"]
        for e in kids:
            assert e["tid"] == step["tid"]
            assert step["ts"] <= e["ts"]
            assert e["ts"] + e["dur"] <= step["ts"] + step["dur"]
        for a, b in zip(kids, kids[1:]):
            assert a["ts"] + a["dur"] <= b["ts"]
        # a tick with nothing queued or running records no step
        eng.run_until_drained()
        obs_trace.start()
        assert eng.step() == 0
        assert not obs_trace.spans_named(obs_trace.stop().to_dict(),
                                         "engine.step")

    def test_tpot_observes_the_step_span(self, saved_archive, monkeypatch):
        """serving_tpot_seconds is fed the engine.step span's own seconds:
        one measurement, two consumers."""
        from repro.serving import engine as engine_mod
        seen = []

        class Recording(span):
            __slots__ = ()

            def __exit__(self, *exc):
                out = super().__exit__(*exc)
                if self.name == "engine.step":
                    seen.append(self.seconds)
                return out

        monkeypatch.setattr(engine_mod, "span", Recording)
        obs_metrics.enable()
        eng = make_engine()
        eng.cold_start_foundry(saved_archive)
        eng.submit([5, 9, 2], 3)
        eng.run_until_drained()
        counts, total, n = obs_metrics.REGISTRY.get(
            "serving_tpot_seconds").snapshot()
        assert n == len(seen) == eng.decode_steps > 0
        assert total == sum(seen)
        assert obs_metrics.value("engine_decode_steps_total") == n
