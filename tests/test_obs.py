"""Observability layer: metric math, snapshot algebra, exposition formats,
tracing semantics, and the cost contract of the disabled paths.

The delivery-path integration (metric byte totals == TransferReport totals,
live ``Op.METRICS`` scrape) is asserted in ``tests/test_transport.py``; this
file tests the ``repro.obs`` package itself.
"""

import contextlib
import json
import threading
import time

import pytest

from repro.obs import (LATENCY_BUCKETS, NULL_REGISTRY, NULL_TRACER,
                       MetricsRegistry, MetricsSnapshot, Span, Tracer,
                       check_monotonic, parse_prometheus_text,
                       to_prometheus_text)
from repro.obs import trace as trace_module


# ------------------------------------------------------------------ counters

class TestCounters:
    def test_inc_and_value(self):
        m = MetricsRegistry()
        c = m.counter("reqs_total", "requests").labels()
        c.inc()
        c.inc(4)
        assert c.value() == 5

    def test_labels_are_independent_children(self):
        m = MetricsRegistry()
        fam = m.counter("bytes_total", "bytes", ("direction",))
        fam.labels("in").inc(10)
        fam.labels("out").inc(3)
        assert fam.labels("in").value() == 10
        assert fam.labels("out").value() == 3

    def test_negative_increment_rejected(self):
        m = MetricsRegistry()
        with pytest.raises(ValueError):
            m.counter("c_total", "c").labels().inc(-1)

    def test_reregistration_returns_same_family(self):
        m = MetricsRegistry()
        a = m.counter("c_total", "c")
        b = m.counter("c_total", "c")
        a.labels().inc()
        assert b.labels().value() == 1

    def test_kind_mismatch_rejected(self):
        m = MetricsRegistry()
        m.counter("x_total", "x")
        with pytest.raises(ValueError):
            m.gauge("x_total", "x")

    def test_labelnames_mismatch_rejected(self):
        m = MetricsRegistry()
        m.counter("y_total", "y", ("a",))
        with pytest.raises(ValueError):
            m.counter("y_total", "y", ("a", "b"))

    def test_concurrent_increments_lose_nothing(self):
        """The whole point of re-basing ServerStats on the registry: many
        threads hammering one counter must not lose increments the way the
        old unsynchronized ``+=`` could."""
        m = MetricsRegistry()
        c = m.counter("n_total", "n").labels()
        n_threads, per_thread = 8, 2000

        def work():
            for _ in range(per_thread):
                c.inc()

        threads = [threading.Thread(target=work) for _ in range(n_threads)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert c.value() == n_threads * per_thread


# ------------------------------------------------------------------- gauges

class TestGauges:
    def test_set_inc_dec(self):
        m = MetricsRegistry()
        g = m.gauge("inflight", "in flight").labels()
        g.set(5)
        g.inc(2)
        g.dec()
        assert g.value() == 6


# --------------------------------------------------------------- histograms

class TestHistograms:
    def test_bucket_edges_are_le_semantics(self):
        m = MetricsRegistry()
        h = m.histogram("lat", "latency", buckets=(1.0, 2.0, 4.0)).labels()
        for v in (0.5, 1.0, 1.5, 4.0, 99.0):
            h.observe(v)
        view = m.snapshot().histogram("lat", {})
        # counts per bucket: le=1.0 gets {0.5, 1.0}, le=2.0 gets {1.5},
        # le=4.0 gets {4.0}, overflow gets {99.0}
        assert list(view.counts) == [2, 1, 1, 1]
        assert view.count == 5
        assert view.sum == pytest.approx(106.0)

    def test_quantiles_interpolate(self):
        m = MetricsRegistry()
        h = m.histogram("lat", "latency",
                        buckets=(0.1, 0.2, 0.4, 0.8)).labels()
        for _ in range(100):
            h.observe(0.15)                # all in the (0.1, 0.2] bucket
        view = m.snapshot().histogram("lat", {})
        q50 = view.quantile(0.5)
        assert 0.1 <= q50 <= 0.2
        assert view.quantile(0.0) <= view.quantile(0.99)

    def test_overflow_quantile_clamps_to_last_edge(self):
        m = MetricsRegistry()
        h = m.histogram("lat", "latency", buckets=(1.0, 2.0)).labels()
        h.observe(100.0)
        assert m.snapshot().histogram("lat", {}).quantile(0.99) == 2.0

    def test_empty_quantile_is_zero(self):
        m = MetricsRegistry()
        m.histogram("lat", "latency", buckets=(1.0,)).labels()
        assert m.snapshot().histogram("lat", {}).quantile(0.5) == 0.0

    def test_default_latency_buckets_span_sub_ms_to_10s(self):
        assert LATENCY_BUCKETS[0] <= 0.0005
        assert LATENCY_BUCKETS[-1] >= 10.0


# ---------------------------------------------------------------- snapshots

class TestSnapshots:
    def _sample(self):
        m = MetricsRegistry()
        m.counter("c_total", "c", ("k",)).labels("a").inc(3)
        m.gauge("g", "g").labels().set(7)
        h = m.histogram("h", "h", buckets=(1.0, 2.0)).labels()
        h.observe(0.5)
        h.observe(1.5)
        return m.snapshot()

    def test_value_lookup(self):
        snap = self._sample()
        assert snap.value("c_total", {"k": "a"}) == 3
        assert snap.value("g", {}) == 7
        assert snap.value("c_total", {"k": "zzz"}) == 0
        assert snap.value("nope", {}, default=None) is None

    def test_json_round_trip(self):
        snap = self._sample()
        again = MetricsSnapshot.from_json(snap.to_json())
        assert again.to_json_obj() == snap.to_json_obj()
        # and the payload is plain JSON (the Op.METRICS wire body)
        obj = json.loads(snap.to_json())
        assert obj["v"] == 1

    def test_merge_sums_counters_and_histograms(self):
        a, b = self._sample(), self._sample()
        merged = a.merge(b)
        assert merged.value("c_total", {"k": "a"}) == 6
        h = merged.histogram("h", {})
        assert h.count == 4
        assert h.sum == pytest.approx(4.0)

    def test_sum_values_across_label_sets(self):
        m = MetricsRegistry()
        fam = m.counter("c_total", "c", ("k",))
        fam.labels("a").inc(1)
        fam.labels("b").inc(2)
        snap = m.snapshot()
        assert snap.sum_values("c_total") == 3
        assert snap.sum_values("c_total", k="a") == 1


# --------------------------------------------------------------- exposition

class TestPrometheusText:
    def test_round_trip_parses(self):
        m = MetricsRegistry()
        m.counter("c_total", "help text", ("k",)).labels("v").inc(2)
        m.histogram("h_seconds", "hist", buckets=(0.5, 1.0)).labels()\
            .observe(0.7)
        text = to_prometheus_text(m.snapshot())
        parsed = parse_prometheus_text(text)
        assert parsed[("c_total", (("k", "v"),))] == 2
        # histogram exposition: cumulative buckets, +Inf, _sum, _count
        assert parsed[("h_seconds_bucket", (("le", "0.5"),))] == 0
        assert parsed[("h_seconds_bucket", (("le", "1"),))] == 1
        assert parsed[("h_seconds_bucket", (("le", "+Inf"),))] == 1
        assert parsed[("h_seconds_count", ())] == 1

    def test_parse_rejects_garbage(self):
        with pytest.raises(ValueError):
            parse_prometheus_text("this is not prometheus\n")

    def test_check_monotonic(self):
        m = MetricsRegistry()
        c = m.counter("c_total", "c").labels()
        c.inc(2)
        before = m.snapshot()
        c.inc()
        after = m.snapshot()
        assert check_monotonic(before, after) == []
        assert check_monotonic(after, before)  # regression detected


# ------------------------------------------------------------------ tracing

class TestTracing:
    def test_nesting_and_attrs(self):
        tr = Tracer(enabled=True)
        with tr.span("pull", tag="v1") as sp:
            with tr.span("plan"):
                pass
            sp.annotate(chunks=3)
        roots = tr.take()
        assert len(roots) == 1
        root = roots[0]
        assert root.name == "pull"
        assert root.attrs == {"tag": "v1", "chunks": 3}
        assert [c.name for c in root.children] == ["plan"]
        assert root.duration >= root.children[0].duration

    def test_explicit_parent_crosses_threads(self):
        tr = Tracer(enabled=True)
        with tr.span("execute") as sp:
            parent = tr.current()
            assert parent is sp

            def work():
                with tr.span("fetch_batch", parent=parent):
                    pass

            t = threading.Thread(target=work)
            t.start()
            t.join()
        [root] = tr.take()
        assert [c.name for c in root.children] == ["fetch_batch"]

    def test_error_annotated(self):
        tr = Tracer(enabled=True)
        with pytest.raises(RuntimeError):
            with tr.span("boom"):
                raise RuntimeError("x")
        [root] = tr.take()
        assert root.attrs["error"] == "RuntimeError"

    def test_ring_buffer_bounds_memory(self):
        tr = Tracer(enabled=True, capacity=4)
        for i in range(10):
            with tr.span(f"s{i}"):
                pass
        roots = tr.take()
        assert [r.name for r in roots] == ["s6", "s7", "s8", "s9"]
        assert tr.take() == []             # drained

    def test_dict_round_trip(self):
        tr = Tracer(enabled=True)
        with tr.span("a", k=1):
            with tr.span("b"):
                pass
        [root] = tr.take()
        again = Span.from_dict(root.to_dict())
        assert again.name == "a"
        assert again.attrs == {"k": 1}
        assert [c.name for c in again.children] == ["b"]
        walked = [(d, s.name) for d, s in again.walk()]
        assert walked == [(0, "a"), (1, "b")]

    def test_dict_round_trip_keeps_start(self):
        sp = Span("a", {"k": 1})
        sp.t0, sp.t1 = 12.5, 13.75
        child = Span("b")
        child.t0, child.t1 = 12.75, 13.0
        sp.children.append(child)
        again = Span.from_dict(json.loads(json.dumps(sp.to_dict())))
        assert (again.t0, again.t1) == (12.5, 13.75)
        assert (again.children[0].t0, again.children[0].duration) == \
            (12.75, 0.25)

    @pytest.mark.parametrize("fails", [False, True])
    def test_hook_wraps_every_span_once_nested(self, fails):
        """An enabled tracer enters its hook around each span, inside the
        parent's, and hands it the span's outcome."""
        log = []

        class Hooked:
            def __init__(self, name):
                self.name = name

            def __enter__(self):
                log.append(("enter", self.name))

            def __exit__(self, exc_type, exc, tb):
                log.append(("exit", self.name,
                            exc_type.__name__ if exc_type else None))
                return False

        tr = Tracer(enabled=True, hook=Hooked)
        with pytest.raises(RuntimeError) if fails else contextlib.nullcontext():
            with tr.span("outer"):
                with tr.span("inner"):
                    if fails:
                        raise RuntimeError("x")
        error = "RuntimeError" if fails else None
        assert log == [("enter", "outer"), ("enter", "inner"),
                       ("exit", "inner", error), ("exit", "outer", error)]
        [root] = tr.take()
        assert [c.name for c in root.children] == ["inner"]


# ------------------------------------------------------------ disabled cost

class TestDisabledCost:
    def test_null_registry_vends_noops(self):
        c = NULL_REGISTRY.counter("c_total", "c").labels()
        c.inc(5)
        assert c.value() == 0
        assert NULL_REGISTRY.snapshot().names() == []

    def test_disabled_tracer_shares_one_null_span(self):
        a = NULL_TRACER.span("x")
        b = NULL_TRACER.span("y", parent=None, attr=1)
        assert a is b                      # no allocation per span
        with a as sp:
            sp.annotate(ignored=True)
        assert NULL_TRACER.take() == []

    def test_disabled_tracing_is_cheap(self, monkeypatch):
        """Disabled span entry must cost roughly a no-op method call — the
        budget here (2µs/span) is ~100x the observed cost, tight enough to
        catch an accidental allocation-per-span or clock read.  A disabled
        tracer with a hook never calls it and never reads the clock."""
        calls = []

        class Clock:
            @staticmethod
            def perf_counter():
                calls.append("clock")
                return 0.0

        monkeypatch.setattr(trace_module, "time", Clock)
        tr = Tracer(enabled=False, hook=lambda name: calls.append(name))
        n = 20_000
        t0 = time.perf_counter()
        for _ in range(n):
            with tr.span("hot", a=1):
                pass
        per_span = (time.perf_counter() - t0) / n
        assert per_span < 2e-6
        assert calls == []
        assert tr.take() == []


# ----------------------------------------------------- registry wiring smoke

class TestRegistryWiring:
    def test_core_registry_owns_metrics(self):
        from repro.core.registry import Registry
        reg = Registry()
        assert isinstance(reg.metrics, MetricsRegistry)

    def test_server_adopts_registry_metrics(self):
        from repro.core.registry import Registry
        from repro.delivery import RegistryServer
        reg = Registry()
        srv = RegistryServer(reg)
        assert srv.metrics is reg.metrics
        assert srv.cache.metrics is reg.metrics
