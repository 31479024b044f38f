"""Metrics registry + trace-span unit tests: instrument semantics
(get-or-create identity, counter monotonicity, callback gauges, histogram
buckets), the two serialization surfaces (Prometheus text, JSON snapshot),
the ring-buffer time series, the NullRegistry off switch, and the
TraceLog/SpanRecorder batch-span machinery documented in
docs/observability.md.
"""
import math
import threading

import pytest

from repro.data.metrics import (COUNT_BUCKETS, DEFAULT_BUCKETS, BatchSpan,
                                Counter, Gauge, Histogram, MetricsRegistry,
                                NullRegistry, SPAN_STAGES, TraceLog, disabled,
                                get_registry, set_registry)


# -- registry identity --------------------------------------------------------

def test_get_or_create_returns_same_instrument():
    reg = MetricsRegistry()
    a = reg.counter("hits_total", "help once")
    b = reg.counter("hits_total", "ignored on re-register")
    assert a is b
    a.inc(3)
    assert b.value() == 3


def test_identity_is_name_plus_labels_order_insensitive():
    reg = MetricsRegistry()
    a = reg.counter("c", labels={"topic": "t", "part": "0"})
    b = reg.counter("c", labels={"part": "0", "topic": "t"})
    c = reg.counter("c", labels={"topic": "other"})
    assert a is b
    assert c is not a
    assert len(reg.metrics()) == 2


def test_kind_mismatch_is_an_error():
    reg = MetricsRegistry()
    reg.counter("x")
    with pytest.raises(ValueError, match="already registered as counter"):
        reg.gauge("x")


# -- instruments --------------------------------------------------------------

def test_counter_monotonic():
    c = MetricsRegistry().counter("n_total")
    c.inc()
    c.inc(4)
    assert c.value() == 5
    with pytest.raises(ValueError):
        c.inc(-1)


def test_gauge_set_inc_dec():
    g = MetricsRegistry().gauge("depth")
    g.set(10)
    g.inc(2)
    g.dec(5)
    assert g.value() == 7


def test_callback_gauge_reads_live_and_latest_wins():
    reg = MetricsRegistry()
    box = {"v": 1}
    g = reg.gauge("live", callback=lambda: box["v"])
    box["v"] = 42
    assert g.value() == 42
    # a rebuilt component re-registers: its callback replaces the old one
    g2 = reg.gauge("live", callback=lambda: 7)
    assert g2 is g
    assert g.value() == 7


def test_dead_callback_gauge_is_nan_not_a_crash():
    g = MetricsRegistry().gauge(
        "dead", callback=lambda: (_ for _ in ()).throw(RuntimeError("gone")))
    assert math.isnan(g.value())
    # and serializes as null, never NaN, in the JSON snapshot
    reg = MetricsRegistry()
    reg.gauge("dead", callback=lambda: 1 / 0)
    (entry,) = reg.snapshot()["metrics"]
    assert entry["value"] is None


def test_histogram_buckets_sum_count():
    h = MetricsRegistry().histogram("lat_seconds", buckets=(0.01, 0.1, 1.0))
    for v in (0.005, 0.05, 0.05, 0.5, 99.0):
        h.observe(v)
    snap = h.snapshot()
    assert snap["buckets"] == [0.01, 0.1, 1.0]
    assert snap["counts"] == [1, 3, 4, 5]      # cumulative, last is +Inf
    assert snap["count"] == 5
    assert snap["sum"] == pytest.approx(99.605)
    assert h.value() == 5                      # scalar view = observations


def test_histogram_timer_context():
    h = MetricsRegistry().histogram("t_seconds")
    with h.time():
        pass
    snap = h.snapshot()
    assert snap["count"] == 1
    assert 0 <= snap["sum"] < 1.0


def test_count_buckets_cover_flush_sizes():
    h = MetricsRegistry().histogram("flush", buckets=COUNT_BUCKETS)
    h.observe(64)
    snap = h.snapshot()
    i = snap["buckets"].index(64)
    assert snap["counts"][i] == 1
    assert snap["counts"][i - 1] == 0


def test_counter_thread_safety():
    c = MetricsRegistry().counter("n")
    threads = [threading.Thread(target=lambda: [c.inc() for _ in range(1000)])
               for _ in range(8)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert c.value() == 8000


# -- serialization ------------------------------------------------------------

def test_prometheus_text_format():
    reg = MetricsRegistry(namespace="repro")
    reg.counter("reads_total", "records read",
                labels={"topic": "t"}).inc(3)
    reg.gauge("depth").set(2)
    reg.histogram("lat_seconds", buckets=(0.1, 1.0)).observe(0.05)
    text = reg.prometheus_text()
    assert "# HELP repro_reads_total records read" in text
    assert "# TYPE repro_reads_total counter" in text
    assert 'repro_reads_total{topic="t"} 3' in text
    assert "repro_depth 2" in text
    assert 'repro_lat_seconds_bucket{le="0.1"} 1' in text
    assert 'repro_lat_seconds_bucket{le="+Inf"} 1' in text
    assert "repro_lat_seconds_sum 0.05" in text
    assert "repro_lat_seconds_count 1" in text


def test_snapshot_shape():
    reg = MetricsRegistry()
    reg.counter("c", "h", labels={"a": "b"}).inc()
    reg.sample(now=1.0)
    snap = reg.snapshot()
    assert set(snap) == {"sampled_at", "metrics"}
    (m,) = snap["metrics"]
    assert m["name"] == "c" and m["kind"] == "counter"
    assert m["labels"] == {"a": "b"} and m["value"] == 1
    assert m["series"] == [(1.0, 1)]


def test_ring_buffer_series_is_bounded():
    reg = MetricsRegistry(ring_size=4)
    c = reg.counter("c")
    for i in range(10):
        c.inc()
        reg.sample(now=float(i))
    pts = c.series_points()
    assert len(pts) == 4                       # bounded by ring_size
    assert [t for t, _ in pts] == [6.0, 7.0, 8.0, 9.0]
    assert [v for _, v in pts] == [7, 8, 9, 10]


# -- the off switch -----------------------------------------------------------

def test_null_registry_absorbs_everything():
    reg = NullRegistry()
    c = reg.counter("c")
    c.inc()
    reg.gauge("g").set(5)
    h = reg.histogram("h")
    h.observe(1.0)
    with h.time():
        pass
    assert reg.metrics() == []
    assert reg.snapshot()["metrics"] == []
    assert reg.prometheus_text() == "\n"


def test_set_registry_returns_previous_and_disabled_restores():
    base = get_registry()
    mine = MetricsRegistry()
    prev = set_registry(mine)
    try:
        assert prev is base
        assert get_registry() is mine
        with disabled() as null:
            assert isinstance(null, NullRegistry)
            assert get_registry() is null
        assert get_registry() is mine          # restored on exit
    finally:
        set_registry(prev)
    assert get_registry() is base


@pytest.mark.parametrize("registry_on", [True, False])
def test_ingest_drains_every_record_with_registry_on_and_off(registry_on):
    """The instrumented ingest path and the same path built under
    disabled() deliver the same records: telemetry observes, never alters."""
    from repro.core import Broker, Context, StreamingContext
    from repro.data import IngestConfig, IngestRunner, SyntheticRateSource

    def drain() -> list:
        broker = Broker()
        sc = StreamingContext(Context(), broker, max_records_per_partition=20)
        runner = IngestRunner(broker, consumer=sc)
        runner.add(SyntheticRateSource(rate=1e9, total=400),
                   IngestConfig(topic="t", partitions=2, poll_batch=40))
        sc.subscribe(["t"])
        got: list = []
        sc.foreach_batch(lambda rdd, info: got.extend(rdd.collect()))
        while not runner.done or sc.lag("t") > 0:
            runner.pump()
            sc.run_one_batch()
        assert sum(b.num_records for b in sc.history) == 400
        return got

    if registry_on:
        reg = MetricsRegistry()
        prev = set_registry(reg)
        try:
            got = drain()
        finally:
            set_registry(prev)
        produced = reg.counter("ingest_produced_records_total",
                               labels={"topic": "t"})
        assert produced.value() == 400
    else:
        with disabled():
            got = drain()
    assert sorted(got) == list(range(400))


# -- trace spans --------------------------------------------------------------

def test_span_stages_cover_the_documented_pipeline_order():
    assert SPAN_STAGES == ("pump", "batch_fn", "sinks", "state_commit",
                           "checkpoint", "broker_commit", "delivery_submit")


def test_span_recorder_builds_and_records_a_span():
    log = TraceLog()
    rec = log.begin(batch_index=3, num_records=17)
    rec.add("pump", 0.25)
    rec.add("pump", 0.25)                      # accumulates
    with rec.stage("batch_fn"):
        pass
    with rec.stage("batch_fn"):                # re-entry accumulates too
        pass
    span = rec.finish(epoch=9)
    assert span.batch_index == 3 and span.num_records == 17
    assert span.epoch == 9
    assert span.stages["pump"] == pytest.approx(0.5)
    assert span.stages["batch_fn"] >= 0
    assert span.total_s >= 0
    assert log.last() == [span]
    assert log.recorded == 1
    d = span.as_dict()
    assert set(d) == {"batch_index", "epoch", "num_records", "started_at",
                      "total_s", "stages"}


def test_trace_log_capacity_and_last_n():
    log = TraceLog(capacity=3)
    for i in range(5):
        log.begin(i, 1).finish(epoch=i + 1)
    spans = log.last()
    assert [s.batch_index for s in spans] == [2, 3, 4]   # oldest evicted
    assert log.recorded == 5                             # total, not retained
    assert [s.batch_index for s in log.last(2)] == [3, 4]
    assert log.last(0) == []


def test_stage_totals_roll_up_across_spans():
    log = TraceLog()
    for i in range(3):
        rec = log.begin(i, 1)
        rec.add("batch_fn", 0.1)
        rec.add("sinks", 0.01)
        rec.finish(epoch=i + 1)
    totals = log.stage_totals()
    assert totals["batch_fn"] == pytest.approx(0.3)
    assert totals["sinks"] == pytest.approx(0.03)


def test_unfinished_span_is_not_recorded():
    log = TraceLog()
    rec = log.begin(0, 4)
    rec.add("pump", 0.1)                       # abandoned: batch failed
    assert log.last() == []
    assert log.recorded == 0
    assert isinstance(rec.span, BatchSpan)


def test_default_buckets_are_sorted_and_nonempty():
    assert list(DEFAULT_BUCKETS) == sorted(DEFAULT_BUCKETS)
    assert DEFAULT_BUCKETS and COUNT_BUCKETS
    with pytest.raises(ValueError):
        Histogram("h", "", (), 8, buckets=())
