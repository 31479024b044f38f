"""The pipeline's profiler spans, read back from a trace recorded on the CPU:
three micro-batches of a checkpointed pipeline whose keyed sink runs on a
retry lane and fails its first write, under ``jax.profiler``. Each
``TraceLog`` stage opens ``repro.<stage>`` around the interval it times,
``run_one_batch`` opens ``repro.batch`` and ``repro.pump``, and the lane's
worker thread opens ``repro.lane.write`` per attempt; all of them carry
the batch's index (``docs/observability.md``, "Profiler spans and RAAR
scopes")."""
import glob
import os
import threading
from dataclasses import dataclass, field

import jax
import pytest

from repro.core import Broker, NearRealTimePipeline, PipelineConfig
from repro.data import SinkPolicy, SyntheticRateSource

BATCHES = 3
MS = 1e-3


@dataclass
class Span:
    name: str
    thread: int                # the index of its line: one per thread
    start_ns: float
    end_ns: float
    args: dict = field(default_factory=dict)

    @property
    def seconds(self) -> float:
        return (self.end_ns - self.start_ns) * 1e-9

    def holds(self, other: "Span") -> bool:
        return self.start_ns <= other.start_ns and other.end_ns <= self.end_ns


class FlakySink:
    """Keyed sink whose first write raises; the lane retries it."""

    def __init__(self) -> None:
        self.calls = 0
        self.written: list = []
        self._lock = threading.Lock()

    def write_batch(self, items):
        with self._lock:
            self.calls += 1
            if self.calls == 1:
                raise RuntimeError("the first write fails")
            self.written.extend(items)


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    tmp = tmp_path_factory.mktemp("spans")
    sink = FlakySink()
    pipe = NearRealTimePipeline(
        Broker(),
        PipelineConfig(batch_interval=0.001, max_records_per_partition=4,
                       checkpoint_path=str(tmp / "ckpt.json")),
        lambda rdd, info, bridge: [(f"rec-{v:04d}", v)
                                   for v in rdd.collect()],
        sources=[SyntheticRateSource(rate=1e9, total=4 * BATCHES)],
        sinks=[(sink, SinkPolicy.retry(2))])
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp / "trace"), profiler_options=opts)
    try:
        pipe.run(max_batches=BATCHES)
        pipe.close()                  # drains the lane inside the trace
    finally:
        jax.profiler.stop_trace()
    [path] = glob.glob(os.path.join(tmp, "trace", "**", "*.xplane.pb"),
                       recursive=True)
    spans = []
    for plane in jax.profiler.ProfileData.from_file(path).planes:
        for thread, line in enumerate(plane.lines):
            for ev in line.events:
                if ev.name.startswith("repro."):
                    spans.append(Span(ev.name, thread, ev.start_ns,
                                      ev.start_ns + ev.duration_ns,
                                      dict(ev.stats)))
    return pipe.streaming.traces.last(), spans, sink


def _named(spans, name, batch_index=None):
    return [s for s in spans if s.name == name
            and (batch_index is None
                 or s.args.get("batch_index") == batch_index)]


def test_each_batch_has_one_span_with_its_index_and_size(traced):
    log, spans, _ = traced
    assert [s.batch_index for s in log] == list(range(BATCHES))
    for rec in log:
        [batch] = _named(spans, "repro.batch", rec.batch_index)
        assert batch.args["num_records"] == rec.num_records
        assert abs(batch.seconds - rec.total_s) < MS


def test_stage_spans_lie_inside_their_batch_and_match_its_seconds(traced):
    log, spans, _ = traced
    stages = set()
    for rec in log:
        [batch] = _named(spans, "repro.batch", rec.batch_index)
        for stage, seconds in rec.stages.items():
            stages.add(stage)
            if stage == "pump":       # timed before the batch exists
                pumps = [p for p in _named(spans, "repro.pump",
                                           rec.batch_index)
                         if p.end_ns <= batch.start_ns]
                assert pumps and batch.start_ns - pumps[-1].end_ns < 1e6
                assert abs(pumps[-1].seconds - seconds) < MS
                continue
            mine = _named(spans, f"repro.{stage}", rec.batch_index)
            assert mine and all(batch.holds(s) for s in mine), stage
            assert abs(sum(s.seconds for s in mine) - seconds) < MS, stage
    assert {"pump", "batch_fn", "sinks", "checkpoint", "broker_commit",
            "delivery_submit"} <= stages
    # no stage span strays outside a batch of its own index; the RDD
    # scheduler's job and task spans (the batch's collect) carry no index
    # and lie inside one batch
    batches = {s.args["batch_index"]: s for s in _named(spans, "repro.batch")}
    for s in spans:
        if s.name.startswith("repro.rdd."):
            assert any(b.holds(s) for b in batches.values()), s
        elif s.name not in ("repro.batch", "repro.pump", "repro.lane.write"):
            assert batches[s.args["batch_index"]].holds(s), s


def test_lane_writes_carry_their_batch_across_threads(traced):
    log, spans, sink = traced
    writes = _named(spans, "repro.lane.write")
    main = {s.thread for s in _named(spans, "repro.batch")}
    assert writes and {w.thread for w in writes}.isdisjoint(main)
    attempts = sorted((w.args["batch_index"], w.args["attempt"])
                      for w in writes)
    assert attempts == [(0, 0), (0, 1)] + [(i, 0)
                                           for i in range(1, BATCHES)]
    for w in writes:
        assert w.args["lane"] == "FlakySink"
        [submit] = _named(spans, "repro.delivery_submit",
                          w.args["batch_index"])
        assert w.start_ns >= submit.start_ns
        assert w.args["queued_ms"] >= 0
    assert len(sink.written) == 4 * BATCHES
