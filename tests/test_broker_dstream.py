"""Broker (Kafka semantics) + discretized streams."""
import json
import os

import numpy as np
import pytest

try:
    from hypothesis import given, settings, strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:          # container has no hypothesis; smoke path below
    HAVE_HYPOTHESIS = False

from repro.core import (Broker, Context, OffsetRange, StreamingContext,
                        StreamProgress, create_rdd)


def test_partition_order_and_offsets():
    b = Broker()
    b.create_topic("t", 2)
    for i in range(10):
        b.produce("t", i, partition=i % 2)
    recs = b.read(OffsetRange("t", 0, 0, 5))
    assert [r.value for r in recs] == [0, 2, 4, 6, 8]
    assert [r.offset for r in recs] == list(range(5))
    assert b.end_offsets("t") == [5, 5]


def test_offset_range_reads_are_replayable():
    b = Broker()
    b.create_topic("t", 1)
    for i in range(8):
        b.produce("t", i)
    ctx = Context()
    r1 = create_rdd(ctx, b, [OffsetRange("t", 0, 2, 6)])
    r2 = create_rdd(ctx, b, [OffsetRange("t", 0, 2, 6)])
    assert r1.collect() == r2.collect() == [2, 3, 4, 5]


def _check_per_partition_total_order(partition_choices):
    """However producers interleave, each partition's log preserves produce
    order (Kafka's ordering contract: total per-partition, none across)."""
    b = Broker()
    b.create_topic("t", 4)
    expect: dict[int, list[int]] = {p: [] for p in range(4)}
    for i, p in enumerate(partition_choices):
        b.produce("t", i, partition=p)
        expect[p].append(i)
    for p in range(4):
        got = [r.value for r in b.read(OffsetRange("t", p, 0, 10 ** 6))]
        assert got == expect[p]


def test_per_partition_total_order_smoke():
    """Deterministic replicas of the hypothesis property (runs everywhere)."""
    rng = np.random.default_rng(7)
    for n in (1, 5, 80):
        _check_per_partition_total_order(rng.integers(0, 4, n).tolist())


if HAVE_HYPOTHESIS:
    @given(st.lists(st.integers(0, 3), min_size=1, max_size=80))
    @settings(max_examples=25, deadline=None)
    def test_property_per_partition_total_order(partition_choices):
        _check_per_partition_total_order(partition_choices)


def test_microbatch_union_across_topics():
    b = Broker()
    b.create_topic("a", 1)
    b.create_topic("b", 2)
    for i in range(6):
        b.produce("a", ("a", i))
        b.produce("b", ("b", i), partition=i % 2)
    ctx = Context()
    sc = StreamingContext(ctx, b)
    sc.subscribe(["a", "b"])
    seen = []
    sc.foreach_batch(lambda rdd, info: seen.extend(rdd.collect()))
    info = sc.run_one_batch()
    assert info.num_records == 12
    assert sorted(x[1] for x in seen if x[0] == "a") == list(range(6))
    assert sc.run_one_batch() is None      # drained


def test_offset_checkpoint_resume(tmp_path):
    """Restarted stream resumes exactly after the last committed batch."""
    b = Broker()
    b.create_topic("t", 1)
    for i in range(10):
        b.produce("t", i)
    path = str(tmp_path / "progress.json")
    ctx = Context()
    sc = StreamingContext(ctx, b, max_records_per_partition=4,
                          checkpoint_path=path)
    sc.subscribe(["t"])
    got = []
    sc.foreach_batch(lambda rdd, info: got.extend(rdd.collect()))
    sc.run_one_batch()
    assert got == [0, 1, 2, 3]
    # "crash" -> new context from the same checkpoint
    sc2 = StreamingContext(ctx, b, max_records_per_partition=4,
                           checkpoint_path=path)
    sc2.subscribe(["t"])
    got2 = []
    sc2.foreach_batch(lambda rdd, info: got2.extend(rdd.collect()))
    sc2.run_one_batch()
    sc2.run_one_batch()
    assert got2 == [4, 5, 6, 7, 8, 9]


def test_failed_batch_does_not_commit(tmp_path):
    b = Broker()
    b.create_topic("t", 1)
    for i in range(4):
        b.produce("t", i)
    ctx = Context()
    sc = StreamingContext(ctx, b, checkpoint_path=str(tmp_path / "p.json"))
    sc.subscribe(["t"])
    calls = {"n": 0}

    def flaky(rdd, info):
        calls["n"] += 1
        if calls["n"] == 1:
            raise RuntimeError("transient sink failure")
        return rdd.collect()

    sc.foreach_batch(flaky)
    with pytest.raises(RuntimeError):
        sc.run_one_batch()
    info = sc.run_one_batch()              # replays the same records
    assert info.result == [0, 1, 2, 3]     # at-least-once delivery


def test_pump_round_robin_persists_across_batches():
    """The produce cursor must survive the batch loop: resetting it every
    pump landed *every* record on partition 0 whenever a poll returned fewer
    records than the topic has partitions (e.g. poll_batch=1, 4 partitions).
    """
    from repro.data import SyntheticRateSource

    b = Broker()
    sc = StreamingContext(Context(), b)
    sc.subscribe_source(SyntheticRateSource(rate=1e9, total=16), topic="t",
                        partitions=4, poll_batch=1)
    sc.foreach_batch(lambda rdd, info: rdd.count())
    while not (sc.sources_exhausted and sc.lag("t") == 0):
        sc.run_one_batch()
    assert b.end_offsets("t") == [4, 4, 4, 4]   # near-even, not all-on-p0


def test_checkpoint_from_fewer_partitions_consumes_new_ones(tmp_path):
    """A checkpoint written when the topic had 2 partitions, replayed
    against a 4-partition topic: the padded offsets must consume the new
    partitions from 0 instead of silently never reading them."""
    path = str(tmp_path / "progress.json")
    b2 = Broker()
    b2.create_topic("t", 2)
    for i in range(6):
        b2.produce("t", i, partition=i % 2)
    sc = StreamingContext(Context(), b2, checkpoint_path=path)
    sc.subscribe(["t"])
    sc.foreach_batch(lambda rdd, info: rdd.count())
    sc.run_one_batch()
    assert StreamProgress.load(path).offsets["t"] == [3, 3]

    b4 = Broker()                          # the repartitioned topic
    b4.create_topic("t", 4)
    for i in range(12):
        b4.produce("t", i, partition=i % 4)
    sc2 = StreamingContext(Context(), b4, checkpoint_path=path)
    sc2.subscribe(["t"])
    seen = []
    sc2.foreach_batch(lambda rdd, info: seen.extend(rdd.collect()))
    info = sc2.run_one_batch()
    # partitions 0/1 resume at 3; partitions 2/3 are consumed from 0
    assert [(r.partition, r.start, r.until) for r in info.ranges] == \
        [(2, 0, 3), (3, 0, 3)]
    assert sorted(seen) == [2, 3, 6, 7, 10, 11]
    assert StreamProgress.load(path).offsets["t"] == [3, 3, 3, 3]


def test_partition_growth_between_batches_is_picked_up():
    """Padding re-runs every batch, so partitions added after subscribe are
    consumed too (not only ones present at subscribe time)."""
    b = Broker()
    b.create_topic("t", 1)
    b.produce("t", 0)
    sc = StreamingContext(Context(), b)
    sc.subscribe(["t"])
    seen = []
    sc.foreach_batch(lambda rdd, info: seen.extend(rdd.collect()))
    sc.run_one_batch()
    b._topics["t"].append(type(b._topics["t"][0])())   # grow the topic
    for done in b._committed["t"].values():            # pad every group
        done.append(0)
    b.produce("t", 1, partition=1)
    sc.run_one_batch()
    assert sorted(seen) == [0, 1]


def test_serial_sink_runs_before_commit(tmp_path):
    """A crash between commit and sink delivery used to lose the batch from
    every serial sink. Sinks now run before the commit: a raising sink
    leaves offsets and checkpoint untouched and the batch replays."""
    path = str(tmp_path / "p.json")
    b = Broker()
    b.create_topic("t", 1)
    for i in range(4):
        b.produce("t", i)
    sc = StreamingContext(Context(), b, checkpoint_path=path)
    sc.subscribe(["t"])
    sc.foreach_batch(lambda rdd, info: rdd.collect())
    events = []
    sc.add_sink(lambda info: events.append(("sink", list(info.result))))

    armed = {"boom": True}

    def exploding(info):
        events.append(("boom", list(info.result)))
        if armed.pop("boom", False):
            raise RuntimeError("sink died")

    sc.add_sink(exploding)
    with pytest.raises(RuntimeError):
        sc.run_one_batch()
    # nothing committed anywhere: memory, checkpoint file, broker-side
    assert sc.committed("t") == 0
    assert StreamProgress.load(path).offsets == {}
    assert b.committed("t") == [0]
    assert sc.history == []                # the batch did not count
    info = sc.run_one_batch()              # replay delivers to every sink
    assert info.result == [0, 1, 2, 3]
    assert events == [("sink", [0, 1, 2, 3]), ("boom", [0, 1, 2, 3]),
                      ("sink", [0, 1, 2, 3]), ("boom", [0, 1, 2, 3])]


def test_corrupt_checkpoint_degrades_to_empty(tmp_path, caplog):
    """A torn or garbage checkpoint must not make the restart unrecoverable:
    load falls back to empty progress (replay from 0) with a warning."""
    path = str(tmp_path / "p.json")
    full = StreamProgress(offsets={"t": [5]}, epoch=3)
    full.save(path)
    blob = open(path, "rb").read()
    cases = {
        "truncated": blob[:len(blob) // 2],
        "garbage": b"\x00\xffnot json at all",
        "wrong-shape": b'{"offsets": 42}',
        "missing-key": b'{"epoch": 1}',
    }
    for name, payload in cases.items():
        with open(path, "wb") as f:
            f.write(payload)
        got = StreamProgress.load(path)
        assert got.offsets == {} and got.epoch == 0, name
    # and the stream actually restarts from offset 0
    b = Broker()
    b.create_topic("t", 1)
    for i in range(3):
        b.produce("t", i)
    sc = StreamingContext(Context(), b, checkpoint_path=path)
    sc.subscribe(["t"])
    seen = []
    sc.foreach_batch(lambda rdd, info: seen.extend(rdd.collect()))
    sc.run_one_batch()
    assert seen == [0, 1, 2]


def test_old_format_checkpoint_still_loads(tmp_path):
    path = str(tmp_path / "p.json")
    with open(path, "w") as f:
        json.dump({"offsets": {"t": [7]}}, f)   # pre-epoch format
    got = StreamProgress.load(path)
    assert got.offsets == {"t": [7]} and got.epoch == 0
    assert got.window_refs == {}


def test_realtime_report():
    b = Broker()
    b.create_topic("t", 1)
    for i in range(20):
        b.produce("t", i)
    ctx = Context()
    sc = StreamingContext(ctx, b, batch_interval=5.0,
                          max_records_per_partition=5)
    sc.subscribe(["t"])
    sc.foreach_batch(lambda rdd, info: rdd.count())
    sc.run_batches(4)
    rep = sc.realtime_report()
    assert rep["batches"] == 4 and rep["records"] == 20
    assert rep["keeps_up"] is True


def test_microbatch_allreduce_per_batch():
    """Paper Figs. 7-8: each micro-batch of a two-topic union becomes one
    collective job. The batch's sum, allreduced over the bridge's ranks, is
    world times that sum, and every record is seen once."""
    from repro.core import MPIBridge

    b = Broker()
    for t in range(2):
        b.create_topic(f"topic-{t}", 1)
    for i in range(40):
        b.produce(f"topic-{i % 2}", np.float32(i))
    ctx = Context()
    bridge = MPIBridge()
    sc = StreamingContext(ctx, b, max_records_per_partition=5)
    sc.subscribe(["topic-0", "topic-1"])
    sums = []

    def on_batch(rdd, info):
        total = np.float32(np.sum(rdd.collect()))
        part = ctx.from_partitions([np.full(16, total, np.float32)]
                                   * bridge.world)
        reduced = np.asarray(bridge.allreduce(part))
        np.testing.assert_allclose(reduced, bridge.world * total)
        sums.append(total)

    sc.foreach_batch(on_batch)
    sc.run_batches(4)
    assert len(sums) == 4
    assert float(np.sum(sums)) == float(sum(range(40)))
