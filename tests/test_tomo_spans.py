"""The RDD scheduler's profiler spans, read back from a trace recorded on the
CPU: three micro-batches of streaming tomography through one
``SliceReconstructor``, and one job whose straggling partition gets a
speculative copy, under ``jax.profiler``. ``TaskScheduler.run`` opens
``repro.rdd.job`` around a job, and each task attempt opens
``repro.rdd.task`` on the executor thread that runs it
(``docs/observability.md``, "Profiler spans and RAAR scopes")."""
import glob
import os
from dataclasses import dataclass, field

import jax
import numpy as np
import pytest

from repro.apps.tomo.solver import (SliceReconstructor, TomoConfig,
                                    reconstruct_batch, simulate_tilt_series)
from repro.core import Broker, Context, NearRealTimePipeline, PipelineConfig
from repro.core.rdd import FailureInjector, TaskScheduler
from repro.data import NpzDirectorySink, ProjectionSource
from repro.data.metrics import MetricsRegistry, set_registry

BATCHES, BATCH, PARTITIONS = 3, 4, 2
STRAGGLE_S = 1.0


@dataclass
class Span:
    name: str
    thread: int                # the index of its line: one per thread
    start_ns: float
    end_ns: float
    args: dict = field(default_factory=dict)

    def holds(self, other: "Span") -> bool:
        return self.start_ns <= other.start_ns and other.end_ns <= self.end_ns


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    tmp = tmp_path_factory.mktemp("rdd_spans")
    cfg = TomoConfig(nray=16, angles=tuple(np.linspace(-75, 75, 5).tolist()))
    _, sino = simulate_tilt_series(cfg, BATCHES * BATCH)
    registry = MetricsRegistry()
    previous = set_registry(registry)
    operator = SliceReconstructor(cfg)
    ctx = Context(scheduler=TaskScheduler(num_executors=PARTITIONS))
    pipe = NearRealTimePipeline(
        Broker(), PipelineConfig(batch_interval=0.001,
                                 max_records_per_partition=BATCH),
        lambda rdd, info, bridge: reconstruct_batch(rdd, operator,
                                                    PARTITIONS),
        context=ctx, sinks=[NpzDirectorySink(str(tmp / "sink"))])
    pipe.subscribe_source(ProjectionSource(sino), topic="tilt-series")
    # partition 0 of this job sleeps unless it is the speculative copy
    straggler = TaskScheduler(
        num_executors=2, failure_injector=FailureInjector(
            slow={0: STRAGGLE_S}))
    slow_job = Context(scheduler=straggler).parallelize(range(4), 2)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp / "trace"), profiler_options=opts)
    try:
        pipe.run(max_batches=BATCHES)
        slow_job.collect()
    finally:
        jax.profiler.stop_trace()
        pipe.close()
        set_registry(previous)
    [path] = glob.glob(os.path.join(tmp, "trace", "**", "*.xplane.pb"),
                       recursive=True)
    spans = []
    for plane in jax.profiler.ProfileData.from_file(path).planes:
        for thread, line in enumerate(plane.lines):
            for ev in line.events:
                if ev.name.startswith("repro.rdd."):
                    spans.append(Span(ev.name, thread, ev.start_ns,
                                      ev.start_ns + ev.duration_ns,
                                      dict(ev.stats)))
    counters = {m.name: m.value() for m in registry.metrics()}
    return spans, operator, counters, slow_job.id, straggler


def _named(spans, name, **args):
    return [s for s in spans if s.name == name
            and all(s.args.get(k) == v for k, v in args.items())]


def test_each_job_holds_its_tasks_on_their_threads(traced):
    spans, _, _, slow_rdd, _ = traced
    jobs = [j for j in _named(spans, "repro.rdd.job")
            if j.args["rdd"] != slow_rdd]
    # per batch: the collect of the batch's records (one topic partition)
    # and the operator mapped over PARTITIONS partitions
    assert sorted(j.args["partitions"] for j in jobs) == (
        [1] * BATCHES + [PARTITIONS] * BATCHES)
    for job in jobs:
        tasks = _named(spans, "repro.rdd.task", rdd=job.args["rdd"])
        assert sorted(t.args["partition"] for t in tasks) == list(
            range(job.args["partitions"]))
        assert all(job.holds(t) and t.thread != job.thread for t in tasks)
        assert all(t.args["attempt"] == 0 and not t.args["speculative"]
                   for t in tasks)


def test_a_straggler_gets_a_speculative_attempt(traced):
    spans, _, _, slow_rdd, straggler = traced
    [job] = _named(spans, "repro.rdd.job", rdd=slow_rdd)
    tasks = _named(spans, "repro.rdd.task", rdd=slow_rdd)
    [copy] = [t for t in tasks if t.args["speculative"]]
    assert copy.args["partition"] == 0 and copy.args["attempt"] == 1
    assert job.holds(copy)
    # the copy won: the job ended before the straggler woke
    assert (job.end_ns - job.start_ns) * 1e-9 < STRAGGLE_S
    assert straggler.metrics["speculative"] == 1


def test_the_system_matrix_is_placed_once_for_three_batches(traced):
    _, operator, counters, _, _ = traced
    assert operator.placements == 1
    assert counters["tomo_system_placements_total"] == 1
    assert counters["tomo_system_bytes_total"] == operator.A.nbytes
