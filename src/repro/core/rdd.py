"""Resilient Distributed Datasets — the Spark middleware layer, in-process.

The paper leans on three RDD properties and we reproduce all of them:

1. **Partitioned, lazily-evaluated datasets** with narrow (map, filter, zip,
   union) and wide (repartition) dependencies — `RDD` below.
2. **Lineage-based fault tolerance**: a lost partition is *recomputed* from
   its parents instead of being replicated. Our scheduler retries failed
   tasks by replaying lineage (see `TaskScheduler`), and `test_fault.py`
   kills partitions mid-job to prove it.
3. **The driver–worker execution model**: a driver builds the DAG, a
   scheduler runs partition tasks on an executor pool. This is the *slow
   path* the paper benchmarks against (Table I): `collect()` funnels every
   partition back through the driver.

The fast path — running a tightly-coupled collective program *in place* over
the partitions — is `core/bridge.py`, the paper's actual contribution.

Executors are threads (this container is one host); the scheduler implements
the two production behaviours that matter at 1000-node scale regardless of
transport: bounded retries driven by lineage, and speculative re-execution of
stragglers.
"""
from __future__ import annotations

import itertools
import threading
import time
from concurrent.futures import FIRST_COMPLETED, Future, ThreadPoolExecutor, wait
from dataclasses import dataclass
from typing import Any, Callable, Iterable, Sequence

import numpy as np
from jax.profiler import TraceAnnotation

from repro.utils import get_logger

log = get_logger(__name__)

_rdd_ids = itertools.count()


class PartitionLostError(RuntimeError):
    """Raised by failure injection / executors when a partition's cached or
    computed data is lost; the scheduler recomputes from lineage."""


@dataclass(frozen=True)
class TaskAttempt:
    rdd_id: int
    partition: int
    attempt: int
    speculative: bool = False


class FailureInjector:
    """Deterministic fault injection for tests/benchmarks.

    ``fail_map[(rdd_id_offset_or_None, partition)] = n`` makes the first ``n``
    attempts of that partition raise ``PartitionLostError``. ``slow_map``
    makes attempts sleep (straggler simulation).
    """

    def __init__(self,
                 fail: dict[int, int] | None = None,
                 slow: dict[int, float] | None = None) -> None:
        self.fail = dict(fail or {})
        self.slow = dict(slow or {})
        self._lock = threading.Lock()
        self._attempts: dict[int, int] = {}

    def on_task(self, attempt: TaskAttempt) -> None:
        with self._lock:
            n = self._attempts.get(attempt.partition, 0)
            self._attempts[attempt.partition] = n + 1
        delay = self.slow.get(attempt.partition)
        if delay and not attempt.speculative:
            time.sleep(delay)
        if self.fail.get(attempt.partition, 0) > n:
            raise PartitionLostError(
                f"injected loss of partition {attempt.partition} "
                f"(attempt {attempt.attempt})")


class RDD:
    """An immutable, partitioned, lazily-evaluated dataset with lineage."""

    def __init__(self, context: "Context", num_partitions: int,
                 parents: Sequence["RDD"],
                 compute: Callable[[int], Any],
                 name: str = "rdd") -> None:
        self.context = context
        self.id = next(_rdd_ids)
        self.num_partitions = num_partitions
        self.parents = tuple(parents)
        self._compute = compute  # partition index -> partition data
        self.name = name
        self._cache: dict[int, Any] = {}
        self._cached = False

    # -- lineage ----------------------------------------------------------
    def compute_partition(self, idx: int) -> Any:
        """Compute partition ``idx`` from lineage (uses cache when present)."""
        if idx in self._cache:
            return self._cache[idx]
        data = self._compute(idx)
        if self._cached:
            self._cache[idx] = data
        return data

    def cache(self) -> "RDD":
        self._cached = True
        return self

    def unpersist_partition(self, idx: int) -> None:
        """Simulate loss of a cached partition (node crash)."""
        self._cache.pop(idx, None)

    def lineage(self) -> list["RDD"]:
        """Topologically-ordered ancestry (self last)."""
        seen: dict[int, RDD] = {}

        def visit(r: RDD) -> None:
            if r.id in seen:
                return
            for p in r.parents:
                visit(p)
            seen[r.id] = r

        visit(self)
        return list(seen.values())

    # -- narrow transformations ---------------------------------------------
    def map(self, fn: Callable[[Any], Any]) -> "RDD":
        def compute(idx: int, parent: "RDD" = self) -> Any:
            part = parent.compute_partition(idx)
            if isinstance(part, list):
                return [fn(x) for x in part]
            return fn(part)

        return RDD(self.context, self.num_partitions, [self], compute,
                   name=f"{self.name}.map")

    def map_partitions(self, fn: Callable[[Any], Any]) -> "RDD":
        def compute(idx: int, parent: "RDD" = self) -> Any:
            return fn(parent.compute_partition(idx))

        return RDD(self.context, self.num_partitions, [self], compute,
                   name=f"{self.name}.mapPartitions")

    def map_partitions_with_index(self, fn: Callable[[int, Any], Any]) -> "RDD":
        def compute(idx: int, parent: "RDD" = self) -> Any:
            return fn(idx, parent.compute_partition(idx))

        return RDD(self.context, self.num_partitions, [self], compute,
                   name=f"{self.name}.mapPartitionsWithIndex")

    def filter(self, pred: Callable[[Any], bool]) -> "RDD":
        def compute(idx: int, parent: "RDD" = self) -> Any:
            part = parent.compute_partition(idx)
            items = part if isinstance(part, list) else [part]
            return [x for x in items if pred(x)]

        return RDD(self.context, self.num_partitions, [self], compute,
                   name=f"{self.name}.filter")

    def zip_partitions(self, other: "RDD",
                       fn: Callable[[Any, Any], Any]) -> "RDD":
        if other.num_partitions != self.num_partitions:
            raise ValueError("zip requires equal partition counts")

        def compute(idx: int, a: "RDD" = self, b: "RDD" = other) -> Any:
            return fn(a.compute_partition(idx), b.compute_partition(idx))

        return RDD(self.context, self.num_partitions, [self, other], compute,
                   name=f"{self.name}.zip")

    def union(self, *others: "RDD") -> "RDD":
        """Paper Fig. 8: per-topic RDDs combined with a union before the MPI
        job — partitions are concatenated, lineage fans in."""
        rdds = (self,) + others
        offsets = np.cumsum([0] + [r.num_partitions for r in rdds])

        def compute(idx: int, rdds: tuple = rdds, offsets=offsets) -> Any:
            src = int(np.searchsorted(offsets, idx, side="right") - 1)
            return rdds[src].compute_partition(idx - int(offsets[src]))

        return RDD(self.context, int(offsets[-1]), list(rdds), compute,
                   name=f"{self.name}.union")

    # -- wide transformation ------------------------------------------------
    def repartition(self, num_partitions: int) -> "RDD":
        """Wide dependency: every output partition reads all input partitions
        (the tomography pipeline repartitions so neighbouring slices land in
        the same partition)."""
        def compute(idx: int, parent: "RDD" = self, n: int = num_partitions) -> Any:
            items: list[Any] = []
            for p in range(parent.num_partitions):
                part = parent.compute_partition(p)
                items.extend(part if isinstance(part, list) else [part])
            return items[idx::n] if n > 0 else items

        return RDD(self.context, num_partitions, [self], compute,
                   name=f"{self.name}.repartition")

    # -- actions ------------------------------------------------------------
    def collect(self) -> list[Any]:
        """Driver-side gather of every partition (the Table-I slow path)."""
        parts = self.context.scheduler.run(self)
        out: list[Any] = []
        for part in parts:
            out.extend(part if isinstance(part, list) else [part])
        return out

    def collect_partitions(self) -> list[Any]:
        return self.context.scheduler.run(self)

    def count(self) -> int:
        return len(self.collect())

    def reduce(self, fn: Callable[[Any, Any], Any]) -> Any:
        items = self.collect()
        if not items:
            raise ValueError("reduce of empty RDD")
        acc = items[0]
        for x in items[1:]:
            acc = fn(acc, x)
        return acc

    def take(self, n: int) -> list[Any]:
        return self.collect()[:n]


class TaskScheduler:
    """Runs partition tasks with lineage-driven retries + speculation.

    * Retry: a task failing with any exception is re-run up to
      ``max_failures`` times; because RDDs are lazy + deterministic, the
      re-run *is* the lineage recompute.
    * Straggler mitigation: when a task runs longer than
      ``speculation_multiplier`` × median of completed tasks (and at least
      ``speculation_quantile`` of tasks finished), a speculative copy is
      launched; first result wins — Spark's speculative execution.
    """

    def __init__(self, num_executors: int = 4, max_failures: int = 4,
                 speculation: bool = True, speculation_multiplier: float = 4.0,
                 speculation_quantile: float = 0.5,
                 failure_injector: FailureInjector | None = None) -> None:
        self.num_executors = num_executors
        self.max_failures = max_failures
        self.speculation = speculation
        self.speculation_multiplier = speculation_multiplier
        self.speculation_quantile = speculation_quantile
        self.failure_injector = failure_injector
        self.metrics = {"tasks": 0, "retries": 0, "speculative": 0,
                        "speculative_wins": 0}

    def _run_task(self, rdd: RDD, attempt: TaskAttempt) -> Any:
        with TraceAnnotation("repro.rdd.task", rdd=attempt.rdd_id,
                             partition=attempt.partition,
                             attempt=attempt.attempt,
                             speculative=attempt.speculative):
            self.metrics["tasks"] += 1
            if self.failure_injector is not None:
                self.failure_injector.on_task(attempt)
            return rdd.compute_partition(attempt.partition)

    def run(self, rdd: RDD) -> list[Any]:
        """Every partition of ``rdd``, in order, under one profiler span
        ``repro.rdd.job``; each task attempt opens ``repro.rdd.task`` on the
        executor thread that runs it."""
        with TraceAnnotation("repro.rdd.job", rdd=rdd.id,
                             partitions=rdd.num_partitions):
            return self._run(rdd)

    def _run(self, rdd: RDD) -> list[Any]:
        n = rdd.num_partitions
        results: dict[int, Any] = {}
        attempts: dict[int, int] = {p: 0 for p in range(n)}
        durations: list[float] = []

        pool = ThreadPoolExecutor(max_workers=self.num_executors)
        try:
            running: dict[Future, tuple[TaskAttempt, float]] = {}

            def launch(p: int, speculative: bool = False) -> None:
                att = TaskAttempt(rdd.id, p, attempts[p], speculative)
                attempts[p] += 1
                fut = pool.submit(self._run_task, rdd, att)
                running[fut] = (att, time.monotonic())
                if speculative:
                    self.metrics["speculative"] += 1

            for p in range(n):
                launch(p)

            while len(results) < n:
                done, _ = wait(list(running), timeout=0.05,
                               return_when=FIRST_COMPLETED)
                now = time.monotonic()
                for fut in done:
                    att, t0 = running.pop(fut)
                    if att.partition in results:
                        continue  # a twin already finished
                    try:
                        results[att.partition] = fut.result()
                        durations.append(now - t0)
                        if att.speculative:
                            self.metrics["speculative_wins"] += 1
                    except Exception as exc:  # lineage recompute path
                        if attempts[att.partition] > self.max_failures:
                            raise RuntimeError(
                                f"partition {att.partition} of {rdd.name} failed "
                                f"{attempts[att.partition]} times") from exc
                        self.metrics["retries"] += 1
                        log.debug("retrying partition %d of %s: %s",
                                  att.partition, rdd.name, exc)
                        launch(att.partition)
                # speculative re-execution of stragglers
                if (self.speculation and durations
                        and len(durations) >= self.speculation_quantile * n):
                    median = float(np.median(durations))
                    threshold = max(self.speculation_multiplier * median, 0.05)
                    live = {a.partition for a, _ in running.values()}
                    for fut, (att, t0) in list(running.items()):
                        p = att.partition
                        if (p not in results and now - t0 > threshold
                                and sum(1 for a, _ in running.values()
                                        if a.partition == p) == 1):
                            launch(p, speculative=True)
        finally:
            # abandoned straggler twins must not block job completion
            pool.shutdown(wait=False, cancel_futures=True)
        return [results[p] for p in range(n)]


class Context:
    """The SparkContext analogue: owns the scheduler, builds source RDDs."""

    def __init__(self, num_executors: int = 4,
                 scheduler: TaskScheduler | None = None) -> None:
        self.scheduler = scheduler or TaskScheduler(num_executors=num_executors)

    def parallelize(self, data: Iterable[Any], num_partitions: int) -> RDD:
        items = list(data)
        if num_partitions <= 0:
            raise ValueError("num_partitions must be positive")
        # Spark-style contiguous slicing.
        bounds = np.linspace(0, len(items), num_partitions + 1).astype(int)

        def compute(idx: int) -> list[Any]:
            return items[bounds[idx]:bounds[idx + 1]]

        return RDD(self, num_partitions, [], compute, name="parallelize")

    def from_partitions(self, partitions: Sequence[Any]) -> RDD:
        parts = list(partitions)

        def compute(idx: int) -> Any:
            return parts[idx]

        return RDD(self, len(parts), [], compute, name="fromPartitions")

    def union(self, rdds: Sequence[RDD]) -> RDD:
        first, *rest = rdds
        return first.union(*rest)
