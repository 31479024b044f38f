"""Streaming tomography (paper §IV), wired as ``examples/tomo_pipeline.py``
wires it, driven micro-batch after micro-batch.

Traffic: an unpaced TEM stage (a ``ProjectionSource``) streams the tilt
series of ``volumes`` phantoms, each ``slices_per_volume`` sinogram rows,
back to back and without end into ``NearRealTimePipeline``,
``batch_slices`` slices per micro-batch. Slice ``g`` of the stream is slice
``g % slices_per_volume`` of volume ``g // slices_per_volume``, which shows
phantom ``volume % volumes``. The per-batch function is the program's
``reconstruct_batch``: the batch's records in slice order, ``parallelize``d
into ``partitions`` of neighbouring slices, the ``SliceReconstructor`` (the
dense system matrix resident on the device, the ART sweep compiled) mapped
over them by the RDD scheduler with the example's settings, and one keyed
sub-volume per partition into the NPZ sink on a retry lane. The next batch
is polled once the last batch's sub-volumes are in the sink: a closed loop.

Set-up makes every input from the seed: the phantoms on the device, their
tilt series through the reference's own float64 system matrix (rounded to
float32 on the device and freed), the operator, and one warm-up batch,
which compiles the one program the window runs.
"""
from __future__ import annotations

import time
from concurrent.futures import ThreadPoolExecutor
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from chipbench import roofline_art
from chipbench.art_trace import KERNEL
from chipbench.apps import tomo_ref
from chipbench.harness import Check
from chipbench.stamped import StampedNpzSink
from repro.apps.tomo.solver import (SliceReconstructor, TomoConfig,
                                    reconstruct_batch)
from repro.core import Broker, Context, NearRealTimePipeline, PipelineConfig
from repro.core.rdd import TaskScheduler
from repro.data import MetricsSink, ProjectionSource, SinkPolicy
from repro.data.metrics import get_registry

ELLIPSOIDS = 6


# -- traffic generator -----------------------------------------------------
def ellipsoids(rng: np.random.Generator, volumes: int) -> np.ndarray:
    """(volumes, ELLIPSOIDS, 7): centre (z, y, x) in [-0.4, 0.4], radii in
    [0.15, 0.5] and amplitude in [0.2, 1], as ``solver.make_phantom``
    draws them."""
    c = rng.uniform(-0.4, 0.4, (volumes, ELLIPSOIDS, 3))
    r = rng.uniform(0.15, 0.5, (volumes, ELLIPSOIDS, 3))
    a = rng.uniform(0.2, 1.0, (volumes, ELLIPSOIDS, 1))
    return np.concatenate([c, r, a], axis=-1)


def make_sinograms(params: jax.Array, A: jax.Array, slices: int,
                   nray: int) -> jax.Array:
    """Phantoms of nested ellipsoids inside the unit sphere, (slices, nray²)
    each, projected through ``A``: the tilt series, (volumes·slices, Nrow),
    one jitted call on the device."""
    def grid(k):
        return (jnp.arange(k, dtype=jnp.float32) - k / 2) / (k / 2)

    z = grid(slices)[:, None, None]
    y = grid(nray)[None, :, None]
    x = grid(nray)[None, None, :]

    def phantom(p):
        def add(vol, e):
            inside = (((z - e[0]) / e[3]) ** 2 + ((y - e[1]) / e[4]) ** 2
                      + ((x - e[2]) / e[5]) ** 2) < 1.0
            return vol + e[6] * inside, None
        vol, _ = jax.lax.scan(add, jnp.zeros((slices, nray, nray)), p)
        return jnp.where(z ** 2 + y ** 2 + x ** 2 > 0.95, 0.0, vol)

    def project(p):
        flat = phantom(p).reshape(slices, nray * nray)
        return jnp.dot(flat, A.T, precision="highest")

    return jax.lax.map(project, params).reshape(-1, A.shape[0])


class StampedProjections(ProjectionSource):
    """The tilt series of every phantom, repeated without end, with the time
    each slice was released recorded."""

    def __init__(self, sinograms: np.ndarray, interval: float) -> None:
        super().__init__(sinograms, interval=interval)
        self.released: dict[int, float] = {}

    def __len__(self) -> int:
        return 1 << 62

    def record_at(self, i: int):
        return f"slice-{i:012d}".encode(), (i, self._sino[i % len(self._sino)])

    def poll(self, max_records: int):
        with jax.profiler.TraceAnnotation("bench.source.poll"):
            recs = super().poll(max_records)
        now = time.perf_counter()
        for _, (i, _) in recs:
            self.released[i] = now
        return recs


class App:
    unit = "batch"

    def __init__(self, config: dict[str, Any], traffic: dict[str, Any],
                 seed: int, out_dir: str) -> None:
        self.c, self.t, self.seed, self.out_dir = config, traffic, seed, out_dir
        self.nray = int(config["nray"])
        self.S = int(config["slices_per_volume"])
        a = config["angles_deg"]
        self.angles = np.linspace(float(a["first"]), float(a["last"]),
                                  int(a["count"]))
        self.sweeps = int(config["sweeps"])
        self.beta = float(config["beta"])
        self.batch = int(traffic["batch_slices"])
        self.partitions = int(traffic["partitions"])
        if self.S % self.batch or self.batch % self.partitions:
            raise ValueError("slices_per_volume must be a multiple of "
                             "batch_slices, and that of partitions")
        self.per_part = self.batch // self.partitions
        self.objects = int(traffic["volumes"])
        self.nrow = len(self.angles) * self.nray
        self.tomo = TomoConfig(nray=self.nray,
                               angles=tuple(self.angles.tolist()),
                               beta=self.beta, iterations=self.sweeps)
        self.batches: list[dict[str, Any]] = []
        self.units: list[dict[str, Any]] = []

    # -- set-up -------------------------------------------------------------
    def make_inputs(self) -> None:
        """The reference's system matrix, and the phantoms' tilt series from
        the seed."""
        rng = np.random.default_rng(self.seed)
        params = ellipsoids(rng, self.objects)
        self.A_ref = tomo_ref.system_matrix(self.nray, self.angles)
        A = jax.device_put(self.A_ref.astype(np.float32))
        sino = jax.jit(make_sinograms, static_argnums=(2, 3))(
            jnp.asarray(params, jnp.float32), A, self.S, self.nray)
        self.sino = np.asarray(sino)
        del A, sino

    def setup(self) -> None:
        self.make_inputs()
        s = self.c["scheduler"]
        self.placements = get_registry().counter(
            "tomo_system_placements_total")
        self.operator = SliceReconstructor(self.tomo)
        self.ctx = Context(scheduler=TaskScheduler(
            num_executors=int(s["executors"]),
            max_failures=int(s["max_failures"]),
            speculation=bool(s["speculation"]),
            speculation_multiplier=float(s["speculation_multiplier"]),
            speculation_quantile=float(s["speculation_quantile"])))
        self.source = StampedProjections(
            self.sino, float(self.t["slice_interval_s"]))
        self.sink = StampedNpzSink(self.out_dir + "/tomo")
        self.metrics = MetricsSink()
        self.pipeline = NearRealTimePipeline(
            Broker(),
            PipelineConfig(batch_interval=0.05,
                           max_records_per_partition=self.batch),
            self._process, context=self.ctx,
            sinks=[self.metrics,
                   (self.sink, SinkPolicy.retry(2, queue_depth=32))])
        self.pipeline.subscribe_source(self.source, topic="tilt-series")
        self.run_unit()          # warm-up batch: the window's one program

    # -- the timed path -----------------------------------------------------
    def _process(self, rdd, info, bridge):
        out = reconstruct_batch(rdd, self.operator, self.partitions)
        if out is None:
            return None
        idx = np.concatenate([v["idx"] for _, v in out])
        self.batches.append({
            "first": int(idx.min()), "keys": [k for k, _ in out],
            "released": max(self.source.released[int(i)] for i in idx)})
        return out

    def run_unit(self) -> None:
        """One micro-batch: polled, reconstructed, and its sub-volumes in
        the sink; with the copies of a system matrix to the device that the
        program counted meanwhile."""
        t0, placed = time.perf_counter(), self.placements.value()
        self.pipeline.run(max_batches=1)
        self.pipeline.streaming.delivery.drain()
        self.units.append({"start": t0, "end": time.perf_counter(),
                           "batch": len(self.batches) - 1,
                           "placements": self.placements.value() - placed})

    def close(self) -> None:
        self.pipeline.close()

    def release(self) -> None:
        """Free the device state: the operator and its system matrix."""
        del self.operator

    # -- what the window did --------------------------------------------------
    def _window_units(self, w) -> list[dict[str, Any]]:
        return [u for u in self.units
                if w.t0 <= u["start"] and u["end"] <= w.t1]

    def _window_batches(self, w) -> list[dict[str, Any]]:
        return [self.batches[u["batch"]] for u in self._window_units(w)]

    def _written(self, b: dict[str, Any]) -> float | None:
        stamps = [self.sink.stamps.get(k) for k in b["keys"]]
        return None if None in stamps else max(stamps)

    def end_to_end(self, w) -> dict[str, float]:
        lat = [self._written(b) - b["released"]
               for b in self._window_batches(w) if self._written(b)]
        return {"batch_latency_s": float(np.mean(lat))}

    def attempted_failed(self, w) -> tuple[int, int]:
        batches = self._window_batches(w)
        return len(batches), sum(self._written(b) is None for b in batches)

    def facts(self, w) -> dict[str, Any]:
        units = self._window_units(w)
        return {"batches": len(units), "slices": len(units) * self.batch,
                "system_uploads": sum(u["placements"] for u in units),
                "art_work": roofline_art.art_batch(
                    self.batch, self.nrow, self.nray ** 2, self.sweeps)}

    def breakdown_ops(self, red) -> list[list[Any]]:
        named = ([[KERNEL, red.op_seconds(f"^{KERNEL}$")]]
                 if red.op_seconds(f"^{KERNEL}$") > 0 else [])
        rest = [r for r in red.top_ops(10) if r[0] != KERNEL]
        return (named + rest)[:10]

    # -- correctness -------------------------------------------------------------
    def _read(self, key: str) -> dict[str, np.ndarray] | None:
        try:
            with np.load(self.sink.path_for(key)) as z:
                return {k: z[k] for k in z.files}
        except OSError:
            return None

    def _expected(self, first: int) -> list[tuple[str, np.ndarray]]:
        """The keys and slice indices of a batch's sub-volumes."""
        out = []
        for p in range(self.partitions):
            lo = first + p * self.per_part
            hi = lo + self.per_part - 1
            out.append((f"slices-{lo:04d}-{hi:04d}", np.arange(lo, hi + 1)))
        return out

    def check(self, rng: np.random.Generator) -> list[Check]:
        """The data plane over every batch after the warm-up; the images of
        slices drawn from the seed against the plain reference.

        ``results_missing``: sub-volumes of those batches not on disk.
        ``batches_misplaced``: batches whose sub-volumes do not hold exactly
        their own slices, ``partitions`` runs of neighbouring ones, each
        with one image per slice. ``slice_gap``: the widest relative L2
        gap of a published image from the reference's, over one slice drawn
        from each partition position, each from a batch drawn from the run,
        so the draw spans the window's volumes; slices past every
        phantom's support (a zero sinogram row) are not drawn.
        """
        batches = self.batches[1:]                # not the warm-up
        drawn = set(self._draw(rng, [b["first"] for b in batches]))
        missing = misplaced = 0
        published: dict[int, dict[int, np.ndarray]] = {}
        for b in batches:
            bad = False
            for key, idx in self._expected(b["first"]):
                got = self._read(key)
                if got is None:
                    missing += 1
                    continue
                bad |= not (np.array_equal(got["idx"], idx)
                            and got["block"].shape
                            == (len(idx), self.nray, self.nray))
                for g, image in zip(got["idx"].tolist(), got["block"]):
                    if g in drawn:
                        o, s = self._volume_slice(g)
                        published.setdefault(o, {})[s] = image
            misplaced += bad
        checks = [Check("results_missing", missing, 0),
                  Check("batches_misplaced", misplaced, 0)]
        if sum(map(len, published.values())) < len(drawn):
            return checks + [Check("slice_unreadable", 1, 0)]
        gaps = [v for o, pub in published.items() for v in self._gaps(pub, o)]
        return checks + [Check("slice_gap", _finite(max(gaps)),
                               self.limits()["slice_gap"])]

    def limits(self) -> dict[str, float]:
        return {k: float(v) for k, v in self.c["limits"].items()}

    def _volume_slice(self, g: int) -> tuple[int, int]:
        """The phantom and slice that stream slice ``g`` shows."""
        return (g // self.S) % self.objects, g % self.S

    def _draw(self, rng: np.random.Generator, firsts: list[int]
              ) -> list[int]:
        """One stream slice per partition position, each from a batch drawn
        from ``firsts``; only slices whose sinogram row is not zero."""
        out = []
        for p in range(self.partitions):
            while True:
                first = firsts[int(rng.integers(len(firsts)))]
                g = first + p * self.per_part + int(
                    rng.integers(self.per_part))
                if self.sino[g % len(self.sino)].any():
                    out.append(g)
                    break
        return out

    def control_outputs(self, o: int, round_to: Any) -> dict[int, Any]:
        """The plain reference in ``round_to`` put in the program's place:
        what it publishes for slices of phantom ``o``, one per partition
        position of its batches, drawn from the seed."""
        rng = np.random.default_rng([self.seed, o])
        firsts = list(range(o * self.S, (o + 1) * self.S, self.batch))
        slices = [g % self.S for g in self._draw(rng, firsts)]
        images = self._reference(o, slices, round_to)
        return {s: im.reshape(self.nray, self.nray)
                for s, im in zip(slices, images)}

    def _reference(self, o: int, slices: list[int], round_to: Any = None
                   ) -> list[np.ndarray]:
        rows = [self.sino[o * self.S + s] for s in slices]
        with ThreadPoolExecutor(max_workers=len(rows)) as pool:
            return list(pool.map(
                lambda b: tomo_ref.art(self.A_ref, b, self.beta, self.sweeps,
                                       round_to), rows))

    def _gaps(self, pub: dict[int, Any], o: int) -> list[float]:
        slices = sorted(pub)
        refs = self._reference(o, slices)
        return [_rel(pub[s], ref) for s, ref in zip(slices, refs)]

    def compare(self, pub: dict[int, Any], o: int) -> list[Check]:
        """Published images of slices of phantom ``o`` (slice -> image)
        against the float64 reference: ``slice_gap``, their widest relative
        L2 gap."""
        return [Check("slice_gap", _finite(max(self._gaps(pub, o))),
                      self.limits()["slice_gap"])]


def _rel(got: Any, ref: np.ndarray) -> float:
    """Relative L2 gap of ``got`` from ``ref``."""
    got = np.asarray(got, np.float64).ravel()
    if got.shape != ref.shape:
        return float("inf")
    return float(np.linalg.norm(got - ref) / np.linalg.norm(ref))


def _finite(x: float) -> float:
    return float(x) if np.isfinite(x) else float("inf")
