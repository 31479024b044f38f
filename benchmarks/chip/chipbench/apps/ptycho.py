"""Streaming ptychography (paper §III), wired as ``examples/ptycho_pipeline.py``
wires it, driven scan after scan.

Traffic: an unpaced detector (``DetectorSource``) streams scans of
``frames_per_scan`` frames back to back into ``NearRealTimePipeline`` over
``partitions`` source partitions, ``batch_frames`` frames per micro-batch.
Frame ``g`` of the stream is frame ``g % frames_per_scan`` of scan
``g // frames_per_scan``; scan ``s`` measures object ``s % objects``. The
per-batch function is the example's: it grows the exit waves with
``init_waves`` and runs the RAAR step ``iterations_per_batch`` times under
one ``jax.jit`` held for the run, then publishes a keyed result through the
example's sinks (``MetricsSink``, and ``NpzDirectorySink`` on a retry
lane). After a scan's last batch come ``refine_iterations`` more steps over
all its frames, and its final object is published. The next scan starts
when that object is in the sink: a closed loop.

Set-up makes every input from the seed (objects, jittered scan grids and
far-field magnitudes in one jitted call on the device) and runs one scan
through the pipeline with one iteration per batch and one refinement
iteration: the iteration number is a traced argument, so that touches
every program the window runs.
"""
from __future__ import annotations

import time
from types import SimpleNamespace
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from chipbench import roofline
from chipbench.apps import ptycho_ref
from chipbench.harness import Check
from chipbench.stamped import StampedNpzSink
from repro.apps.ptycho.solver import SolverConfig, init_waves, raar_step
from repro.core import Broker, NearRealTimePipeline, PipelineConfig
from repro.data import DetectorSource, MetricsSink, SinkPolicy

# Pallas kernels of the RAAR step, reported by name in the breakdown.
KERNELS = ("modulus_project", "overlap_products", "raar_combine")


# -- traffic generator -----------------------------------------------------
def make_probe(n: int) -> np.ndarray:
    """Gaussian-apodised disk with a quadratic (defocus) phase."""
    y, x = np.mgrid[:n, :n] - n / 2 + 0.5
    r2 = (x ** 2 + y ** 2) / (n / 3.5) ** 2
    amp = np.exp(-r2) * (r2 < 4.0)
    return (amp * np.exp(1j * 0.8 * r2)).astype(np.complex64)


def scan_grid(rng: np.random.Generator, obj: int, n: int, step: int,
              frames: int) -> np.ndarray:
    """Raster of frame corners with ±step/4 jitter; the first ``frames``."""
    lim = obj - n
    xs = np.arange(0, lim + 1, step)
    pos = np.array([(y, x) for y in xs for x in xs])
    if len(pos) < frames:
        raise ValueError(f"a {obj}² object at step {step} holds only "
                         f"{len(pos)} positions, not {frames}")
    jitter = rng.integers(-(step // 4), step // 4 + 1, pos.shape)
    return np.clip(pos + jitter, 0, lim).astype(np.int32)[:frames]


def _smooth(key: jax.Array, count: int, size: int, scale: int) -> jax.Array:
    small = jax.random.normal(key, (count, size // scale, size // scale))
    return jax.image.resize(small, (count, size, size), "linear")


def make_measurements(key: jax.Array, positions: jax.Array,
                      probe: jax.Array, size: int) -> jax.Array:
    """Far-field magnitudes |F(P · O_patch)| of ``len(positions)`` random
    smooth objects (amplitude in [0.7, 1], phase of low-frequency
    structure), one jitted call on the device."""
    count, frames = positions.shape[:2]
    n = probe.shape[-1]
    k1, k2, k3 = jax.random.split(key, 3)
    amp = 0.85 + 0.15 * jnp.tanh(_smooth(k1, count, size, 8))
    phase = (1.4 * jnp.tanh(_smooth(k2, count, size, 4))
             + 0.6 * jnp.tanh(_smooth(k3, count, size, 16)))
    objs = (amp * jnp.exp(1j * phase)).astype(jnp.complex64)
    r = jnp.arange(n)
    iy = positions[..., 0, None, None] + r[None, None, :, None]
    ix = positions[..., 1, None, None] + r[None, None, None, :]
    patches = jax.vmap(lambda o, y, x: o[y, x])(objs, iy, ix)
    return jnp.abs(jnp.fft.fft2(probe * patches)).astype(jnp.float32)


class StampedDetector(DetectorSource):
    """The detector, with the time each frame was released recorded."""

    def __init__(self, frames_total: int, interval: float) -> None:
        super().__init__(SimpleNamespace(num_frames=frames_total),
                         frame_interval=interval)
        self.released: dict[int, float] = {}

    def poll(self, max_records: int):
        with jax.profiler.TraceAnnotation("bench.source.poll"):
            recs = super().poll(max_records)
        now = time.perf_counter()
        for _, frame in recs:
            self.released[frame] = now
        return recs


class App:
    unit = "scan"

    def __init__(self, config: dict[str, Any], traffic: dict[str, Any],
                 seed: int, out_dir: str) -> None:
        self.c, self.t, self.seed, self.out_dir = config, traffic, seed, out_dir
        self.F = int(config["frames_per_scan"])
        self.n = int(config["probe_size"])
        self.obj_shape = (int(config["object_size"]),) * 2
        self.batch = int(traffic["batch_frames"])
        if self.F % self.batch:
            raise ValueError("frames_per_scan must be a multiple of "
                             "batch_frames")
        self.batches_per_scan = self.F // self.batch
        self.iters = int(config["iterations_per_batch"])
        self.refine = int(config["refine_iterations"])
        self.objects = int(traffic["objects"])
        self.solver = SolverConfig(
            beta=float(config["beta"]), iterations=self.refine,
            probe_update_start=int(config["probe_update_start"]),
            eps=float(config["eps"]))
        self.scan = 0
        self.batches: list[dict[str, Any]] = []
        self.scans: list[dict[str, Any]] = []
        # per scan, each published iteration (the last of each batch and of
        # the refinement) as the device holds it: input waves, probe and
        # iteration number, output waves and probe
        self.steps: dict[int, list[tuple[Any, ...]]] = {}

    # -- set-up -------------------------------------------------------------
    def make_inputs(self) -> None:
        """Objects, scan grids and magnitudes, from the seed."""
        rng = np.random.default_rng(self.seed)
        self.positions = np.stack([
            scan_grid(rng, self.obj_shape[0], self.n,
                      int(self.c["scan_step"]), self.F)
            for _ in range(self.objects)])
        self.probe0 = make_probe(self.n)
        key = jax.random.PRNGKey(int(rng.integers(0, 2 ** 31 - 1)))
        self.pos_dev = jnp.asarray(self.positions)
        self.mags = jax.jit(make_measurements, static_argnums=3)(
            key, self.pos_dev, jnp.asarray(self.probe0), self.obj_shape[0])
        # the reference's copy: the device's is freed before it runs
        self.mags_host = np.asarray(self.mags)

    def setup(self) -> None:
        self.make_inputs()

        obj_shape, solver = self.obj_shape, self.solver

        def raar_iteration(psi, mag, pos, probe, it):
            return raar_step(psi, mag, pos, probe, obj_shape, solver, it)

        self.step = jax.jit(raar_iteration)
        self.source = StampedDetector(
            1 << 62, float(self.t["frame_interval_s"]))
        self.sink = StampedNpzSink(self.out_dir + "/ptycho")
        self.metrics = MetricsSink()
        self.state: dict[str, Any] = {}
        self.pipeline = NearRealTimePipeline(
            Broker(),
            PipelineConfig(batch_interval=0.05,
                           max_records_per_partition=(
                               self.batch // int(self.t["partitions"])),
                           source_partitions=int(self.t["partitions"])),
            self._process,
            sinks=[self.metrics,
                   (self.sink, SinkPolicy.retry(2, queue_depth=32))])
        self.pipeline.subscribe_source(self.source, topic="frames")
        self.run_unit(iters=1, refine=1)  # warm-up scan: every program
        self.steps.clear()

    # -- the timed path -----------------------------------------------------
    def _process(self, rdd, info, bridge):
        ids = sorted(rdd.collect())
        if not ids:
            return None
        with jax.profiler.TraceAnnotation("bench.ptycho.batch_fn"):
            return self._batch(ids, info)

    def _batch(self, ids, info):
        # the example's per-batch closure, fed from the frames that arrived
        scan = ids[0] // self.F
        local = np.asarray(ids) % self.F
        st = self.state
        if st.get("scan") != scan:
            st.clear()
            st.update(scan=scan, probe=jnp.asarray(self.probe0), psi=None,
                      n_seen=0, iteration=0)
        o = scan % self.objects
        new_mag = self.mags[o][local]
        new_pos = self.pos_dev[o][local]
        if st["psi"] is None:
            psi, mags, pos = init_waves(new_mag, st["probe"]), new_mag, new_pos
        else:
            psi = jnp.concatenate([st["psi"], init_waves(new_mag,
                                                         st["probe"])])
            mags = jnp.concatenate([st["mags"], new_mag])
            pos = jnp.concatenate([st["pos"], new_pos])
        for i in range(self._iters):
            psi_in, probe_in, it = psi, st["probe"], st["iteration"]
            psi, obj, probe, err = self.step(psi, mags, pos, probe_in, it)
            if i == self._iters - 1:       # the published iteration
                self.steps.setdefault(scan, []).append(
                    (psi_in, probe_in, it, psi, probe))
            st["probe"] = probe
            st["iteration"] += 1
        st.update(psi=psi, obj=obj, mags=mags, pos=pos,
                  n_seen=st["n_seen"] + len(ids))
        k = st["n_seen"] // self.batch - 1
        key = f"scan-{scan:06d}-batch-{k:03d}"
        value = {"fourier_err": np.float32(err),
                 "frames_seen": np.int32(st["n_seen"]),
                 "frames": np.asarray(ids, np.int64)}
        self.batches.append({"key": key, "scan": scan,
                             "released": max(self.source.released[i]
                                             for i in ids)})
        return [(key, value)]

    def run_unit(self, iters: int | None = None,
                 refine: int | None = None) -> None:
        """One scan: its batches through the pipeline, the refinement, the
        final object into the sink; by the configuration's schedule unless
        ``iters`` per batch and ``refine`` are given."""
        scan = self.scan
        self._iters = self.iters if iters is None else iters
        refine = self.refine if refine is None else refine
        t0 = time.perf_counter()
        self.pipeline.run(max_batches=self.batches_per_scan)
        st = self.state
        with jax.profiler.TraceAnnotation("bench.ptycho.refine"):
            psi, probe = st["psi"], st["probe"]
            for i in range(refine):
                psi_in, probe_in, it = psi, probe, st["iteration"] + i
                psi, obj, probe, err = self.step(psi, st["mags"], st["pos"],
                                                 probe_in, it)
                if i == refine - 1:
                    self.steps.setdefault(scan, []).append(
                        (psi_in, probe_in, it, psi, probe))
            obj = np.asarray(obj)
        value = {"obj": obj, "fourier_err": np.float32(err)}
        key = f"scan-{scan:06d}-final"
        self.sink.write_batch([(key, value)], overwrite=True)
        self.scans.append({"scan": scan, "start": t0,
                           "end": self.sink.stamps[key]})
        self.scan += 1

    def close(self) -> None:
        self.pipeline.close()

    def release(self) -> None:
        """Free the device state; the scan the check compares, drawn from the
        seed among those the run completed after set-up, keeps its published
        iterations' inputs on the host."""
        scans = [u["scan"] for u in self.scans][1:]
        rng = np.random.default_rng(self.seed)
        self.sampled = int(scans[rng.integers(len(scans))])
        self.sampled_steps = [
            (np.asarray(p), np.asarray(q), it, np.asarray(p1), np.asarray(q1))
            for p, q, it, p1, q1 in self.steps[self.sampled]]
        self.state.clear()
        self.steps.clear()
        del self.mags, self.pos_dev

    # -- what the window did --------------------------------------------------
    def _window_scans(self, w) -> list[int]:
        return [s["scan"] for s in self.scans
                if w.t0 <= s["start"] and s["end"] <= w.t1]

    def _window_batches(self, w) -> list[dict[str, Any]]:
        scans = set(self._window_scans(w))
        return [b for b in self.batches if b["scan"] in scans]

    def end_to_end(self, w) -> dict[str, float]:
        lat = [self.sink.stamps[b["key"]] - b["released"]
               for b in self._window_batches(w) if b["key"] in self.sink.stamps]
        return {"scan_s": w.seconds / len(self._window_scans(w)),
                "batch_latency_s": float(np.mean(lat))}

    def attempted_failed(self, w) -> tuple[int, int]:
        batches = self._window_batches(w)
        return len(batches), sum(b["key"] not in self.sink.stamps
                                 for b in batches)

    def facts(self, w) -> dict[str, Any]:
        spans = [s for s in self.pipeline.streaming.traces.last()
                 if w.wall0 <= s.started_at <= w.wall1]
        return {"batch_spans": spans, "refine_iterations": self.refine,
                "raar_work": roofline.raar_iteration(self.F, self.n,
                                                     self.obj_shape)}

    def breakdown_ops(self, red) -> list[list[Any]]:
        named = [[k, red.op_seconds(f"^{k}$")] for k in KERNELS
                 if red.op_seconds(f"^{k}$") > 0]
        rest = [r for r in red.top_ops(10) if r[0] not in KERNELS]
        return (named + rest)[:10]

    # -- correctness -------------------------------------------------------------
    def _read(self, key: str) -> dict[str, np.ndarray] | None:
        path = self.sink.path_for(key)
        try:
            with np.load(path) as z:
                return {k: z[k] for k in z.files}
        except OSError:
            return None

    def check(self, rng: np.random.Generator) -> list[Check]:
        """The data plane over every scan the window completed; the
        reconstruction of one of them, drawn from the seed, against the
        plain reference."""
        scans = [s["scan"] for s in self.scans][1:]      # not the warm-up
        missing = misplaced = 0
        for s in scans:
            for k in range(self.batches_per_scan):
                got = self._read(f"scan-{s:06d}-batch-{k:03d}")
                if got is None:
                    missing += 1
                    continue
                lo = s * self.F + k * self.batch
                if not np.array_equal(np.sort(got["frames"]),
                                      np.arange(lo, lo + self.batch)):
                    misplaced += 1
            missing += self._read(f"scan-{s:06d}-final") is None
        checks = [Check("results_missing", missing, 0),
                  Check("batches_misplaced", misplaced, 0)]
        prog = self.program_outputs(self.sampled)
        if prog is None:
            return checks + [Check("scan_unreadable", 1, 0)]
        return checks + self.compare(prog, self.sampled % self.objects)

    def limits(self) -> dict[str, float]:
        return {k: float(v) for k, v in self.c["limits"].items()}

    def program_outputs(self, s: int) -> dict[str, Any] | None:
        errs = []
        for k in range(self.batches_per_scan):
            got = self._read(f"scan-{s:06d}-batch-{k:03d}")
            if got is None:
                return None
            errs.append(float(got["fourier_err"]))
        final = self._read(f"scan-{s:06d}-final")
        if final is None:
            return None
        return {"batch_errors": errs, "object": final["obj"],
                "error": float(final["fourier_err"]),
                "steps": self.sampled_steps}

    def _ref_kw(self) -> dict[str, Any]:
        return dict(beta=self.solver.beta,
                    probe_update_start=self.solver.probe_update_start,
                    eps=self.solver.eps)

    def control_outputs(self, o: int, round_to: Any) -> dict[str, Any]:
        """The plain reference in ``round_to`` put in the program's place:
        what it publishes for a scan of object ``o``."""
        return ptycho_ref.reconstruct(
            self.mags_host[o], self.positions[o], self.probe0,
            self.obj_shape, batch_frames=self.batch,
            iters_per_batch=self.iters, refine=self.refine,
            round_to=round_to, **self._ref_kw())

    def compare(self, pub: dict[str, Any], o: int) -> list[Check]:
        """What a scan of object ``o`` published, against the reference.

        ``first_batch_gap``: the first batch's error against the
        reference's own run from the start waves (6 iterations, the start
        waves and the modulus and overlap projections covered end to end).
        The ``step_*`` gaps: each published iteration (the last of every
        batch and of the refinement, over 64 to 512 frames) against one
        reference iteration from the same input waves and probe, with the
        scan's own magnitudes and positions: the widest relative gap of the
        published errors (``step_err_gap``), and the widest relative L2
        gap of the output waves (``step_wave_gap``: the modulus projection,
        the object and probe solves, the gather of the object's patches and
        the combine over every frame) and of the output probe
        (``step_probe_gap``); and the relative L2 gap of the final object
        (``step_obj_gap``). A longer run of the reference is not compared:
        RAAR amplifies rounding differences over its iterations, so two
        sound float32 runs part after some tens of them.
        """
        mags, pos, lim = self.mags_host[o], self.positions[o], self.limits()
        first = ptycho_ref.reconstruct(
            mags, pos, self.probe0, self.obj_shape, batch_frames=self.batch,
            iters_per_batch=self.iters, refine=0, batches=1,
            **self._ref_kw())["batch_errors"][0]
        errs = pub["batch_errors"] + [pub["error"]]
        if len(pub["steps"]) != len(errs):
            return [Check("steps_missing", 1, 0)]
        err_gaps, wave_gaps, probe_gaps = [], [], []
        for (psi, probe, it, psi_out, probe_out), e in zip(pub["steps"],
                                                           errs):
            psi_ref, obj, probe_ref, e_ref = ptycho_ref.one_step(
                mags, pos, psi, probe, it, self.obj_shape, **self._ref_kw())
            err_gaps.append(abs(e - e_ref) / e_ref)
            wave_gaps.append(_rel(psi_out, psi_ref))
            probe_gaps.append(_rel(probe_out, probe_ref))
        gaps = {"first_batch_gap": abs(errs[0] - first) / first,
                "step_err_gap": max(err_gaps),
                "step_wave_gap": max(wave_gaps),
                "step_probe_gap": max(probe_gaps),
                "step_obj_gap": _rel(pub["object"], obj)}
        return [Check(k, _finite(v), lim[k]) for k, v in gaps.items()]


def _rel(got: Any, ref: np.ndarray) -> float:
    """Relative L2 gap of ``got`` from ``ref``."""
    got = np.asarray(got, np.complex128)
    if got.shape != ref.shape:
        return float("inf")
    return float(np.linalg.norm(got - ref) / np.linalg.norm(ref))


def _finite(x: float) -> float:
    return float(x) if np.isfinite(x) else float("inf")
