#!/usr/bin/env python3
"""Smoke test: the streaming pipelines' main path, end to end, on a TPU.

Run from the root of a checkout, on a machine with a TPU:

    python chip_smoke.py              # one chip: phases (a)-(d)
    python chip_smoke.py --chips 4    # four chips: the collective phase only

Everything runs in this one process: an accelerator belongs to one process
at a time, so no phase starts a child that would need it.

  (a) device    fail unless JAX's first device is a TPU; kernels dispatch
                compiled, never in interpret mode
  (b) kernels   modulus, overlap products and scatter, RAAR combine and ART
                sweep, compiled, at the main-path shapes, against their
                ``ref.py``
  (c) ptycho    ``examples/ptycho_pipeline.py`` at the paper's Table II size
                (512 frames of 64² streamed over a 256² object)
  (d) tomo      ``examples/tomo_pipeline.py`` at its defaults (64 rays,
                25 angles, 32 slices, ART through the kernel), its system
                matrix placed on the chip once for the whole stream
  (e) --chips 4 only: ``MPIBridge.allreduce`` of the paper's Table I
                payload against a numpy sum, and ``raar_step`` with its
                frames split over the chips (``psum`` of the overlap sums)
                against one device

Each phase prints PASS or FAIL with its wall time and the number of XLA
programs it needed: how many were compiled and how many the persistent
compilation cache served.
Artifacts go to ``out/chip_smoke/``. The last line of a passing run is
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``;
a failing run exits non-zero and never prints it. Nothing here is a
benchmark: the times include compilation.
"""
from __future__ import annotations

import argparse
import importlib.util
import json
import math
import os
import sys
import time
import traceback

REPO = os.path.dirname(os.path.abspath(__file__))
OUT = os.path.join(REPO, "out", "chip_smoke")

FRAMES, FRAME, OBJ, SCAN_STEP = 512, 64, 256, 8    # paper Table II
NRAY, ANGLES, SLICES, SWEEPS = 64, 25, 32, 2       # tomo_pipeline defaults
ALLREDUCE_N = 2_000_000                            # paper Table I payload
ELEMENTWISE_TOL, ART_TOL, SHARDED_TOL = 1e-5, 1e-4, 1e-4
# Quality floors: the same runs on the host CPU (reference jnp path, same
# size and seed) gave phase correlation 0.943 and sinogram residual 0.368;
# the chip must come within 0.05 of each.
PTYCHO_MIN_PHASE_CORR = 0.943 - 0.05
TOMO_MAX_RESIDUAL = 0.368 + 0.05


class CompileCounter:
    """Counts the programs XLA was asked for, and how many of those the
    persistent cache served; the rest were compiled."""

    def __init__(self, jax) -> None:
        self.programs = self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event: str, duration_secs: float, **_) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.programs += 1      # timed around compile-or-load

    def _event(self, event: str, **_) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1


def rel_err(got, want) -> float:
    import numpy as np
    got, want = np.asarray(got), np.asarray(want)
    return float(np.max(np.abs(got - want)) / max(np.max(np.abs(want)),
                                                  1e-30))


def check(cond: bool, what: str) -> None:
    if not cond:
        raise AssertionError(what)


def load_example(name: str):
    path = os.path.join(REPO, "examples", f"{name}.py")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def phase_kernels() -> str:
    """(b) each main-path kernel, compiled, against its oracle."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.apps.tomo.projector import make_system
    from repro.apps.tomo.solver import TomoConfig, simulate_tilt_series
    from repro.kernels.art import kernel as art_k, ref as art_r
    from repro.kernels.modulus import kernel as mod_k, ref as mod_r
    from repro.apps.ptycho.sim import scan_grid
    from repro.kernels.overlap import kernel as ov_k, ref as ov_r
    from repro.kernels.raar import kernel as raar_k, ref as raar_r

    shape = (FRAMES, FRAME, FRAME)
    keys = jax.random.split(jax.random.PRNGKey(0), 8)
    planes = [jax.random.normal(k, shape, jnp.float32) for k in keys]
    mag = jnp.abs(planes[2])
    pos = jnp.asarray(scan_grid(OBJ, FRAME, SCAN_STEP)[:FRAMES])
    probe = jax.lax.complex(planes[2][0], planes[3][0])
    num, den = ov_r.overlap_scatter_complex(
        jax.lax.complex(*planes[:2]), probe, pos, (OBJ, OBJ))
    cases = {
        "modulus": (mod_k.modulus_project(*planes[:2], mag, interpret=False),
                    mod_r.modulus_project_ref(*planes[:2], mag)),
        "overlap": (ov_k.overlap_products(*planes[:4], interpret=False),
                    ov_r.overlap_products_ref(*planes[:4])),
        "overlap scatter": (ov_k.overlap_scatter(
            *planes[:2], planes[2][0], planes[3][0], pos,
            obj_shape=(OBJ, OBJ), interpret=False),
            (jnp.real(num), jnp.imag(num), den)),
        "raar": (raar_k.raar_combine(*planes, beta=0.75, interpret=False),
                 raar_r.raar_combine_ref(*planes, beta=0.75)),
    }
    errs = {name: max(rel_err(g, w) for g, w in zip(got, want))
            for name, (got, want) in cases.items()}

    cfg = TomoConfig(nray=NRAY,
                     angles=tuple(np.linspace(-75, 75, ANGLES).tolist()))
    _, sino = simulate_tilt_series(cfg, SLICES)
    A = jnp.asarray(make_system(NRAY, np.asarray(cfg.angles)))
    rip = jnp.sum(A * A, axis=1)
    inv_rip = jnp.where(rip > 0, 1.0 / jnp.maximum(rip, 1e-12), 0.0)
    f0 = jnp.zeros((NRAY * NRAY,), jnp.float32)
    oracle = jax.jit(jax.vmap(lambda b: art_r.art_sweep_ref(
        A, b, inv_rip, f0, iters=SWEEPS)))
    B = jnp.asarray(sino)
    errs["art"] = rel_err(art_k.art_sweep(A, B, inv_rip, iters=SWEEPS,
                                          interpret=False), oracle(B))

    for name, e in errs.items():
        tol = ART_TOL if name == "art" else ELEMENTWISE_TOL
        check(math.isfinite(e) and e <= tol,
              f"{name}: max rel err {e:.3e} > {tol:g}")
    return ", ".join(f"{n} max rel err {e:.3e}" for n, e in errs.items())


def phase_ptycho() -> str:
    """(c) streaming ptychography through the example's own main()."""
    res = load_example("ptycho_pipeline").main(
        ["--frames", str(FRAMES), "--obj-size", str(OBJ),
         "--probe-size", str(FRAME), "--scan-step", str(SCAN_STEP),
         "--out", OUT])
    errs = res["batch_errors"]
    check(res["frames_streamed"] == FRAMES,
          f"{res['frames_streamed']} of {FRAMES} frames streamed")
    check(all(math.isfinite(e) for e in errs + [res["final_error"]]),
          f"non-finite Fourier error: {errs} -> {res['final_error']}")
    check(res["final_error"] < errs[0],
          f"Fourier error did not fall: {errs[0]} -> {res['final_error']}")
    q = res["phase_correlation"]
    check(q >= PTYCHO_MIN_PHASE_CORR,
          f"phase correlation {q:.4f} < {PTYCHO_MIN_PHASE_CORR:.3f}")
    keys = res["artifact_keys"]
    check(len(keys) == len(errs) + 1 and "object-final" in keys,
          f"{len(keys)} npz artifacts for {len(errs)} batches: {keys}")
    check(all(os.path.exists(p) for p in res["renders"]),
          f"missing renders {res['renders']}")
    return (f"{res['frames_streamed']}/{FRAMES} frames in {len(errs)} "
            f"batches, fourier err {errs[0]:.4f} -> "
            f"{res['final_error']:.4f}, phase corr {q:.4f} "
            f"(floor {PTYCHO_MIN_PHASE_CORR:.3f}), {len(keys)} npz")


def phase_tomo() -> str:
    """(d) streaming tomography through the example's own main()."""
    res = load_example("tomo_pipeline").main(
        ["--nray", str(NRAY), "--angles", str(ANGLES),
         "--nslice", str(SLICES), "--iterations", str(SWEEPS),
         "--out", OUT])
    r = res["residual"]
    check(res["batches"] >= 2, f"only {res['batches']} micro-batch(es)")
    check(math.isfinite(r) and r <= TOMO_MAX_RESIDUAL,
          f"sinogram residual {r:.4f} > {TOMO_MAX_RESIDUAL:.3f}")
    check(len(res["artifact_keys"]) > 0
          and all(os.path.exists(p) for p in res["renders"]),
          "missing sub-volume artifacts or renders")
    check(res["system_placements"] == 1,
          f"system matrix placed {res['system_placements']} times, not once")
    return (f"{res['slices']} slices in {res['batches']} micro-batches, "
            f"sinogram residual {r:.4f} (ceiling {TOMO_MAX_RESIDUAL:.3f}), "
            f"volume rel err {res['volume_error']:.4f}, system matrix "
            f"placed once")


def phase_collectives(chips: int) -> str:
    """(e) the paper's collectives across chips, against one device."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.apps.ptycho.sim import simulate
    from repro.apps.ptycho.solver import SolverConfig, init_waves, raar_step
    from repro.core import Context, MPIBridge

    bridge = MPIBridge()
    check(bridge.world == chips, f"bridge world {bridge.world} != {chips}")
    rng = np.random.default_rng(0)
    parts = [rng.standard_normal(ALLREDUCE_N).astype(np.float32)
             for _ in range(chips)]
    e_ar = rel_err(bridge.allreduce(Context().from_partitions(parts)),
                   np.sum(parts, axis=0))
    check(e_ar <= 1e-6, f"allreduce max rel err {e_ar:.3e}")

    prob = simulate(OBJ, FRAME, SCAN_STEP)
    mags = prob.magnitudes[:FRAMES]
    pos = jnp.asarray(prob.positions[:FRAMES])
    probe = jnp.asarray(prob.probe_true)
    psi = init_waves(mags, probe)
    obj_shape, cfg, it = prob.object_true.shape, SolverConfig(), 5
    want = jax.jit(lambda *a: raar_step(*a, obj_shape, cfg, it))(
        psi, mags, pos, probe)

    def rank(psi, mag, pos, probe):
        out = raar_step(psi[0], mag[0], pos[0], probe[0], obj_shape, cfg, it,
                        axis_name=bridge.axis_name)
        return tuple(x[None] for x in out)

    shard = NamedSharding(bridge.mesh, P(bridge.axis_name))
    split = [jax.device_put(x.reshape((chips, -1) + x.shape[1:]), shard)
             for x in (psi, mags, pos)]
    probes = jax.device_put(jnp.stack([probe] * chips), shard)
    got = bridge.spmd(rank)(*split, probes)
    check(len(got[0].sharding.device_set) == chips,
          f"sharded psi lives on {len(got[0].sharding.device_set)} devices")
    errs = {"psi": rel_err(got[0].reshape(want[0].shape), want[0]),
            "object": rel_err(got[1][0], want[1]),
            "probe": rel_err(got[2][0], want[2]),
            "fourier err": rel_err(got[3][0], want[3])}
    for name, e in errs.items():
        check(e <= SHARDED_TOL, f"sharded {name}: max rel err {e:.3e}")
    return (f"allreduce {ALLREDUCE_N} f32 x {chips} max rel err {e_ar:.3e}; "
            f"raar_step {FRAMES} frames over {chips} chips vs one: "
            + ", ".join(f"{n} {e:.3e}" for n, e in errs.items()))


def programs(n: int, hits: int) -> str:
    return f"{n} programs ({n - hits} compiled, {hits} from the cache)"


def run_phase(name: str, fn, counter: CompileCounter) -> bool:
    c0, h0, t0 = counter.programs, counter.cache_hits, time.perf_counter()
    try:
        detail, ok = fn(), True
    except Exception as e:      # report every failure, then exit 1
        traceback.print_exc()
        detail, ok = f"{type(e).__name__}: {e}", False
    print(f"[{name}] {'PASS' if ok else 'FAIL'} in "
          f"{time.perf_counter() - t0:.1f}s, "
          f"{programs(counter.programs - c0, counter.cache_hits - h0)}: "
          f"{detail}",
          flush=True)
    return ok


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the collective phase, across 4 chips")
    args = ap.parse_args(argv)

    if not os.path.isdir(os.path.join(REPO, "src", "repro")):
        print(f"FAIL: no src/repro next to {__file__}: run from a checkout",
              file=sys.stderr)
        return 2
    sys.path.insert(0, os.path.join(REPO, "src"))
    from repro.utils import enable_compile_cache
    cache_dir = enable_compile_cache()       # before the first compilation
    import jax
    from repro.kernels import dispatch
    counter = CompileCounter(jax)
    t_start = time.perf_counter()

    devs = jax.devices()
    dev = devs[0]
    print(f"[a device] platform {dev.platform}, kind {dev.device_kind!r}, "
          f"count {len(devs)}; compile cache {cache_dir}", flush=True)
    if dev.platform != "tpu":
        print("[a device] FAIL: no TPU found (this smoke test never falls "
              "back to the CPU)", flush=True)
        return 1
    if len(devs) < args.chips:
        print(f"[a device] FAIL: --chips {args.chips} but {len(devs)} "
              f"device(s)", flush=True)
        return 1
    mode = dispatch.kernel_mode()
    if mode != (True, False):
        print(f"[a device] FAIL: kernel dispatch {mode}, want compiled "
              f"kernels (True, False)", flush=True)
        return 1
    print("[a device] PASS: Pallas kernels dispatch compiled "
          "(use_pallas=True, interpret=False)", flush=True)

    os.makedirs(OUT, exist_ok=True)
    if args.chips == 4:
        phases = [("e collectives", lambda: phase_collectives(4))]
    else:
        phases = [("b kernels", phase_kernels), ("c ptycho", phase_ptycho),
                  ("d tomo", phase_tomo)]
    ok = all([run_phase(name, fn, counter) for name, fn in phases])
    print(f"total {time.perf_counter() - t_start:.1f}s, "
          f"{programs(counter.programs, counter.cache_hits)}", flush=True)
    if not ok:
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devs)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
