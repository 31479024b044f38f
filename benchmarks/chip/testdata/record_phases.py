#!/usr/bin/env python3
"""Record ``raar_phases.xplane.pb`` on one TPU: three RAAR steps of
``apps/ptycho/solver.py``, with its phase scopes, at a small size (64
frames of 32² on a 128² object, Pallas kernels, the iteration number
traced and past the start of the probe updates), inside one ``bench.raar``
span; the program compiled and warmed up before the trace starts.

    python3 benchmarks/chip/testdata/record_phases.py <out.xplane.pb>

From the root of a checkout, on a machine with a TPU.
"""
import glob
import os
import shutil
import sys
import tempfile

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(HERE, "..", "..", "..", "src"))

FRAMES, FRAME, OBJ, STEPS = 64, 32, 128, 3


def main(out: str) -> int:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.apps.ptycho.solver import SolverConfig, init_waves, raar_step

    if jax.devices()[0].platform != "tpu":
        print("no TPU", file=sys.stderr)
        return 2
    rng = np.random.default_rng(0)
    pos = jnp.asarray(rng.integers(0, OBJ - FRAME, (FRAMES, 2)), jnp.int32)
    mag = jnp.asarray(rng.random((FRAMES, FRAME, FRAME)), jnp.float32)
    probe = jnp.ones((FRAME, FRAME), jnp.complex64)
    cfg = SolverConfig()
    step = jax.jit(lambda psi, probe, it: raar_step(
        psi, mag, pos, probe, (OBJ, OBJ), cfg, it))
    psi = init_waves(mag, probe)
    it = jnp.int32(cfg.probe_update_start)
    jax.block_until_ready(step(psi, probe, it))          # compile, warm up

    log_dir = tempfile.mkdtemp()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(log_dir, profiler_options=opts)
    with jax.profiler.TraceAnnotation("bench.raar"):
        for _ in range(STEPS):
            psi, _, probe, err = step(psi, probe, it)
        jax.block_until_ready(psi)
    jax.profiler.stop_trace()
    [path] = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                       recursive=True)
    shutil.copyfile(path, out)
    print(f"{out}: {os.path.getsize(out)} bytes, err {float(err):.4f}")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))
