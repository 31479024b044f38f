"""Paper Table II: SHARP-NSLS2 ptychographic solver scaling (512 frames,
100 iterations; paper: 22.7 / 13.6 / 8.6 s on 1/2/4 K80 nodes).

Measured: RAAR iteration time on the default backend, which the row names
(reduced frames for tractability, then scaled to the paper's 512×64²
workload by FLOP ratio). Derived: the v5e model — per-iteration FLOPs
(2 FFTs + overlap products + combine per frame) over peak, plus the two
psum allreduces of the object/probe numerators (paper Fig. 9) over ICI —
for 1/2/4 chips, the Table II layout.
"""
from __future__ import annotations

import numpy as np

from benchmarks.common import (HBM_BW, ICI_BW, PEAK_FLOPS,
                               allreduce_model_time, emit, time_call)


def _iteration_flops(frames: int, fsize: int, obj: int) -> float:
    fft = 2 * 5.0 * frames * fsize * fsize * np.log2(fsize * fsize)  # c2c x2
    elemwise = 40.0 * frames * fsize * fsize       # modulus+overlap+combine
    return fft + elemwise


def _iteration_bytes(frames: int, fsize: int, obj: int) -> float:
    # psi read/write ~6 passes of complex64 + object/probe canvases
    return 6.0 * frames * fsize * fsize * 8 + 4.0 * obj * obj * 8


def run(frames: int = 128, fsize: int = 32, iters: int = 10) -> None:
    import jax
    import jax.numpy as jnp
    from repro.apps.ptycho.sim import simulate
    from repro.apps.ptycho.solver import SolverConfig, init_waves, raar_step

    prob = simulate(obj_size=96, probe_size=fsize, step=8)
    n = min(frames, prob.num_frames)
    mags = prob.magnitudes[:n]
    pos = jnp.asarray(prob.positions[:n])
    cfg = SolverConfig()
    probe = jnp.asarray(prob.probe_true)
    psi = init_waves(mags, probe)
    obj_shape = prob.object_true.shape

    @jax.jit
    def one_iter(psi, probe):
        psi, obj, probe, err = raar_step(psi, mags, pos, probe, obj_shape,
                                         cfg, 3)
        return psi, probe

    psi, probe = one_iter(psi, probe)   # compile
    t = time_call(lambda: jax.block_until_ready(one_iter(psi, probe)),
                  repeats=3)
    backend = jax.default_backend()
    emit(f"ptycho/raar_iter_{backend}", t,
         f"measured: {n} frames of {fsize}^2 per iteration")

    # scale to the paper workload and derive the v5e Table II row
    paper_frames, paper_fsize, paper_iters = 512, 64, 100
    scale = (_iteration_flops(paper_frames, paper_fsize, 256)
             / _iteration_flops(n, fsize, 96))
    cpu_100 = t * scale * paper_iters
    emit(f"ptycho/100iter_512f_{backend}_scaled", cpu_100,
         f"{backend}-scaled paper workload (paper 1 node: 22.7s)")
    for chips in (1, 2, 4):
        fl = _iteration_flops(paper_frames // chips, paper_fsize, 256)
        by = _iteration_bytes(paper_frames // chips, paper_fsize, 256)
        # overlap allreduce: object+probe numerators+denominators, complex64
        ar_bytes = 256 * 256 * 12 + paper_fsize * paper_fsize * 12
        t_it = max(fl / PEAK_FLOPS, by / HBM_BW) + \
            allreduce_model_time(ar_bytes, chips, ICI_BW, latency=1e-6)
        emit(f"ptycho/model_{chips}chips_100iter", t_it * paper_iters,
             f"v5e roofline model (paper K80 row: "
             f"{ {1: 22.7, 2: 13.6, 4: 8.6}[chips] }s)")


if __name__ == "__main__":
    run()
