"""Device time of the RAAR step's ``raar/far_field`` phase (the FFTs and the
Fourier error sums) per refinement iteration, in ms: chip 0's ops in that
phase (``chipbench.program_trace``) inside the ``bench.ptycho.refine``
spans, over the refinement iterations."""
from chipbench import program_trace


def read(run):
    return program_trace.phase_ms(run, "far_field")
