"""Device time of the RAAR step's ``raar/modulus`` phase (the modulus
projection) per refinement iteration, in ms: chip 0's ops in that phase
(``chipbench.program_trace``) inside the ``bench.ptycho.refine`` spans, over
the refinement iterations."""
from chipbench import program_trace


def read(run):
    return program_trace.phase_ms(run, "modulus")
