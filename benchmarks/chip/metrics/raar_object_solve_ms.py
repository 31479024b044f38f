"""Device time of the RAAR step's ``raar/object_solve`` phase (the object
update: its products, scatter-adds and divide) per refinement iteration, in
ms: chip 0's ops in that phase (``chipbench.program_trace``) inside the
``bench.ptycho.refine`` spans, over the refinement iterations."""
from chipbench import program_trace


def read(run):
    return program_trace.phase_ms(run, "object_solve")
