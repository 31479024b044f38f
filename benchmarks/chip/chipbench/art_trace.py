"""The ART sweep's device time in a traced run's window, from the program's
own marks (``chipbench.program_trace``): chip 0's ops whose scope path holds
the operator's ``art/sweep`` scope, and the ``art_sweep`` kernel by its
name, where the compiler left an op without a scope."""
from __future__ import annotations

import re
from typing import Any

from chipbench import program_trace, xplane

SCOPE = re.compile(r"(?:^|/)art/sweep(?:/|:|$)")
KERNEL = "art_sweep"


def is_sweep(op: program_trace.DeviceOp) -> bool:
    return bool(SCOPE.search(op.scope)) or xplane.op_name(op.name) == KERNEL


def seconds(run: Any) -> float | None:
    """Device seconds of chip 0's sweep ops inside the window; None where
    the trace has none (a program without the scope or the kernel)."""
    prog = program_trace.for_run(run)
    if prog is None:
        return None
    a, b = run.trace.window
    ns = sum(max(0.0, min(op.end_ns, b) - max(op.start_ns, a))
             for op in prog.ops if is_sweep(op))
    return ns * 1e-9 or None
