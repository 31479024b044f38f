"""The one kernel-dispatch rule shared by every Pallas op wrapper."""
from __future__ import annotations

import jax

# A v5e's compiler gives a Pallas kernel 16 MiB of its 128 MiB of VMEM
# unless the kernel asks for more (``vmem_limit_bytes``).
DEFAULT_VMEM_BYTES = 16 * 2**20


def kernel_mode(use_pallas: bool | None = None) -> tuple[bool, bool]:
    """``(run the Pallas kernel, run it in interpret mode)``.

    ``use_pallas=None`` picks the kernel on a TPU and the pure-jnp reference
    elsewhere; ``True``/``False`` force one or the other. Interpret mode is
    the CPU test path only: on a TPU a kernel always runs compiled.
    """
    on_tpu = jax.default_backend() == "tpu"
    return (on_tpu if use_pallas is None else use_pallas), not on_tpu
