"""Paper Table I: AllReduce — driver-worker vs Spark-MPI vs slow transport.

Measured on this container: the driver-collect path (threads + host sum)
and the psum path (8 virtual devices in a subprocess, exercising the real
shard_map collective program). Derived: the communication-model times for
2/4/8/10 nodes on the paper's three transports (Ethernet driver-worker,
InfiniBand MPI, Ethernet MPI) and on the TPU target (ICI psum) — the
apples-to-apples reproduction of Table I's shape: in-place collectives beat
driver funnels by ~2 orders of magnitude.
"""
from __future__ import annotations

import json
import os
import subprocess
import sys

import numpy as np

from benchmarks.common import (ETHERNET_BW, IB_BW, ICI_BW, allreduce_model_time,
                               emit, gather_model_time, time_call)

N = 2_000_000          # paper payload: 2M float32
BYTES = N * 4

_SUBPROC = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import sys, json, time
sys.path.insert(0, "src")
import numpy as np
from repro.core import Context, MPIBridge
ctx = Context()
bridge = MPIBridge()
parts = [np.arange(2_000_000, dtype=np.float32) for _ in range(bridge.world)]
rdd = ctx.from_partitions(parts)
bridge.allreduce(rdd)                      # warmup/compile
stacked = bridge._stack_partitions(rdd)
prog = bridge.spmd(lambda x: __import__("jax").lax.psum(x, "workers"))
prog(stacked)[0].block_until_ready()
times = []
for _ in range(5):
    t0 = time.perf_counter()
    prog(stacked)[0].block_until_ready()
    times.append(time.perf_counter() - t0)
print(json.dumps(sorted(times)[2]))
"""


def run() -> None:
    from repro.core import Context, MPIBridge

    ctx = Context()
    world = 8
    parts = [np.arange(N, dtype=np.float32) for _ in range(world)]
    rdd = ctx.from_partitions(parts)

    t_driver = time_call(lambda: MPIBridge.driver_reduce(rdd))
    emit("allreduce/driver_collect_8p_cpu", t_driver,
         "measured: collect+sum on driver, 8 partitions")

    # the child's 8 devices are virtual CPU ones: held to the CPU, it never
    # competes with this process for an accelerator
    out = subprocess.run([sys.executable, "-c", _SUBPROC],
                         capture_output=True, text=True,
                         env=dict(os.environ, JAX_PLATFORMS="cpu"),
                         cwd=os.path.dirname(os.path.dirname(
                             os.path.abspath(__file__))))
    if out.returncode == 0:
        t_psum = json.loads(out.stdout.strip().splitlines()[-1])
        emit("allreduce/psum_8dev_cpu", t_psum,
             "measured: shard_map psum, 8 virtual devices")
    else:
        emit("allreduce/psum_8dev_cpu", float("nan"),
             "subprocess failed: " + out.stderr.strip()[-120:])

    # Table I reproduction via the communication model
    for n in (2, 4, 8, 10):
        t_spark = gather_model_time(BYTES, n, ETHERNET_BW) + N * n / 2e9
        t_mpi_ib = allreduce_model_time(BYTES, n, IB_BW)
        t_mpi_eth = allreduce_model_time(BYTES, n, ETHERNET_BW)
        t_tpu = allreduce_model_time(BYTES, n, ICI_BW, latency=1e-6)
        emit(f"allreduce/model_{n}nodes", t_mpi_ib,
             f"driver/eth={t_spark:.4f}s mpi/ib={t_mpi_ib:.4f}s "
             f"mpi/eth={t_mpi_eth:.4f}s tpu/ici={t_tpu:.6f}s "
             f"(paper: {dict([(2,(0.20,0.0036,0.07)),(4,(0.37,0.0049,0.14)),(8,(0.95,0.0060,0.31)),(10,(1.12,0.0097,0.36))])[n]})")


if __name__ == "__main__":
    run()
