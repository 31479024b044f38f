"""Quickstart: the Spark-MPI pattern in one page (paper Figs. 5-6).

Builds an RDD of per-worker arrays, then reduces it two ways:
  1. the Spark driver-worker path  (collect to driver, sum on driver);
  2. the Spark-MPI path            (in-place allreduce on the worker mesh).
Both give the same numbers; Table I of the paper measures why path 2 wins
on a real fabric.

Run:  PYTHONPATH=src python examples/quickstart.py
"""
import numpy as np

from repro.core import Context, MPIBridge

N = 2_000_000                      # the paper's 2M-float payload


def main() -> None:
    ctx = Context()
    bridge = MPIBridge()           # one rank per local device
    world = bridge.world

    # Fig. 5/6: every rank holds arange(N) with a sentinel at the end
    def make_payload(rank: int) -> np.ndarray:
        buf = np.arange(N, dtype=np.float32)
        buf[-1] = 5.0
        return buf

    rdd = ctx.from_partitions([make_payload(r) for r in range(world)])

    # path 1: driver-worker (collect + sum on the driver)
    driver_sum = MPIBridge.driver_reduce(rdd)
    # path 2: Spark-MPI (MPI_Allreduce == psum on the mesh)
    mpi_sum = bridge.allreduce(rdd)

    print(f"world={world}")
    print(f"driver path : buffer[-1] = {driver_sum[-1]:.1f}")
    print(f"spark-mpi   : buffer[-1] = {np.asarray(mpi_sum)[-1]:.1f}")
    assert np.allclose(driver_sum, np.asarray(mpi_sum))
    print("identical results")


if __name__ == "__main__":
    main()
