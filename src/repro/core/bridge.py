"""The Spark↔MPI bridge — the paper's contribution, JAX-native.

The paper's central move (Fig. 1): the *same workers* that hold RDD
partitions flip into MPI ranks and run a collective program in place — no
driver round-trip. Here a "rank" is a mesh coordinate and the collective
program is a ``jax.shard_map``-ed function free to use ``jax.lax`` collectives
(psum == MPI_Allreduce, all_gather == MPI_Allgather, ppermute ==
MPI_Sendrecv, ...).

Three execution paths mirror the paper's Table I:

* :meth:`MPIBridge.run` / :meth:`MPIBridge.allreduce` — the Spark-MPI path:
  partitions live on devices, collectives run over the fabric (ICI/DCN on a
  real pod).
* :meth:`MPIBridge.driver_reduce` — the Spark driver-worker path: every
  partition funnels through the host (``collect`` + host sum) — the slow
  baseline.
* gradient-compressed allreduce (int8 + error feedback) — the
  distributed-optimization upgrade the paper points at for deep-learning
  pipelines ("gRPC/Ethernet ... area for future upgrades").

The bridge also implements the PMI contract from the paper: before the first
collective of a generation, workers ``put`` their coordinates into the KVS
and ``fence`` — on a real multi-host pod this is where
``jax.distributed.initialize`` handshakes; in-process it keeps the elastic
bookkeeping honest (see ``core/fault.py``).
"""
from __future__ import annotations

import functools
from typing import Any, Callable, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core.pmi import PMIClient, PMIServer
from repro.core.rdd import RDD, Context
from repro.utils import get_logger, make_mesh

log = get_logger(__name__)


def make_worker_mesh(devices: Sequence[jax.Device] | None = None,
                     axis_name: str = "workers") -> Mesh:
    devs = list(devices) if devices is not None else jax.devices()
    return make_mesh((len(devs),), (axis_name,), devices=devs)


class MPIBridge:
    """Runs SPMD collective programs over RDD partitions on a device mesh."""

    def __init__(self, mesh: Mesh | None = None, axis_name: str = "workers",
                 pmi: PMIServer | None = None) -> None:
        self.axis_name = axis_name
        self.mesh = mesh if mesh is not None else make_worker_mesh(axis_name=axis_name)
        if axis_name not in self.mesh.axis_names:
            raise ValueError(f"mesh lacks axis {axis_name!r}")
        self.world = int(np.prod(
            [self.mesh.shape[a] for a in self.mesh.axis_names]))
        # PMI wire-up: every rank publishes its coordinates, then fences.
        self.pmi = pmi or PMIServer(world_size=self.world)
        self._clients = [PMIClient(self.pmi, f"worker-{r}") for r in range(self.world)]
        for c in self._clients:
            c.put(f"coords/{c.rank}", str(self.mesh.devices.flat[c.rank]))
        # driver-coordinated fence: all ranks are in-process here, so the
        # driver commits the KVS once every put has landed (the threaded
        # fence path is exercised by tests/test_pmi.py)
        self.pmi.kvs().commit_all()

    # -- data plane -> compute plane ------------------------------------------
    def _stack_partitions(self, rdd: RDD) -> Any:
        """Materialize RDD partitions and stack them into leading-axis-sharded
        global arrays: partition p -> mesh worker p."""
        parts = rdd.collect_partitions()
        if len(parts) != self.world:
            raise ValueError(
                f"RDD has {len(parts)} partitions but bridge world is "
                f"{self.world}; repartition first (paper: one rank per worker)")
        stacked = jax.tree_util.tree_map(lambda *xs: np.stack(xs), *parts)
        sharding = NamedSharding(self.mesh, P(self.axis_name))
        return jax.tree_util.tree_map(
            lambda x: jax.device_put(x, sharding), stacked)

    def to_rdd(self, context: Context, tree: Any) -> RDD:
        """Compute plane -> data plane: split leading axis back to partitions."""
        parts = []
        for r in range(self.world):
            parts.append(jax.tree_util.tree_map(lambda x: np.asarray(x[r]), tree))
        return context.from_partitions(parts)

    # -- collective programs ---------------------------------------------------
    def spmd(self, fn: Callable[..., Any],
             out_specs: Any = None) -> Callable[..., Any]:
        """Wrap a per-rank function into a jitted shard_map over the bridge
        mesh. ``fn`` sees its rank's block (leading axis length 1) and may use
        any ``jax.lax`` collective with ``axis_name``."""
        in_specs = P(self.axis_name)
        out_specs = P(self.axis_name) if out_specs is None else out_specs
        # check_vma=False: a Pallas kernel in a rank's program declares its
        # outputs without the varying-axes annotation that check demands
        sm = jax.shard_map(fn, mesh=self.mesh, in_specs=in_specs,
                           out_specs=out_specs, check_vma=False)
        return jax.jit(sm)

    def run(self, rdd: RDD, fn: Callable[..., Any],
            out_specs: Any = None) -> Any:
        """Run ``fn`` as one rank per worker over the RDD's partitions."""
        stacked = self._stack_partitions(rdd)
        program = self.spmd(fn, out_specs=out_specs)
        return program(stacked)

    def allreduce(self, rdd: RDD, op: str = "sum",
                  compression: str | None = None) -> Any:
        """paper Fig. 6 ``allreduce.py``: in-place sum across workers."""
        axis = self.axis_name

        def prog(x):
            if compression == "int8":
                from repro.optim.compression import compressed_psum
                return compressed_psum(x, axis)
            if op == "sum":
                return jax.lax.psum(x, axis)
            if op == "max":
                return jax.lax.pmax(x, axis)
            if op == "mean":
                return jax.lax.pmean(x, axis)
            raise ValueError(f"unknown op {op!r}")

        out = self.run(rdd, prog)
        # Every rank holds the same reduced value; return rank 0's copy.
        return jax.tree_util.tree_map(lambda x: np.asarray(x[0]), out)

    # -- the slow path (Table I baseline) ------------------------------------
    @staticmethod
    def driver_reduce(rdd: RDD, op: str = "sum") -> Any:
        """paper Fig. 5 ``collect.py``: gather partitions to the driver and
        reduce there — the path Table I shows losing by 100×."""
        parts = rdd.collect_partitions()
        arrays = [jax.tree_util.tree_map(np.asarray, p) for p in parts]
        if op != "sum":
            raise ValueError("driver_reduce benchmark implements sum")
        acc = arrays[0]
        for a in arrays[1:]:
            acc = jax.tree_util.tree_map(np.add, acc, a)
        return acc


def rank_of(axis_name: str = "workers") -> jax.Array:
    """MPI_Comm_rank inside a collective program."""
    return jax.lax.axis_index(axis_name)


def world_of(mesh: Mesh, axis_name: str = "workers") -> int:
    return mesh.shape[axis_name]
