"""distributed — multi-process SPMD via ``jax.distributed`` on the
``-mpi-*`` flag ABI.

The tpu-native multi-host story (SURVEY.md §2 "DCN via jax.distributed"):
each process receives the reference launcher's ``--mpi-addr`` /
``--mpi-alladdr`` flags (gompirun.go:68-90 ABI), derives its process id
by the sorted-address rule (network.go:94-109), and joins one
``jax.distributed`` world; afterwards every compiled program spans all
devices of all processes and XLA's collectives carry the traffic.

Run (2 processes; on CPU each gets 4 virtual devices)::

    python -m mpi_tpu.launch.mpirun 2 examples/distributed.py

On a real multi-host TPU pod, run one copy per host with the same flags
(or via the SLURM launcher) and drop the CPU forcing env.
"""

import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

# Off-TPU demo: 4 virtual CPU devices per process. Must run before any
# jax device query.
if os.environ.get("MPI_TPU_DEMO_CPU", "1") == "1":
    from mpi_tpu.utils.platform import force_platform

    force_platform("cpu", 4)

import numpy as np  # noqa: E402

import mpi_tpu.distributed as dist  # noqa: E402


def main() -> None:
    pid = dist.initialize_from_flags()

    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from mpi_tpu.parallel import collectives as C

    mesh = dist.global_mesh()
    n = len(jax.devices())
    fn = jax.jit(jax.shard_map(
        lambda x: C.allreduce(x, "rank"), mesh=mesh,
        in_specs=P("rank"), out_specs=P("rank"), check_vma=False))

    # Each process materialises only its local rows; the global array is
    # assembled from per-process shards (the multi-host input idiom).
    gdata = np.arange(n * 4, dtype=np.float32).reshape(n, 4)
    local_rows = len(jax.local_devices())
    start = pid * local_rows
    x = jax.make_array_from_process_local_data(
        NamedSharding(mesh, P("rank")),
        gdata[start:start + local_rows])
    out = fn(x)
    want = gdata.sum(axis=0)
    for shard in out.addressable_shards:
        np.testing.assert_allclose(np.asarray(shard.data)[0], want)
    print(f"process {pid}/{jax.process_count()}: allreduce over {n} "
          f"devices ok -> {np.asarray(want).tolist()}", flush=True)


if __name__ == "__main__":
    main()
