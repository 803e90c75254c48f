"""Device-collective smoke program for the hybrid launch model.

Run (one app-shell process owning every rank as a chip-driving
thread — the deployment that makes coll/tpu reachable from mpirun):

    python -m ompi_tpu.tools.mpirun -np 8 --ranks-per-proc all \
        examples/device_allreduce.py

Each rank allreduces / reduce-scatters a device-resident array, then
rank 0 prints the engagement counters: the collectives must have been
served by a device module — coll/tpu (one rank per device, XLA mesh
collectives) or coll/hbm (ranks sharing one chip, stacked kernels) —
and none by the host-staged arr_host fallback.
"""
import numpy as np

import ompi_tpu
from ompi_tpu.mca.params import registry
from ompi_tpu.op import op as mpi_op

comm = ompi_tpu.init()
rank, size = comm.rank, comm.size

import jax
import jax.numpy as jnp

x = jax.device_put(jnp.full((size * 4,), float(rank + 1), jnp.float32),
                   comm.device)
r = comm.allreduce_arr(x, mpi_op.SUM)
rs = comm.reduce_scatter_arr(x, mpi_op.SUM)
expect = sum(range(1, size + 1))
assert float(np.asarray(r)[0]) == expect, (rank, np.asarray(r)[0])
assert float(np.asarray(rs)[0]) == expect

# sub-communicator: even/odd split still offloads on its sub-mesh
sub = comm.split(rank % 2)
sr = sub.allreduce_arr(x, mpi_op.MAX)
assert float(np.asarray(sr)[0]) == float(size - 2 + (rank % 2) + 1)

comm.Barrier()  # every rank's collectives are counted
pv = {p.full_name: p.read() for p in registry.all_pvars()}
tpu = pv.get("coll_tpu_offloaded_collectives", 0)
hbm = pv.get("coll_hbm_offloaded_collectives", 0)
staged = pv.get("coll_arr_host_staged_collectives", 0)
# one atomic write per line: every rank is a thread of ONE app-shell
# process, and print()'s separate text/newline writes interleave
# across ranks on the shared stdout
import sys
if rank == 0:
    sys.stdout.write(f"coll_tpu_offloaded_collectives={tpu}\n"
                     f"coll_hbm_offloaded_collectives={hbm}\n"
                     f"coll_arr_host_staged_collectives={staged}\n")
    sys.stdout.flush()
    assert tpu + hbm > 0, "device collectives were not offloaded!"
    assert staged == 0, "device collectives were staged through the host!"
sys.stdout.write(f"rank {rank} ok\n")
sys.stdout.flush()
ompi_tpu.finalize()
