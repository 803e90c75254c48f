"""Cross-process device p2p under mpirun -np 2 --mca btl_tpu_chunk_bytes
4096: one message small enough to be pickled whole, one above the chunk
size (pulled in chunks).  The sender prints what btl/tpu's counters
moved by around each."""
import time

import numpy as np

import ompi_tpu
import ompi_tpu.btl.tpu  # noqa: F401  (registers btl_tpu_*)
from ompi_tpu.mca.params import registry

NAMES = ("d2d_sends", "d2d_bytes", "byref_sends", "staged_sends",
         "staged_bytes", "recv_moves")


def counters():
    pv = {p.full_name: p for p in registry.all_pvars()}
    return [pv["btl_tpu_" + n].read() for n in NAMES]


comm = ompi_tpu.init()
small = np.arange(256, dtype=np.float32)          # 1,024 B
big = np.arange(5000, dtype=np.float32)           # 20,000 B: 5 chunks
for name, x, tag in (("pickled", small, 5), ("chunked", big, 6)):
    comm.Barrier()
    before = counters()
    if comm.rank == 0:
        comm.send_arr(x, 1, tag=tag)
        eng = getattr(comm.state, "_tpu_rndv", None)
        deadline = time.monotonic() + 60
        while eng is not None and (eng.pending or eng._inflight) \
                and time.monotonic() < deadline:
            comm.state.progress.progress()
            comm.state.progress.idle_tick()
    else:
        got = np.asarray(comm.recv_arr(0, tag=tag))
        assert got.tobytes() == x.tobytes()
    comm.Barrier()
    moved = [a - b for a, b in zip(counters(), before)]
    print(f"devp2p-counters rank={comm.rank} {name} "
          + " ".join(f"{n}={v}" for n, v in zip(NAMES, moved)), flush=True)
ompi_tpu.finalize()
