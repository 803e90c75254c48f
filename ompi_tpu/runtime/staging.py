"""runtime/staging: the shared host<->device staging discipline.

Hoisted from osc/device.py (the zero-copy DMA path) so every
subsystem that stages host memory into device buffers — one-sided
windows, and the pml, should it grow a staged eager path — shares ONE
alignment rule, ONE runtime aliasing probe and ONE mirror pool,
instead of growing private copies that drift.

Three pieces:

* ``STAGE_ALIGN`` / ``aligned_empty``: the CPU runtime aliases a
  64-byte-aligned host buffer on ``device_put`` instead of copying it;
  numpy only guarantees 16-byte alignment, so staging buffers are
  carved at the right offset out of an oversized allocation.
* ``runtime_zero_copy()``: probes ONCE per process whether
  ``device_put`` of an aligned host buffer ALIASES it (the CPU runtime
  does; an accelerator with discrete HBM copies).  Write-through
  mirrors and deferred-decouple puts are only sound when it does;
  otherwise callers degrade to compose-and-upload.
* ``MirrorPool``: a bounded free-list of displaced staging buffers, so
  steady-state re-mirroring (osc decoupling copies) never pays
  fresh-page faults.
"""

from __future__ import annotations

import threading
import warnings
from typing import List, Optional

import numpy as np

# donation is a no-op on the CPU backend (and on a zero-copy runtime
# the donated global may alias host mirrors); the warning would fire
# once per compiled kernel in every tier-1 run
warnings.filterwarnings(
    "ignore", message="Some donated buffers were not usable")

#: staging alignment for DMA-path uploads: the CPU runtime aliases a
#: 64-byte-aligned host buffer on device_put instead of copying it
STAGE_ALIGN = 64


def aligned_empty(nbytes: int) -> np.ndarray:
    """Uninitialized uint8 staging buffer whose data pointer is
    STAGE_ALIGN-aligned (numpy only guarantees 16)."""
    raw = np.empty(nbytes + STAGE_ALIGN, dtype=np.uint8)
    off = (-raw.ctypes.data) % STAGE_ALIGN
    return raw[off: off + nbytes]


_zero_copy: Optional[bool] = None
_probe_lock = threading.Lock()


def runtime_zero_copy() -> bool:
    """Whether device_put of an aligned host buffer ALIASES it (the
    CPU runtime does; an accelerator with discrete HBM copies).
    Probed once per process by mutating the host buffer after the put
    and reading the device view back."""
    global _zero_copy
    if _zero_copy is None:
        with _probe_lock:
            if _zero_copy is None:
                import jax
                probe = aligned_empty(STAGE_ALIGN)
                probe[:] = 0
                arr = jax.device_put(probe)
                arr.block_until_ready()
                probe[0] = 1
                _zero_copy = bool(np.asarray(arr)[0] == 1)
    return _zero_copy


class MirrorPool:
    """Bounded free-list of displaced aligned staging buffers.

    ``take`` prefers a parked buffer of sufficient capacity (sliced to
    the requested span — slicing from offset 0 preserves alignment)
    and falls back to a fresh ``aligned_empty``; ``park`` keeps at
    most ``max_buffers`` around so a pathological caller cannot hoard
    host memory.  Contents of a taken buffer are UNDEFINED — callers
    overwrite before use, exactly as with ``aligned_empty``."""

    __slots__ = ("_free", "_max", "_lock")

    def __init__(self, max_buffers: int = 8) -> None:
        self._free: List[np.ndarray] = []
        self._max = max(1, int(max_buffers))
        self._lock = threading.Lock()

    def take(self, nbytes: int) -> np.ndarray:
        with self._lock:
            for i in range(len(self._free) - 1, -1, -1):
                buf = self._free[i]
                if buf.nbytes >= nbytes:
                    del self._free[i]
                    return buf[:nbytes]
        return aligned_empty(nbytes)

    def park(self, buf: Optional[np.ndarray]) -> None:
        if buf is None:
            return
        with self._lock:
            if len(self._free) < self._max:
                self._free.append(buf)
