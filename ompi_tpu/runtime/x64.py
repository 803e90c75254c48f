"""x64: 8-byte element types on the device, a stated job-level
requirement.

JAX keeps every array at 32 bits unless ``jax_enable_x64`` is on, and
it narrows without a word: ``jax.device_put`` of a float64 host buffer
hands back float32.  For an MPI library that is a wrong answer
(MPI_DOUBLE is eight bytes), so the switch is the library's, one MCA
parameter for the whole job:

    mpirun --mca mpi_device_x64 1 ...

``apply()`` runs once per process wherever a device world first
touches JAX (tools/hostrun, tools/dvm, testing.run_ranks, beside
``jaxcache.enable()``), before the first array exists: the switch is
process-wide and the rank-threads share it, so it is never flipped
per operation.  With the parameter off (the default) nothing changes
for 32-bit jobs, and ``put()``, the one way a host buffer reaches a
device inside this library, refuses an 8-byte buffer instead of
narrowing it.  Windows move bytes (uint8 on the device) and never
narrow.

The switch is not enough.  A CPU or GPU backend holds IEEE binary64;
a TPU v5e has no float64 unit, and XLA keeps each float64 there as
two float32 words: 48 significant bits in float32's exponent range,
so ``jax.device_put`` rounds 1/3 in its 49th bit, flushes 1e-300 to 0 and
turns 1e39 into inf, again without a word (measured, PERF.md section
6, PR 32).  ``native()`` probes that once per process, ``apply()``
says so on stderr, and ``put()`` refuses a float64 or complex128 host
buffer on such a device.  What such a device does hold exactly is 64
bits: MPI_DOUBLE travels there as its BIT PATTERN, a uint64 array
(``bits()``; ``np.asarray(out).view(np.float64)`` reads it back).  A
typed ``*_arr`` collective whose datatype says MPI_DOUBLE takes that
carrier on any backend and serves MAX, MIN (and, being integers, any
data movement) on it exactly, with integer compares in IEEE's total
order (datatype/device.py); a reduction that needs float64 arithmetic
(SUM, PROD) is computed on the host in binary64.
"""

from __future__ import annotations

import sys
from typing import Optional

import numpy as np

from ompi_tpu import errhandler as _eh
from ompi_tpu.mca.params import registry

_x64_var = registry.register(
    "mpi", "device", "x64", 0, int,
    help="1 = 8-byte element types are admitted on the device: "
         "jax_enable_x64 is set once per process, before the first "
         "array; integers live there at full width, float64 does only "
         "where the device holds IEEE binary64 (a TPU v5e does not: "
         "MPI_DOUBLE travels as uint64 bit patterns there, see "
         "runtime/x64.py).  0 (default) = the 32-bit JAX world; a "
         "device collective, send_arr or recv_arr handed such a buffer "
         "then raises MPI_ERR_TYPE instead of computing in 32 bits")

#: what jax narrows with x64 off (complex64 is 8 bytes wide and kept)
_NARROWED = frozenset(np.dtype(t) for t in (
    np.float64, np.int64, np.uint64, np.complex128))
#: what a device without binary64 rounds even with x64 on
_BINARY64 = frozenset(np.dtype(t) for t in (np.float64, np.complex128))
#: one value for each way the two-float32 format loses a double: 53
#: significant bits, the small exponents, the large ones
_PROBE = np.array([1.0 / 3.0, 1e-300, 1e300])

_native: Optional[bool] = None


def native() -> bool:
    """Whether a float64 on the default device is IEEE binary64: three
    doubles go there and back, once per process."""
    global _native
    if _native is None:
        import jax
        with jax.enable_x64(True):
            back = np.asarray(jax.device_put(_PROBE))
        _native = back.dtype == _PROBE.dtype \
            and back.tobytes() == _PROBE.tobytes()
    return _native


def apply() -> None:
    """Set JAX's width from ``mpi_device_x64``.  Only ever turns the
    switch on: a process whose owner enabled x64 itself keeps it.  A
    job that asks for 8-byte types on a device whose float64 is not
    binary64 is told so, once, before its first array."""
    import jax

    if not _x64_var.value:
        return
    if not jax.config.jax_enable_x64:
        jax.config.update("jax_enable_x64", True)
    if not native():
        print("ompi_tpu: mpi_device_x64 is on, but a float64 on "
              f"{jax.devices()[0].device_kind} is not IEEE binary64 (two "
              "float32 words: 48 significant bits, float32's exponent "
              "range).  float64 host buffers are refused (MPI_ERR_TYPE), "
              "not rounded; MPI_DOUBLE travels as uint64 bit patterns "
              "(ompi_tpu.runtime.x64.bits) under datatype=MPI_DOUBLE",
              file=sys.stderr, flush=True)


def check(dtype, what: str) -> None:
    """Refuse a host buffer of ``dtype`` where moving it to the device
    would change it.  Callers reach this only for an element of 8
    bytes or more (one integer compare on their own path), so the
    32-bit job pays nothing here."""
    import jax

    dt = np.dtype(dtype)
    if dt in _NARROWED and not jax.config.jax_enable_x64:
        raise _eh.MPIException(
            _eh.ERR_TYPE,
            f"{what}: {dt.name} would be narrowed to 32 bits on the "
            f"device (MPI_ERR_TYPE); run the job with "
            f"--mca mpi_device_x64 1")
    if dt in _BINARY64 and not native():
        raise _eh.MPIException(
            _eh.ERR_TYPE,
            f"{what}: {dt.name} would be rounded on this device, whose "
            f"float64 is two float32 words and not IEEE binary64 "
            f"(MPI_ERR_TYPE); hand the collective the bit patterns "
            f"(ompi_tpu.runtime.x64.bits(buf), uint64) with "
            f"datatype=MPI_DOUBLE")


def put(x, dev, what: str):
    """``jax.device_put`` that never changes a value: the one way a
    host buffer reaches a device inside this library.  An 8-byte
    element arrives at full width or the call raises MPI_ERR_TYPE."""
    import jax

    dt = getattr(x, "dtype", None)
    if dt is not None and dt.itemsize >= 8 and not isinstance(x, jax.Array):
        check(dt, what)
    return jax.device_put(x, dev)


def bits(x: np.ndarray) -> np.ndarray:
    """The carrier of MPI_DOUBLE on a device without binary64: the
    same bytes as uint64 (a view, nothing is copied)."""
    return np.ascontiguousarray(x, dtype=np.float64).view(np.uint64)
