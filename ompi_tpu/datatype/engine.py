"""Datatype engine: MPI derived datatypes as strided-run descriptors.

Re-design of the reference's two-level datatype stack
(opal/datatype/opal_datatype.h:50-102 — 25 predefined base types and
(type, count, disp) descriptor vectors — plus ompi/datatype/* MPI
constructors).  Instead of the reference's loop/element bytecode
interpreted by a state machine, a committed datatype here is a flat
vector of **runs**:

    Run(disp, dtype, count, stride, nblocks)
      = for b in 0..nblocks-1: `count` contiguous elements of `dtype`
        at byte offset `disp + b*stride`

Regular nesting (contiguous-of-vector etc.) is collapsed at build time
(the analog of opal_datatype_optimize.c), so the host pack path is a
handful of vectorized numpy strided copies, and the device pack path
is static slices of a regular layout, or a single gather with
precomputed indices for any other (datatype/device.py) — both
TPU/XLA-friendly shapes of the same descriptor program.
"""

from __future__ import annotations

import numpy as np
from dataclasses import dataclass
from typing import List, Optional, Sequence, Tuple


@dataclass(frozen=True)
class Run:
    disp: int        # byte displacement of block 0
    dtype: np.dtype  # primitive element type
    count: int       # contiguous elements per block
    stride: int      # bytes between successive block starts
    nblocks: int     # number of blocks

    @property
    def block_bytes(self) -> int:
        return self.count * self.dtype.itemsize

    @property
    def packed_bytes(self) -> int:
        return self.block_bytes * self.nblocks

    def span(self) -> Tuple[int, int]:
        """(min_byte, max_byte_exclusive) touched in the typed buffer."""
        lo = self.disp
        hi = self.disp + (self.nblocks - 1) * self.stride + self.block_bytes
        if self.stride < 0:
            lo = self.disp + (self.nblocks - 1) * self.stride
            hi = self.disp + self.block_bytes
        return lo, hi


def _align(off: int, alignment: int) -> int:
    if alignment <= 1:
        return off
    return (off + alignment - 1) // alignment * alignment


class Datatype:
    """An MPI datatype.  Immutable once committed; constructors return
    new instances.  ``runs`` describe one element; consecutive elements
    are laid out ``extent`` bytes apart."""

    _next_id = [0]

    def __init__(self, runs: List[Run], lb: int, ub: int, name: str = "",
                 base: Optional[np.dtype] = None,
                 envelope: Optional[Tuple] = None) -> None:
        self.runs = runs
        self.lb = lb
        self.ub = ub
        self.name = name
        self.base = base  # set for predefined types
        # (combiner, integers, addresses, datatypes) — MPI_Type_get_contents
        # analog of the reference's args caching (ompi/datatype/ompi_datatype_args.c)
        self.envelope = envelope or ("NAMED", [], [], [])
        self.committed = False
        self.id = Datatype._next_id[0]
        Datatype._next_id[0] += 1

    # -- queries ---------------------------------------------------------
    @property
    def size(self) -> int:
        """Packed size in bytes (MPI_Type_size).  Cached: this sits on
        the per-message hot path and runs never change after commit
        (commit() invalidates)."""
        s = self.__dict__.get("_size")
        if s is None:
            s = sum(r.packed_bytes for r in self.runs)
            self.__dict__["_size"] = s
        return s

    @property
    def extent(self) -> int:
        return self.ub - self.lb

    @property
    def true_lb(self) -> int:
        if not self.runs:
            return 0
        return min(r.span()[0] for r in self.runs)

    @property
    def true_ub(self) -> int:
        if not self.runs:
            return 0
        return max(r.span()[1] for r in self.runs)

    @property
    def true_extent(self) -> int:
        return self.true_ub - self.true_lb

    @property
    def is_contiguous(self) -> bool:
        """True when `count` elements occupy count*size contiguous
        bytes.  Cached (hot path; see ``size``)."""
        c = self.__dict__.get("_contig")
        if c is None:
            if len(self.runs) != 1:
                c = False
            else:
                r = self.runs[0]
                c = ((r.nblocks == 1 or r.stride == r.block_bytes)
                     and r.disp == self.lb and self.extent == self.size)
            self.__dict__["_contig"] = c
        return c

    @property
    def is_predefined(self) -> bool:
        return self.base is not None

    @property
    def alignment(self) -> int:
        if not self.runs:
            return 1
        return max(r.dtype.alignment for r in self.runs)

    def commit(self) -> "Datatype":
        if not self.committed:
            self.runs = _optimize(self.runs)
            self.committed = True
            self.__dict__.pop("_size", None)
            self.__dict__.pop("_contig", None)
        return self

    def free(self) -> None:  # handles are GC'd; parity no-op
        pass

    def get_envelope(self):
        c, i, a, d = self.envelope
        return (len(i), len(a), len(d), c)

    def get_contents(self):
        return self.envelope

    def __repr__(self) -> str:
        return f"Datatype({self.name or self.envelope[0]}, size={self.size})"

    # -- element expansion ----------------------------------------------
    def runs_for_count(self, count: int) -> List[Run]:
        """Runs describing `count` consecutive elements of this type."""
        if count == 1:
            return self.runs
        if self.is_contiguous and len(self.runs) == 1:
            r = self.runs[0]
            total = r.count * r.nblocks * count
            return [Run(r.disp, r.dtype, total, total * r.dtype.itemsize, 1)]
        out: List[Run] = []
        ext = self.extent
        if len(self.runs) == 1:
            r = self.runs[0]
            # extend a single strided run across elements when regular
            if r.stride * r.nblocks == ext:
                return [Run(r.disp, r.dtype, r.count, r.stride,
                            r.nblocks * count)]
        # pack order is element-major (the MPI typemap repeated)
        for e in range(count):
            off = e * ext
            out += [Run(r.disp + off, r.dtype, r.count, r.stride, r.nblocks)
                    for r in self.runs]
        return _optimize(out)


def _optimize(runs: List[Run]) -> List[Run]:
    """Merge adjacent compatible runs (opal_datatype_optimize.c analog)."""
    out: List[Run] = []
    for r in runs:
        if r.nblocks == 0 or r.count == 0:
            continue
        # normalize single-block to stride == block_bytes
        if r.nblocks == 1 and r.stride != r.block_bytes:
            r = Run(r.disp, r.dtype, r.count, r.block_bytes, 1)
        if out:
            p = out[-1]
            if (p.dtype == r.dtype and p.nblocks == 1 and r.nblocks == 1
                    and r.disp == p.disp + p.block_bytes):
                out[-1] = Run(p.disp, p.dtype, p.count + r.count,
                              (p.count + r.count) * p.dtype.itemsize, 1)
                continue
            # fold equally-spaced identical blocks into one strided run
            if (p.dtype == r.dtype and p.count == r.count
                    and p.block_bytes != 0
                    and r.nblocks == 1 and p.stride != 0
                    and r.disp == p.disp + p.nblocks * p.stride):
                out[-1] = Run(p.disp, p.dtype, p.count, p.stride, p.nblocks + 1)
                continue
        out.append(r)
    return out


# ---------------------------------------------------------------------------
# Predefined datatypes (ref: ompi/datatype/ompi_datatype_internal.h tables)
# ---------------------------------------------------------------------------

_predefined: dict = {}


def _make_predefined(name: str, np_dtype) -> Datatype:
    dt = np.dtype(np_dtype)
    d = Datatype([Run(0, dt, 1, dt.itemsize, 1)], 0, dt.itemsize,
                 name=name, base=dt)
    d.commit()
    _predefined[name] = d
    return d


BYTE = _make_predefined("MPI_BYTE", np.uint8)
PACKED = _make_predefined("MPI_PACKED", np.uint8)
CHAR = _make_predefined("MPI_CHAR", np.int8)
SIGNED_CHAR = _make_predefined("MPI_SIGNED_CHAR", np.int8)
UNSIGNED_CHAR = _make_predefined("MPI_UNSIGNED_CHAR", np.uint8)
WCHAR = _make_predefined("MPI_WCHAR", np.int32)
SHORT = _make_predefined("MPI_SHORT", np.int16)
UNSIGNED_SHORT = _make_predefined("MPI_UNSIGNED_SHORT", np.uint16)
INT = _make_predefined("MPI_INT", np.int32)
UNSIGNED = _make_predefined("MPI_UNSIGNED", np.uint32)
LONG = _make_predefined("MPI_LONG", np.int64)
UNSIGNED_LONG = _make_predefined("MPI_UNSIGNED_LONG", np.uint64)
LONG_LONG = _make_predefined("MPI_LONG_LONG", np.int64)
UNSIGNED_LONG_LONG = _make_predefined("MPI_UNSIGNED_LONG_LONG", np.uint64)
INT8_T = _make_predefined("MPI_INT8_T", np.int8)
INT16_T = _make_predefined("MPI_INT16_T", np.int16)
INT32_T = _make_predefined("MPI_INT32_T", np.int32)
INT64_T = _make_predefined("MPI_INT64_T", np.int64)
UINT8_T = _make_predefined("MPI_UINT8_T", np.uint8)
UINT16_T = _make_predefined("MPI_UINT16_T", np.uint16)
UINT32_T = _make_predefined("MPI_UINT32_T", np.uint32)
UINT64_T = _make_predefined("MPI_UINT64_T", np.uint64)
FLOAT = _make_predefined("MPI_FLOAT", np.float32)
DOUBLE = _make_predefined("MPI_DOUBLE", np.float64)
LONG_DOUBLE = _make_predefined("MPI_LONG_DOUBLE", np.longdouble)
C_BOOL = _make_predefined("MPI_C_BOOL", np.bool_)
C_FLOAT_COMPLEX = _make_predefined("MPI_C_FLOAT_COMPLEX", np.complex64)
C_DOUBLE_COMPLEX = _make_predefined("MPI_C_DOUBLE_COMPLEX", np.complex128)
AINT = _make_predefined("MPI_AINT", np.int64)
OFFSET = _make_predefined("MPI_OFFSET", np.int64)
COUNT = _make_predefined("MPI_COUNT", np.int64)
# TPU-native additions (no reference analog: the reference has no
# accelerator dtypes of its own)
try:
    import ml_dtypes  # shipped with jax

    BFLOAT16 = _make_predefined("MPI_BFLOAT16", ml_dtypes.bfloat16)
    FLOAT16 = _make_predefined("MPI_FLOAT16", np.float16)
except Exception:  # pragma: no cover
    BFLOAT16 = None
    FLOAT16 = _make_predefined("MPI_FLOAT16", np.float16)


def _make_pair(name: str, first, second) -> Datatype:
    """MAXLOC/MINLOC pair types as numpy structured dtypes
    (ref: ompi_datatype_internal.h FLOAT_INT et al.)."""
    dt = np.dtype([("v", first), ("i", second)], align=True)
    d = Datatype([Run(0, dt, 1, dt.itemsize, 1)], 0, dt.itemsize,
                 name=name, base=dt)
    d.commit()
    _predefined[name] = d
    return d


FLOAT_INT = _make_pair("MPI_FLOAT_INT", np.float32, np.int32)
DOUBLE_INT = _make_pair("MPI_DOUBLE_INT", np.float64, np.int32)
LONG_INT = _make_pair("MPI_LONG_INT", np.int64, np.int32)
SHORT_INT = _make_pair("MPI_SHORT_INT", np.int16, np.int32)
TWOINT = _make_pair("MPI_2INT", np.int32, np.int32)
LONG_DOUBLE_INT = _make_pair("MPI_LONG_DOUBLE_INT", np.longdouble, np.int32)

# Fortran names mapped onto C layouts (ref: ompi_datatype_internal.h)
INTEGER = INT
REAL = FLOAT
DOUBLE_PRECISION = DOUBLE
COMPLEX = C_FLOAT_COMPLEX
DOUBLE_COMPLEX = C_DOUBLE_COMPLEX
LOGICAL = INT
CHARACTER = CHAR

LB_MARKER = Datatype([], 0, 0, name="MPI_LB")
UB_MARKER = Datatype([], 0, 0, name="MPI_UB")
DATATYPE_NULL = Datatype([], 0, 0, name="MPI_DATATYPE_NULL")


_canonical = {}
for _d in (BYTE, CHAR, UNSIGNED_CHAR, SHORT, UNSIGNED_SHORT, INT, UNSIGNED,
           LONG, UNSIGNED_LONG, FLOAT, DOUBLE, LONG_DOUBLE, C_BOOL,
           C_FLOAT_COMPLEX, C_DOUBLE_COMPLEX, FLOAT16):
    _canonical.setdefault(_d.base, _d)
if BFLOAT16 is not None:
    _canonical.setdefault(BFLOAT16.base, BFLOAT16)


def from_numpy_dtype(dt) -> Datatype:
    """Map a numpy/jax dtype to the canonical predefined Datatype."""
    dt = np.dtype(dt)
    d = _canonical.get(dt)
    if d is not None:
        return d
    for cand in _predefined.values():
        if cand.base is not None and cand.base == dt:
            return cand
    raise KeyError(f"no MPI datatype for numpy dtype {dt}")


def predefined_by_name(name: str) -> Datatype:
    return _predefined[name]


# ---------------------------------------------------------------------------
# Constructors (ref: ompi/mpi/c/type_* and ompi/datatype/ompi_datatype_create_*)
# ---------------------------------------------------------------------------

def dup(oldtype: Datatype) -> Datatype:
    d = Datatype(list(oldtype.runs), oldtype.lb, oldtype.ub,
                 name=oldtype.name,
                 envelope=("DUP", [], [], [oldtype]))
    return d


def contiguous(count: int, oldtype: Datatype) -> Datatype:
    runs = oldtype.runs_for_count(count)
    lb = oldtype.lb
    ub = oldtype.lb + count * oldtype.extent
    return Datatype(runs, lb, ub,
                    envelope=("CONTIGUOUS", [count], [], [oldtype]))


def vector(count: int, blocklength: int, stride: int,
           oldtype: Datatype) -> Datatype:
    """stride in elements of oldtype."""
    return _hvector(count, blocklength, stride * oldtype.extent, oldtype,
                    envelope=("VECTOR", [count, blocklength, stride], [],
                              [oldtype]))


def hvector(count: int, blocklength: int, stride_bytes: int,
            oldtype: Datatype) -> Datatype:
    return _hvector(count, blocklength, stride_bytes, oldtype,
                    envelope=("HVECTOR", [count, blocklength], [stride_bytes],
                              [oldtype]))


def _hvector(count, blocklength, stride_bytes, oldtype, envelope):
    block = oldtype.runs_for_count(blocklength)
    runs: List[Run] = []
    if len(block) == 1 and block[0].nblocks == 1:
        b = block[0]
        runs = [Run(b.disp, b.dtype, b.count, stride_bytes, count)]
    else:
        for i in range(count):
            off = i * stride_bytes
            runs += [Run(r.disp + off, r.dtype, r.count, r.stride, r.nblocks)
                     for r in block]
        runs = _optimize(runs)
    lb = oldtype.lb + min(0, (count - 1) * stride_bytes)
    ub = (oldtype.lb + max((count - 1) * stride_bytes, 0)
          + blocklength * oldtype.extent)
    return Datatype(runs, lb, ub, envelope=envelope)


def indexed(blocklengths: Sequence[int], displacements: Sequence[int],
            oldtype: Datatype) -> Datatype:
    disps = [d * oldtype.extent for d in displacements]
    return _hindexed(blocklengths, disps, oldtype,
                     envelope=("INDEXED",
                               [len(blocklengths), *blocklengths,
                                *displacements], [], [oldtype]))


def hindexed(blocklengths: Sequence[int], displacements: Sequence[int],
             oldtype: Datatype) -> Datatype:
    return _hindexed(blocklengths, list(displacements), oldtype,
                     envelope=("HINDEXED",
                               [len(blocklengths), *blocklengths],
                               list(displacements), [oldtype]))


def _hindexed(blocklengths, byte_disps, oldtype, envelope):
    runs: List[Run] = []
    lb = None
    ub = None
    for bl, bd in zip(blocklengths, byte_disps):
        if bl == 0:
            continue
        block = oldtype.runs_for_count(bl)
        runs += [Run(r.disp + bd, r.dtype, r.count, r.stride, r.nblocks)
                 for r in block]
        blo = oldtype.lb + bd
        bhi = oldtype.lb + bd + bl * oldtype.extent
        lb = blo if lb is None else min(lb, blo)
        ub = bhi if ub is None else max(ub, bhi)
    if lb is None:
        lb = ub = 0
    return Datatype(_optimize(runs), lb, ub, envelope=envelope)


def indexed_block(blocklength: int, displacements: Sequence[int],
                  oldtype: Datatype) -> Datatype:
    d = indexed([blocklength] * len(displacements), displacements, oldtype)
    d.envelope = ("INDEXED_BLOCK",
                  [len(displacements), blocklength, *displacements], [],
                  [oldtype])
    return d


def hindexed_block(blocklength: int, displacements: Sequence[int],
                   oldtype: Datatype) -> Datatype:
    d = hindexed([blocklength] * len(displacements), displacements, oldtype)
    d.envelope = ("HINDEXED_BLOCK", [len(displacements), blocklength],
                  list(displacements), [oldtype])
    return d


def struct(blocklengths: Sequence[int], displacements: Sequence[int],
           types: Sequence[Datatype]) -> Datatype:
    runs: List[Run] = []
    lb = None
    ub = None
    explicit_lb = None
    explicit_ub = None
    align = 1
    for bl, bd, t in zip(blocklengths, displacements, types):
        if t is LB_MARKER:
            explicit_lb = bd if explicit_lb is None else min(explicit_lb, bd)
            continue
        if t is UB_MARKER:
            explicit_ub = bd if explicit_ub is None else max(explicit_ub, bd)
            continue
        if bl == 0:
            continue
        align = max(align, t.alignment)
        block = t.runs_for_count(bl)
        runs += [Run(r.disp + bd, r.dtype, r.count, r.stride, r.nblocks)
                 for r in block]
        blo = t.lb + bd
        bhi = t.lb + bd + bl * t.extent
        lb = blo if lb is None else min(lb, blo)
        ub = bhi if ub is None else max(ub, bhi)
    if lb is None:
        lb = ub = 0
    if explicit_lb is not None:
        lb = explicit_lb
    if explicit_ub is not None:
        ub = explicit_ub
    else:
        # epsilon alignment padding, matching C struct layout
        ub = lb + _align(ub - lb, align)
    return Datatype(_optimize(runs), lb, ub,
                    envelope=("STRUCT", [len(blocklengths), *blocklengths],
                              list(displacements), list(types)))


ORDER_C = 56
ORDER_FORTRAN = 57


def subarray(sizes: Sequence[int], subsizes: Sequence[int],
             starts: Sequence[int], order: int, oldtype: Datatype) -> Datatype:
    """N-dim subarray (ref: ompi/datatype/ompi_datatype_create_subarray.c:
    built as nested vectors from the innermost dimension out)."""
    ndims = len(sizes)
    if order == ORDER_FORTRAN:
        sizes = list(reversed(sizes))
        subsizes = list(reversed(subsizes))
        starts = list(reversed(starts))
    # innermost (last) dimension: contiguous run of subsizes[-1]
    d = contiguous(subsizes[-1], oldtype) if subsizes[-1] != 1 else dup(oldtype)
    extent_inner = oldtype.extent * sizes[-1]
    for dim in range(ndims - 2, -1, -1):
        d = hvector(subsizes[dim], 1, extent_inner, d)
        extent_inner *= sizes[dim]
    # absolute offset of the start corner
    off = 0
    mult = oldtype.extent
    for dim in range(ndims - 1, -1, -1):
        off += starts[dim] * mult
        mult *= sizes[dim]
    full = np.prod(sizes) * oldtype.extent
    runs = [Run(r.disp + off, r.dtype, r.count, r.stride, r.nblocks)
            for r in d.runs]
    out = Datatype(_optimize(runs), 0, int(full),
                   envelope=("SUBARRAY",
                             [len(sizes), *sizes, *subsizes, *starts, order],
                             [], [oldtype]))
    return out


DISTRIBUTE_BLOCK = 121
DISTRIBUTE_CYCLIC = 122
DISTRIBUTE_NONE = 123
DISTRIBUTE_DFLT_DARG = -49767


def darray(size: int, rank: int, gsizes: Sequence[int],
           distribs: Sequence[int], dargs: Sequence[int],
           psizes: Sequence[int], order: int, oldtype: Datatype) -> Datatype:
    """HPF-style distributed array type
    (ref: ompi/datatype/ompi_datatype_create_darray.c).  Built by
    per-dimension recursion — innermost dimension first — where each
    level selects this rank's blocks along that dimension (hindexed
    over the previous level's type) and resizes to the dimension's
    full global span, so BLOCK, CYCLIC(b) and NONE all share one
    mechanism."""
    ndims = len(gsizes)
    # rank → grid coords is row-major regardless of `order` (MPI-3.1
    # §4.1.4: "the process grid is always assumed to be row-major";
    # matches ompi_datatype_create_darray.c)
    coords = []
    r = rank
    for d in range(ndims - 1, -1, -1):
        coords.insert(0, r % psizes[d])
        r //= psizes[d]
    t = oldtype
    dims_iter = range(ndims - 1, -1, -1) if order == ORDER_C \
        else range(ndims)
    for d in dims_iter:
        ext = t.extent
        g, p, c = gsizes[d], psizes[d], coords[d]
        if distribs[d] == DISTRIBUTE_NONE or p == 1:
            lens, offs = [g], [0]
        elif distribs[d] == DISTRIBUTE_BLOCK:
            b = dargs[d]
            if b == DISTRIBUTE_DFLT_DARG:
                b = -(-g // p)
            s = min(c * b, g)
            lens, offs = [max(0, min(s + b, g) - s)], [s * ext]
        elif distribs[d] == DISTRIBUTE_CYCLIC:
            b = dargs[d]
            if b == DISTRIBUTE_DFLT_DARG:
                b = 1
            total_blocks = -(-g // b)
            lens, offs = [], []
            for tb in range(c, total_blocks, p):
                lens.append(min(b, g - tb * b))
                offs.append(tb * b * ext)
        else:
            raise ValueError(f"unknown distribution {distribs[d]}")
        lens = [x for x in lens if x > 0] or [0]
        offs = offs[:len(lens)] if lens != [0] else [0]
        t = hindexed(lens, offs, t)
        t = resized(t, 0, g * ext)
    t.envelope = ("DARRAY", [size, rank, ndims, *gsizes, *distribs,
                             *dargs, *psizes, order], [], [oldtype])
    return t


def resized(oldtype: Datatype, lb: int, extent: int) -> Datatype:
    return Datatype(list(oldtype.runs), lb, lb + extent,
                    envelope=("RESIZED", [], [lb, extent], [oldtype]))
