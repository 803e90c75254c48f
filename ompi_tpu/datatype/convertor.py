"""Convertor: resumable, positionable pack/unpack over a datatype.

Re-design of the reference convertor state machine
(opal/datatype/opal_convertor.h:69-137 — dt_stack_t explicit stack,
opal_convertor_pack/unpack, prepare_for_send/recv;
opal/datatype/opal_datatype_position.c for repositioning;
opal_datatype_checksum.h for checksummed variants;
opal_copy_functions_heterogeneous.c for endian conversion, which here
is the external32 mode).

Because committed datatypes are flat run vectors (see engine.py), the
"stack" collapses to (run index, block index, byte-within-block), and
whole-run copies vectorize through numpy strided views — the same
descriptor program the device path turns into static slices, or one
XLA gather where the layout is irregular.
"""

from __future__ import annotations

import zlib
from typing import List, Optional, Tuple, Union

import numpy as np

from .engine import Datatype, Run

Buffer = Union[np.ndarray, bytearray, memoryview, bytes]


def _byte_view(buf: Buffer, writable: bool) -> np.ndarray:
    """A flat uint8 view of `buf` without copying."""
    if isinstance(buf, np.ndarray):
        if buf.ndim == 0:
            buf = buf.reshape(1)
        if not buf.flags.c_contiguous:
            raise ValueError("buffer must be C-contiguous")
        if writable and not buf.flags.writeable:
            raise ValueError("buffer is read-only")
        return buf.view(np.uint8).reshape(-1)
    mv = memoryview(buf).cast("B")
    if writable and mv.readonly:
        raise ValueError("buffer is read-only")
    return np.frombuffer(mv, dtype=np.uint8) if mv.readonly \
        else np.asarray(mv)


class Convertor:
    """Packs/unpacks `count` elements of `datatype` living in `buf`.

    Modes: native (memcpy semantics) or external32 (big-endian
    canonical, MPI_Pack_external).  Optional crc32 checksum over the
    packed stream (the reference's *_checksum convertor variants).
    """

    def __init__(self, datatype: Datatype, count: int, buf: Buffer,
                 external32: bool = False, checksum: bool = False,
                 offset: int = 0) -> None:
        """`offset`: byte position within `buf` that plays the role of
        the MPI buffer pointer — datatypes with negative lb/displacements
        address bytes before it (C pointers can; numpy views cannot, so
        the origin is explicit here)."""
        self.datatype = datatype
        self.count = count
        self.external32 = external32
        self.checksum = checksum
        self.offset = offset
        self.crc = 0
        self.runs: List[Run] = datatype.runs_for_count(count) if count else []
        self._cum: List[int] = []
        total = 0
        for r in self.runs:
            total += r.packed_bytes
            self._cum.append(total)
        self.packed_size = total
        self.position = 0
        self._buf = buf

    # -- internals -------------------------------------------------------
    def _locate(self, pos: int) -> Tuple[int, int, int]:
        """(run_idx, block_idx, byte_in_block) for packed offset pos."""
        lo = 0
        for i, cum in enumerate(self._cum):
            if pos < cum:
                within = pos - lo
                bb = self.runs[i].block_bytes
                return i, within // bb, within % bb
            lo = cum
        return len(self.runs), 0, 0

    def _check_span(self, base: np.ndarray, r: Run) -> int:
        """Bounds-check run r against the buffer; returns its absolute
        disp.  as_strided performs no checking of its own, so this is
        the memory-safety gate for both pack and unpack."""
        disp = self.offset + r.disp
        slo, shi = r.span()
        if self.offset + slo < 0:
            raise IndexError(
                "datatype addresses bytes before the buffer origin; "
                "pass offset= to Convertor")
        if self.offset + shi > len(base):
            raise IndexError(
                f"datatype spans {self.offset + shi} bytes but buffer "
                f"has only {len(base)}")
        return disp

    @staticmethod
    def _sub_run(r: Run, plo: int, phi: int):
        """Restrict run r to packed-byte range [plo, phi): returns
        (sub_run, byte_lo, byte_hi) where byte_* slice the sub-run's
        packed image.  Keeps pipelined chunking O(chunk), not O(run)."""
        bb = r.block_bytes
        b0 = plo // bb
        b1 = (phi - 1) // bb
        sub = Run(r.disp + b0 * r.stride, r.dtype, r.count, r.stride,
                  b1 - b0 + 1)
        return sub, plo - b0 * bb, phi - b0 * bb

    def _run_bytes(self, base: np.ndarray, r: Run) -> np.ndarray:
        """Packed byte image of a whole run (view-free copy)."""
        disp = self._check_span(base, r)
        if r.stride < 0:
            parts = [base[disp + b * r.stride:
                          disp + b * r.stride + r.block_bytes]
                     for b in range(r.nblocks)]
            out = np.concatenate(parts)
        elif r.nblocks == 1 or r.stride == r.block_bytes:
            out = base[disp:disp + r.packed_bytes].copy()
        else:
            v = np.lib.stride_tricks.as_strided(
                base[disp:], shape=(r.nblocks, r.block_bytes),
                strides=(r.stride, 1))
            out = np.ascontiguousarray(v).reshape(-1)
        if self.external32 and r.dtype.itemsize > 1:
            arr = out.view(r.dtype)
            out = arr.astype(r.dtype.newbyteorder(">")).view(np.uint8)
        return out

    def _run_store(self, base: np.ndarray, r: Run, data: np.ndarray) -> None:
        """Scatter a full run's packed bytes back into the typed buffer."""
        if self.external32 and r.dtype.itemsize > 1:
            arr = data.view(r.dtype.newbyteorder(">"))
            data = arr.astype(r.dtype).view(np.uint8)
        disp = self._check_span(base, r)
        if r.stride < 0:
            for b in range(r.nblocks):
                dst = disp + b * r.stride
                base[dst:dst + r.block_bytes] = \
                    data[b * r.block_bytes:(b + 1) * r.block_bytes]
        elif r.nblocks == 1 or r.stride == r.block_bytes:
            base[disp:disp + r.packed_bytes] = data
        else:
            v = np.lib.stride_tricks.as_strided(
                base[disp:], shape=(r.nblocks, r.block_bytes),
                strides=(r.stride, 1))
            v[:] = data.reshape(r.nblocks, r.block_bytes)

    # -- public API ------------------------------------------------------
    def set_position(self, pos: int) -> None:
        """Reposition the pack/unpack stream (pipelined rendezvous,
        ref: opal_datatype_position.c)."""
        if pos < 0 or pos > self.packed_size:
            raise ValueError("position out of range")
        self.position = pos

    @property
    def done(self) -> bool:
        return self.position >= self.packed_size

    def pack(self, max_bytes: Optional[int] = None) -> bytes:
        """Pack up to max_bytes from the current position; advances."""
        base = _byte_view(self._buf, writable=False)
        start = self.position
        end = self.packed_size if max_bytes is None \
            else min(self.packed_size, start + max_bytes)
        if end <= start:
            return b""
        out = np.empty(end - start, dtype=np.uint8)
        pos = start
        ri, bi, byte = self._locate(start)
        run_lo = self._cum[ri - 1] if ri > 0 else 0
        while pos < end and ri < len(self.runs):
            r = self.runs[ri]
            run_hi = self._cum[ri]
            lo = max(pos, run_lo)
            hi = min(end, run_hi)
            if lo == run_lo and hi == run_hi:
                img = self._run_bytes(base, r)
            else:
                sub, blo, bhi = self._sub_run(r, lo - run_lo, hi - run_lo)
                img = self._run_bytes(base, sub)[blo:bhi]
            out[pos - start:hi - start] = img
            pos = hi
            run_lo = run_hi
            ri += 1
        data = out.tobytes()
        self.position = end
        if self.checksum:
            self.crc = zlib.crc32(data, self.crc)
        return data

    def pack_bytes(self, max_bytes: Optional[int] = None) -> bytes:
        return self.pack(max_bytes)

    def unpack(self, data: bytes) -> int:
        """Unpack bytes at the current position; advances; returns
        bytes consumed."""
        base = _byte_view(self._buf, writable=True)
        src = np.frombuffer(data, dtype=np.uint8)
        start = self.position
        end = min(self.packed_size, start + len(src))
        if end <= start:
            return 0
        pos = start
        ri, _, _ = self._locate(start)
        run_lo = self._cum[ri - 1] if ri > 0 else 0
        while pos < end and ri < len(self.runs):
            r = self.runs[ri]
            run_hi = self._cum[ri]
            lo = max(pos, run_lo)
            hi = min(end, run_hi)
            if lo == run_lo and hi == run_hi:
                self._run_store(base, r, src[lo - start:hi - start])
            else:
                # partial run: read-modify-write only the touched blocks
                sub, blo, bhi = self._sub_run(r, lo - run_lo, hi - run_lo)
                img = self._run_bytes(base, sub)
                img[blo:bhi] = src[lo - start:hi - start]
                self._run_store(base, sub, img)
            pos = hi
            run_lo = run_hi
            ri += 1
        if self.checksum:
            self.crc = zlib.crc32(data[:end - start], self.crc)
        self.position = end
        return end - start


class ContigConvertor:
    """Fast-path convertor: contiguous datatype over a contiguous
    buffer collapses pack/unpack to flat byte-range copies (the
    reference's contiguous-convertor shortcut that skips the stack
    machine entirely, ref: opal_convertor.h:254-262
    opal_convertor_prepare_for_send's CONVERTOR_NO_OP path).

    ``pack`` returns zero-copy memoryviews of the user buffer — legal
    because MPI forbids touching the buffer while a request that still
    streams from it is pending; eager sends that complete immediately
    must use ``pack_bytes`` (the payload may sit in a transport queue
    after completion).
    """

    __slots__ = ("datatype", "count", "packed_size", "position", "_view",
                 "checksum", "crc", "external32")

    def __init__(self, view, datatype, count) -> None:
        self._view = view  # uint8 ndarray view over the packed range
        self.datatype = datatype
        self.count = count
        self.packed_size = len(view)
        self.position = 0
        self.checksum = False
        self.external32 = False
        self.crc = 0

    def set_position(self, pos: int) -> None:
        if pos < 0 or pos > self.packed_size:
            raise ValueError("position out of range")
        self.position = pos

    @property
    def done(self) -> bool:
        return self.position >= self.packed_size

    def pack(self, max_bytes: Optional[int] = None):
        start = self.position
        end = self.packed_size if max_bytes is None \
            else min(self.packed_size, start + max_bytes)
        self.position = end
        if end <= start:
            return b""
        return memoryview(self._view[start:end])

    def pack_bytes(self, max_bytes: Optional[int] = None) -> bytes:
        out = self.pack(max_bytes)
        return out if isinstance(out, bytes) else out.tobytes()

    def unpack(self, data) -> int:
        start = self.position
        n = min(self.packed_size - start, len(data))
        if n <= 0:
            return 0
        src = np.frombuffer(data, dtype=np.uint8, count=n) \
            if isinstance(data, bytes) else \
            np.frombuffer(memoryview(data)[:n], dtype=np.uint8)
        self._view[start:start + n] = src
        self.position = start + n
        return n


def make_convertor(datatype: Datatype, count: int, buf: Buffer,
                   offset: int = 0, writable: bool = False):
    """Pick the cheapest convertor for (datatype, buf): the flat
    fast path when both are contiguous, the full stack machine
    otherwise."""
    if count and datatype.is_contiguous and datatype.lb == 0:
        try:
            view = _byte_view(buf, writable=writable)
        except (ValueError, TypeError, BufferError):
            view = None
        if view is not None:
            need = offset + count * datatype.size
            if need <= view.shape[0]:
                return ContigConvertor(view[offset:need], datatype, count)
    return Convertor(datatype, count, buf, offset=offset)


def pack(datatype: Datatype, count: int, buf: Buffer,
         external32: bool = False) -> bytes:
    """One-shot MPI_Pack."""
    return Convertor(datatype, count, buf, external32=external32).pack()


def unpack(datatype: Datatype, count: int, buf: Buffer, data: bytes,
           external32: bool = False) -> int:
    """One-shot MPI_Unpack."""
    return Convertor(datatype, count, buf, external32=external32).unpack(data)
