"""Datatype engine + convertor tests.

Modeled on the reference's datatype suite (test/datatype/ddt_test.c,
ddt_raw.c, position.c, unpack_ooo.c, external32.c): pack/unpack round
trips checked against independent numpy slicing, partial/pipelined
packing, repositioning, out-of-order unpack, external32 byte order.
"""

import numpy as np
import pytest

from ompi_tpu.datatype import engine as dt
from ompi_tpu.datatype.convertor import Convertor, pack, unpack


def roundtrip(datatype, count, src):
    """pack from src, unpack into zeroed clone, return the clone."""
    data = pack(datatype, count, src)
    assert len(data) == datatype.size * count
    dst = np.zeros_like(src)
    consumed = unpack(datatype, count, dst, data)
    assert consumed == len(data)
    return dst, data


def test_predefined_sizes():
    assert dt.INT.size == 4
    assert dt.DOUBLE.size == 8
    assert dt.FLOAT_INT.size == 8
    assert dt.INT.extent == 4
    assert dt.INT.is_contiguous


def test_contiguous_roundtrip():
    t = dt.contiguous(10, dt.INT).commit()
    assert t.size == 40 and t.extent == 40 and t.is_contiguous
    src = np.arange(10, dtype=np.int32)
    dst, data = roundtrip(t, 1, src)
    np.testing.assert_array_equal(dst, src)
    assert data == src.tobytes()


def test_vector_pack_matches_slicing():
    # 4 blocks of 3 ints, stride 5 ints
    t = dt.vector(4, 3, 5, dt.INT).commit()
    assert t.size == 4 * 3 * 4
    src = np.arange(50, dtype=np.int32)
    data = pack(t, 1, src)
    expected = np.concatenate([src[i * 5:i * 5 + 3] for i in range(4)])
    np.testing.assert_array_equal(np.frombuffer(data, np.int32), expected)
    # unpack scatters back to the same offsets
    dst = np.zeros(50, dtype=np.int32)
    unpack(t, 1, dst, data)
    ref = np.zeros(50, dtype=np.int32)
    for i in range(4):
        ref[i * 5:i * 5 + 3] = src[i * 5:i * 5 + 3]
    np.testing.assert_array_equal(dst, ref)


def test_vector_multiple_count():
    t = dt.vector(3, 2, 4, dt.FLOAT).commit()
    # extent of the vector: (count-1)*stride + blocklen = 2*4+2 = 10 floats
    assert t.extent == 10 * 4
    src = np.arange(40, dtype=np.float32)
    data = pack(t, 2, src)
    exp = []
    for e in range(2):
        for b in range(3):
            off = e * 10 + b * 4
            exp.append(src[off:off + 2])
    np.testing.assert_array_equal(np.frombuffer(data, np.float32),
                                  np.concatenate(exp))


def test_hvector_negative_stride():
    t = dt.hvector(3, 2, -16, dt.INT).commit()
    src = np.arange(20, dtype=np.int32)
    # MPI buffer pointer sits at element 8; blocks at bytes 0,-16,-32
    conv = Convertor(t, 1, src, offset=8 * 4)
    data = conv.pack()
    exp = np.concatenate([src[8:10], src[4:6], src[0:2]])
    np.testing.assert_array_equal(np.frombuffer(data, np.int32), exp)


def test_indexed():
    t = dt.indexed([2, 1, 3], [0, 4, 7], dt.DOUBLE).commit()
    assert t.size == 6 * 8
    src = np.arange(12, dtype=np.float64)
    data = pack(t, 1, src)
    exp = np.concatenate([src[0:2], src[4:5], src[7:10]])
    np.testing.assert_array_equal(np.frombuffer(data, np.float64), exp)


def test_struct_mixed_types():
    # { int a[2]; double b; } with natural alignment
    t = dt.struct([2, 1], [0, 8], [dt.INT, dt.DOUBLE]).commit()
    assert t.size == 16
    assert t.extent == 16  # aligned to 8
    raw = bytearray(32)
    np.frombuffer(raw, np.int32)[0:2] = [7, 9]
    np.frombuffer(raw, np.float64)[1] = 3.5
    np.frombuffer(raw, np.int32)[4:6] = [1, 2]
    np.frombuffer(raw, np.float64)[3] = -1.25
    data = pack(t, 2, np.frombuffer(raw, np.uint8))
    ints = np.frombuffer(data[0:8], np.int32)
    d0 = np.frombuffer(data[8:16], np.float64)[0]
    np.testing.assert_array_equal(ints, [7, 9])
    assert d0 == 3.5
    ints2 = np.frombuffer(data[16:24], np.int32)
    d1 = np.frombuffer(data[24:32], np.float64)[0]
    np.testing.assert_array_equal(ints2, [1, 2])
    assert d1 == -1.25


def test_struct_alignment_padding():
    # { char c; double d; } → extent 16 with epsilon padding
    t = dt.struct([1, 1], [0, 8], [dt.CHAR, dt.DOUBLE]).commit()
    assert t.size == 9
    assert t.extent == 16


def test_subarray_2d():
    # 6x8 array, take rows 1..3, cols 2..5 (C order)
    t = dt.subarray([6, 8], [3, 4], [1, 2], dt.ORDER_C, dt.INT).commit()
    assert t.size == 12 * 4
    assert t.extent == 48 * 4
    src = np.arange(48, dtype=np.int32).reshape(6, 8)
    data = pack(t, 1, src)
    np.testing.assert_array_equal(
        np.frombuffer(data, np.int32).reshape(3, 4), src[1:4, 2:6])


def test_subarray_3d_fortran():
    sizes, subs, starts = [4, 5, 6], [2, 3, 2], [1, 1, 3]
    t = dt.subarray(sizes, subs, starts, dt.ORDER_FORTRAN, dt.FLOAT).commit()
    src = np.arange(120, dtype=np.float32).reshape(6, 5, 4)  # F order => C rev
    data = pack(t, 1, src)
    # Fortran (i,j,k) sizes 4,5,6 == C array [6][5][4] indexed [k][j][i]
    exp = src[3:5, 1:4, 1:3]
    np.testing.assert_array_equal(
        np.frombuffer(data, np.float32), exp.ravel())


def test_darray_block():
    t = dt.darray(4, 1, [8, 8], [dt.DISTRIBUTE_BLOCK] * 2,
                  [dt.DISTRIBUTE_DFLT_DARG] * 2, [2, 2], dt.ORDER_C,
                  dt.INT).commit()
    src = np.arange(64, dtype=np.int32).reshape(8, 8)
    data = pack(t, 1, src)
    # rank 1 of a 2x2 grid in C order → block row 0, col 1
    np.testing.assert_array_equal(
        np.frombuffer(data, np.int32).reshape(4, 4), src[0:4, 4:8])


def test_resized_extent():
    t = dt.resized(dt.INT, 0, 16).commit()
    assert t.extent == 16 and t.size == 4
    src = np.arange(16, dtype=np.int32)
    data = pack(t, 4, src)
    np.testing.assert_array_equal(np.frombuffer(data, np.int32),
                                  src[[0, 4, 8, 12]])


def test_partial_pack_resume():
    """Pipelined rendezvous-style chunked packing."""
    t = dt.vector(8, 3, 5, dt.INT).commit()
    src = np.arange(64, dtype=np.int32)
    whole = pack(t, 1, src)
    conv = Convertor(t, 1, src)
    chunks = []
    while not conv.done:
        chunks.append(conv.pack(max_bytes=7))  # awkward odd chunk size
    assert b"".join(chunks) == whole


def test_partial_unpack_resume():
    t = dt.vector(8, 3, 5, dt.INT).commit()
    src = np.arange(64, dtype=np.int32)
    whole = pack(t, 1, src)
    dst = np.zeros(64, dtype=np.int32)
    conv = Convertor(t, 1, dst)
    off = 0
    for sz in (5, 11, 1, 40, 1000):
        conv.unpack(whole[off:off + sz])
        off += sz
        if off >= len(whole):
            break
    ref = np.zeros(64, dtype=np.int32)
    for i in range(8):
        ref[i * 5:i * 5 + 3] = src[i * 5:i * 5 + 3]
    np.testing.assert_array_equal(dst, ref)


def test_out_of_order_unpack():
    """unpack_ooo.c analog: segments arrive out of order, repositioned."""
    t = dt.vector(6, 4, 7, dt.DOUBLE).commit()
    src = np.arange(50, dtype=np.float64)
    whole = pack(t, 1, src)
    dst = np.zeros(50, dtype=np.float64)
    segs = [(40, 60), (0, 40), (100, len(whole)), (60, 100)]
    for lo, hi in segs:
        conv = Convertor(t, 1, dst)
        conv.set_position(lo)
        conv.unpack(whole[lo:hi])
    ref = np.zeros(50, dtype=np.float64)
    for i in range(6):
        ref[i * 7:i * 7 + 4] = src[i * 7:i * 7 + 4]
    np.testing.assert_array_equal(dst, ref)


def test_position_pack_from_middle():
    t = dt.contiguous(100, dt.INT).commit()
    src = np.arange(100, dtype=np.int32)
    conv = Convertor(t, 1, src)
    conv.set_position(40)
    data = conv.pack(max_bytes=20)
    np.testing.assert_array_equal(np.frombuffer(data, np.int32),
                                  src[10:15])


def test_external32_byteorder():
    t = dt.contiguous(4, dt.INT).commit()
    src = np.array([1, 2, 3, 4], dtype=np.int32)
    data = pack(t, 1, src, external32=True)
    np.testing.assert_array_equal(
        np.frombuffer(data, np.dtype(np.int32).newbyteorder(">")), src)
    dst = np.zeros(4, dtype=np.int32)
    unpack(t, 1, dst, data, external32=True)
    np.testing.assert_array_equal(dst, src)


def test_external32_derived():
    t = dt.vector(3, 2, 4, dt.DOUBLE).commit()
    src = np.arange(12, dtype=np.float64)
    data = pack(t, 1, src, external32=True)
    exp = np.concatenate([src[0:2], src[4:6], src[8:10]])
    np.testing.assert_array_equal(
        np.frombuffer(data, np.dtype(np.float64).newbyteorder(">")), exp)


def test_checksum():
    t = dt.contiguous(16, dt.INT).commit()
    src = np.arange(16, dtype=np.int32)
    c1 = Convertor(t, 1, src, checksum=True)
    c1.pack()
    dst = np.zeros(16, dtype=np.int32)
    c2 = Convertor(t, 1, dst, checksum=True)
    c2.unpack(src.tobytes())
    assert c1.crc == c2.crc != 0


def test_nested_vector_of_struct():
    s = dt.struct([1, 1], [0, 4], [dt.INT, dt.FLOAT]).commit()
    t = dt.vector(3, 2, 3, s).commit()
    assert t.size == 6 * 8
    raw = np.zeros(9 * 8, dtype=np.uint8)
    for i in range(9):
        raw.view(np.int32)[i * 2] = i
        raw.view(np.float32)[i * 2 + 1] = i + 0.5
    data = pack(t, 1, raw)
    got_i = np.frombuffer(data, np.int32)[0::2]
    got_f = np.frombuffer(data, np.float32)[1::2]
    exp_idx = [0, 1, 3, 4, 6, 7]
    np.testing.assert_array_equal(got_i, exp_idx)
    np.testing.assert_array_equal(got_f, np.array(exp_idx, np.float32) + 0.5)


def test_get_envelope_contents():
    t = dt.vector(4, 3, 5, dt.INT)
    ni, na, nd, comb = t.get_envelope()
    assert comb == "VECTOR" and ni == 3 and nd == 1
    comb, ints, addrs, dts = t.get_contents()
    assert ints == [4, 3, 5] and dts[0] is dt.INT


def test_lb_ub_markers():
    t = dt.struct([1, 1, 1], [-4, 0, 12],
                  [dt.LB_MARKER, dt.INT, dt.UB_MARKER]).commit()
    assert t.lb == -4 and t.ub == 12 and t.extent == 16


def test_from_numpy_dtype():
    assert dt.from_numpy_dtype(np.float32) is dt.FLOAT
    assert dt.from_numpy_dtype(np.int32) is dt.INT
    assert dt.from_numpy_dtype("float64") is dt.DOUBLE


def test_pair_type_roundtrip():
    src = np.zeros(4, dtype=dt.FLOAT_INT.base)
    src["v"] = [1.5, -2.0, 3.25, 0.0]
    src["i"] = [10, 20, 30, 40]
    dst, _ = roundtrip(dt.FLOAT_INT, 4, src)
    np.testing.assert_array_equal(dst, src)


def test_buffer_too_short_raises():
    """as_strided has no bounds checks; the convertor must."""
    t = dt.vector(4, 3, 5, dt.INT).commit()  # spans 18 ints = 72 bytes
    short = np.arange(16, dtype=np.int32)    # only 64 bytes
    with pytest.raises(IndexError):
        pack(t, 1, short)
    with pytest.raises(IndexError):
        unpack(t, 1, short, b"\0" * t.size)


def test_darray_fortran_rowmajor_rank_decomp():
    """MPI-3.1 4.1.4: rank->coords is row-major regardless of order."""
    # 2x3 grid, rank 1 => coords [0,1] (row-major), NOT [1,0]
    t = dt.darray(6, 1, [4, 6], [dt.DISTRIBUTE_BLOCK] * 2,
                  [dt.DISTRIBUTE_DFLT_DARG] * 2, [2, 3], dt.ORDER_FORTRAN,
                  dt.INT).commit()
    src = np.arange(24, dtype=np.int32).reshape(6, 4)  # F-order [4][6]
    data = pack(t, 1, src)
    # Fortran gsizes [4,6]: dim0 blocks of 2 over p=2, dim1 blocks of 2
    # over p=3; coords [0,1] -> rows 0:2 (F dim0), cols 2:4 (F dim1)
    exp = src[2:4, 0:2]  # C view: dim order reversed
    np.testing.assert_array_equal(
        np.frombuffer(data, np.int32), exp.ravel())


def test_partial_pack_is_chunk_local():
    """Pipelined chunking must not rematerialize the whole run."""
    import time
    t = dt.contiguous(4 << 20, dt.BYTE).commit()
    src = np.zeros(4 << 20, dtype=np.uint8)
    conv = Convertor(t, 1, src)
    t0 = time.perf_counter()
    n = 0
    while not conv.done:
        conv.pack(max_bytes=64 << 10)
        n += 1
    el = time.perf_counter() - t0
    assert n == 64
    assert el < 1.0  # O(N^2) behavior would take far longer


# -- on-device packing (datatype/device.py; SURVEY §2.9.1 north star) --

def test_device_pack_vector_matches_host_convertor():
    import jax.numpy as jnp
    from ompi_tpu.datatype import convertor as cv
    from ompi_tpu.datatype import engine as dt
    from ompi_tpu.datatype.device import (device_pack, device_unpack,
                                          is_device_packable)

    vec = dt.vector(5, 2, 3, dt.FLOAT).commit()
    assert is_device_packable(vec, 2)
    buf = np.arange(40, dtype=np.float32)
    host = np.frombuffer(cv.pack(vec, 2, buf), dtype=np.float32)
    dev = np.asarray(device_pack(vec, 2, jnp.asarray(buf)))
    assert np.array_equal(host, dev)
    # unpack scatters back to the same slots
    out = np.asarray(device_unpack(vec, 2, jnp.asarray(dev),
                                   jnp.zeros(40, jnp.float32)))
    ref = np.zeros(40, dtype=np.float32)
    cv.unpack(vec, 2, ref, host.tobytes())
    assert np.array_equal(out, ref)


def test_device_pack_rejects_mixed_structs():
    from ompi_tpu.datatype import engine as dt
    from ompi_tpu.datatype.device import is_device_packable

    st = dt.struct([1, 1], [0, 8], [dt.INT, dt.DOUBLE]).commit()
    assert not is_device_packable(st, 1)


def test_device_pack_indexed_and_contiguous():
    import jax.numpy as jnp
    from ompi_tpu.datatype import convertor as cv
    from ompi_tpu.datatype import engine as dt
    from ompi_tpu.datatype.device import device_pack

    idxed = dt.indexed([2, 3], [7, 0], dt.INT).commit()
    buf = np.arange(16, dtype=np.int32)
    host = np.frombuffer(cv.pack(idxed, 1, buf), dtype=np.int32)
    dev = np.asarray(device_pack(idxed, 1, jnp.asarray(buf)))
    assert np.array_equal(host, dev)


def _pack_cases(base):
    """name -> (datatype built on ``base``, count, elements the buffer
    holds beyond the span, how it must lower)."""
    item = base.size
    vec = dt.vector(4, 1, 2, base)
    return {
        # one component of interleaved pairs; the buffer ends at the
        # span, one element short of a whole stride (odd length)
        "vector_1_2_odd_buffer": (dt.vector(9, 1, 2, base), 1, 0, "slice"),
        "vector_1_2_long_buffer": (dt.vector(9, 1, 2, base), 1, 5,
                                   "slice"),
        # the same elements from a first displacement of 3
        "indexed_1_2_from_3": (dt.indexed(
            [1] * 9, [3 + 2 * i for i in range(9)], base), 1, 0, "slice"),
        "vector_3_5_ends_at_span": (dt.vector(6, 3, 5, base), 1, 0,
                                    "slice"),
        "indexed_3_5_from_4": (dt.indexed(
            [3] * 6, [4 + 5 * i for i in range(6)], base), 1, 0,
            "slice"),
        # enough blocks a few elements apart for whole rows of 128
        # strides (the transposed view) and some blocks past them
        "vector_1_2_of_300": (dt.vector(300, 1, 2, base), 1, 0, "slice"),
        "vector_1_2_of_256_whole_rows": (dt.vector(256, 1, 2, base), 1,
                                         1, "slice"),
        "vector_3_5_of_300": (dt.vector(300, 3, 5, base), 1, 0, "slice"),
        "indexed_3_5_of_300_from_4": (dt.indexed(
            [3] * 300, [4 + 5 * i for i in range(300)], base), 1, 2,
            "slice"),
        # row blocks of rows at least 128 elements long
        "vector_100_130_ends_at_span": (dt.vector(5, 100, 130, base), 1,
                                        0, "slice"),
        "vector_100_130_whole_rows": (dt.vector(5, 100, 130, base), 1,
                                      30, "slice"),
        "hvector_100_130_from_7": (dt.hindexed(
            [100] * 5, [(7 + 130 * i) * item for i in range(5)], base),
            1, 0, "slice"),
        "subarray_rows_150_of_200": (dt.subarray(
            [6, 200], [3, 150], [1, 20], dt.ORDER_C, base), 1, 0,
            "slice"),
        "subarray_rows_4_of_8": (dt.subarray(
            [6, 8], [3, 4], [1, 2], dt.ORDER_C, base), 1, 0, "slice"),
        "subarray_columns_2_of_3": (dt.subarray(
            [400, 3], [300, 2], [50, 1], dt.ORDER_C, base), 1, 0,
            "slice"),
        "contiguous": (dt.contiguous(24, base), 1, 0, "identity"),
        "contiguous_in_a_longer_buffer": (dt.contiguous(24, base), 1, 9,
                                          "slice"),
        "primitive_count_n": (base, 24, 0, "identity"),
        # count=3 of a vector resized so its extent continues the
        # stride: one run; as MPI_Type_vector leaves it (the extent
        # ends with the last block): three runs, three slices
        "count3_extent_continues_stride": (
            dt.resized(vec, 0, 8 * item), 3, 0, "slice"),
        "count3_extent_does_not": (vec, 3, 0, "slice"),
        "count3_resized_apart": (dt.resized(vec, 0, 10 * item), 3, 0,
                                 "slice"),
        "indexed_irregular": (dt.indexed(
            [3, 1, 4, 8, 2, 1, 5], [40, 2, 9, 20, 60, 70, 80], base), 1,
            0, "gather"),
    }


_PACK_CASES = sorted(_pack_cases(dt.DOUBLE))
_CARRIERS = {"float32": (dt.FLOAT, np.float32),
             "float64": (dt.DOUBLE, np.float64),
             "bits": (dt.DOUBLE, np.uint64)}


@pytest.fixture
def x64():
    """binary64 and uint64 arrays in this process, for one test."""
    import jax
    jax.config.update("jax_enable_x64", True)
    yield
    jax.config.update("jax_enable_x64", False)


def _pack_case(case, carrier):
    base, dtype = _CARRIERS[carrier]
    datatype, count, beyond, lowering = _pack_cases(base)[case]
    if datatype is not base:
        datatype.commit()
    return datatype, count, beyond, lowering, np.dtype(dtype)


@pytest.mark.parametrize("carrier", sorted(_CARRIERS))
@pytest.mark.parametrize("case", _PACK_CASES)
def test_device_pack_equals_the_host_convertor(case, carrier, x64):
    """Whatever the lowering, the packed stream is the host
    convertor's, bit for bit."""
    import jax
    import jax.numpy as jnp
    from ompi_tpu.datatype import convertor as cv
    from ompi_tpu.datatype.device import device_pack, resolve

    datatype, count, beyond, _, dtype = _pack_case(case, carrier)
    n = resolve(datatype, count).span + beyond
    rng = np.random.default_rng(n)
    buf = rng.integers(0, 2 ** 63, n, dtype=np.uint64) if dtype.kind == "u" \
        else rng.standard_normal(n).astype(dtype)
    # the convertor packs MPI_DOUBLE's bytes, whatever integer the
    # device held them in
    host = cv.pack(datatype, count,
                   buf.view(np.float64 if dtype.kind == "u" else dtype))
    dev = jax.jit(lambda a: device_pack(datatype, count, a))(
        jnp.asarray(buf))
    assert dev.dtype == dtype
    assert np.asarray(dev).tobytes() == bytes(host)


@pytest.mark.parametrize("carrier", sorted(_CARRIERS))
@pytest.mark.parametrize("case", _PACK_CASES)
def test_a_regular_layout_lowers_without_a_gather(case, carrier, x64):
    """The program text of the pack: static slices for a regular run
    (no gather, no index constant), the array itself for an identity,
    one gather for what has no such form."""
    import jax
    from ompi_tpu.datatype.device import resolve, typed_operand

    datatype, count, beyond, lowering, dtype = _pack_case(case, carrier)
    x = jax.ShapeDtypeStruct((resolve(datatype, count).span + beyond,), dtype)
    t = typed_operand(datatype, count, x)
    assert t.sliced == (lowering != "gather")
    text = jax.jit(t.stream).lower(x).as_text()
    assert ("gather" in text) == (lowering == "gather")
    if lowering != "gather":
        assert "constant" not in text and t._idx is None   # no index list
    if lowering == "identity":
        assert "slice" not in text


def test_typed_operand_is_the_one_eligibility_rule():
    """datatype/device.typed_operand: committed, device-packable, base
    type equal to the buffer's, buffer long enough; equal layouts are
    one key whatever datatype object described them."""
    from ompi_tpu import errhandler
    from ompi_tpu.datatype import engine as dt
    from ompi_tpu.datatype.device import label, typed_count, typed_operand

    buf = np.zeros(16, np.float32)
    vec = dt.vector(4, 2, 4, dt.FLOAT).commit()
    t = typed_operand(vec, 1, buf)
    assert (t.elems, t.span, label(vec)) == (8, 14, "VECTOR")
    assert label(dt.FLOAT) == "MPI_FLOAT"
    assert t.dtype == np.float32
    # the same layout through another constructor: the same key
    same = dt.indexed([2] * 4, [0, 4, 8, 12], dt.FLOAT).commit()
    assert typed_operand(same, 1, buf) == t
    assert hash(typed_operand(same, 1, buf)) == hash(t)
    assert typed_operand(dt.vector(4, 2, 3, dt.FLOAT).commit(), 1, buf) != t
    # another base type, a mixed struct: the host convertor's
    assert typed_operand(vec, 1, np.zeros(16, np.int32)) is None
    st = dt.struct([1, 1], [0, 8], [dt.INT, dt.DOUBLE]).commit()
    assert typed_operand(st, 1, buf) is None
    with pytest.raises(IndexError):
        typed_operand(vec, 1, np.zeros(13, np.float32))
    with pytest.raises(errhandler.MPIException) as e:
        typed_operand(dt.vector(4, 2, 4, dt.FLOAT), 1, buf)
    assert e.value.code == errhandler.ERR_TYPE
    # count None: as many elements of the type as the buffer holds
    assert typed_count(vec, None, buf) == 1
    assert typed_count(dt.FLOAT, None, buf) == 16
    assert typed_count(vec, 3, buf) == 3
