import pytest

from toplingdb_tpu.db.dbformat import InternalKeyComparator, ValueType
from toplingdb_tpu.db.memtable import MemTable
from toplingdb_tpu.db.write_batch import WriteBatch
from toplingdb_tpu.utils.status import Corruption


def test_batch_encode_iterate():
    b = WriteBatch()
    b.put(b"k1", b"v1")
    b.delete(b"k2")
    b.merge(b"k3", b"m1")
    b.single_delete(b"k4")
    b.delete_range(b"a", b"z")
    b.put_log_data(b"annotation")  # not counted
    assert b.count() == 5
    got = list(b.entries())
    assert got == [
        (ValueType.VALUE, b"k1", b"v1"),
        (ValueType.DELETION, b"k2", None),
        (ValueType.MERGE, b"k3", b"m1"),
        (ValueType.SINGLE_DELETION, b"k4", None),
        (ValueType.RANGE_DELETION, b"a", b"z"),
    ]


def test_batch_roundtrip_bytes():
    b = WriteBatch()
    b.put(b"key", b"value")
    b.set_sequence(42)
    b2 = WriteBatch(b.data())
    assert b2.sequence() == 42
    assert b2.count() == 1
    assert list(b2.entries()) == list(b.entries())


def test_batch_append_from():
    a = WriteBatch()
    a.put(b"k1", b"v1")
    b = WriteBatch()
    b.put(b"k2", b"v2")
    a.append_from(b)
    assert a.count() == 2
    assert [k for _, k, _ in a.entries()] == [b"k1", b"k2"]


def test_count_mismatch_detected():
    b = WriteBatch()
    b.put(b"k", b"v")
    b.set_count(3)
    with pytest.raises(Corruption):
        list(b.entries())


def test_insert_into_memtable_assigns_seqnos():
    b = WriteBatch()
    b.put(b"ka", b"v1")
    b.put(b"kb", b"v2")
    b.set_sequence(10)
    mem = MemTable(InternalKeyComparator())
    consumed = b.insert_into(mem)
    assert consumed == 2
    entries = list(mem.entries_for_key(b"ka", 2**56 - 1))
    assert entries == [(10, ValueType.VALUE, b"v1")]
    entries = list(mem.entries_for_key(b"kb", 2**56 - 1))
    assert entries == [(11, ValueType.VALUE, b"v2")]


# -- deferred puts: the wire image against an encode-at-once oracle --------

class _Eager(WriteBatch):
    """The oracle: every put encodes at once through _add_record."""

    def put(self, key, value, cf=0):
        self._add_record(ValueType.VALUE, cf, key, value)


_SIZES = (0, 1, 8, 127, 128, 300, 70_000)


def _unspilled(batch, off, count):
    """utilities/transactions.py's spill slice, on a bare batch."""
    import types

    from toplingdb_tpu.utilities.transactions import (
        WriteUnpreparedTransaction)

    txn = types.SimpleNamespace(wbwi=types.SimpleNamespace(batch=batch),
                                _spill_off=off, _spill_count=count)
    return WriteUnpreparedTransaction._unspilled(txn)


def _same(a, b):
    assert a.count() == b.count()
    assert a.data_size() == b.data_size()
    assert a.data() == b.data()
    assert list(a.entries_cf()) == list(b.entries_cf())


@pytest.mark.parametrize("seed", range(4))
@pytest.mark.parametrize("pb", [0, 8])
@pytest.mark.parametrize("encoder", ["native", "python"])
def test_deferred_put_wire_image_parity(monkeypatch, encoder, pb, seed):
    """Random interleavings of every mutator and reader give the wire image,
    count, size and records of a batch that encoded each put at once —
    with bytes, bytearray and memoryview arguments (mutated after the put:
    only exact bytes are kept as references), keys and values of 0-70,000
    bytes, CF 0 and 3, protection on and off, WriteBatch(data), and a
    write-unprepared transaction's spill slice."""
    import random

    from toplingdb_tpu import native
    from toplingdb_tpu.db import write_batch as wbm

    fn = native.wb_put_values() if encoder == "native" else None
    if encoder == "native" and fn is None:
        pytest.skip("C encoder unavailable")
    monkeypatch.setattr(wbm, "_enc_fn", fn)
    rng = random.Random(seed)

    def blob():
        n = rng.choice(_SIZES) if rng.random() < 0.3 else rng.randrange(24)
        return rng.randbytes(n)

    def arg(b):
        if rng.random() < 0.8:
            return b, None
        ba = bytearray(b)
        return rng.choice((ba, memoryview(ba))), ba

    def fresh():
        return WriteBatch(protection_bytes_per_key=pb), \
            _Eager(protection_bytes_per_key=pb)

    def puts(a, b, cf=0):
        for _ in range(rng.randrange(1, 13)):
            k, v = blob(), blob()
            (ka, kba), (va, vba) = arg(k), arg(v)
            a.put(ka, va, cf)
            b.put(k, v, cf)
            for ba in (kba, vba):  # the caller reuses its buffer
                if ba:
                    ba[0] ^= 0xFF

    def side_batch():
        x, y = fresh()
        for _ in range(rng.randrange(3)):
            puts(x, y)
            if rng.random() < 0.3:
                k = blob()
                x.delete(k)
                y.delete(k)
        return x, y

    sut, orc = fresh()
    spill = None
    for step in range(120):
        op = rng.randrange(16)
        if op < 5:
            puts(sut, orc)
        elif op == 5:
            puts(sut, orc, cf=3)
        elif op == 6:
            k, v, cf = blob(), blob(), rng.choice((0, 3))
            f = rng.choice(("merge", "put_entity", "delete_range"))
            getattr(sut, f)(k, v, cf)
            getattr(orc, f)(k, v, cf)
        elif op == 7:
            k = blob()
            f = rng.choice(("delete", "single_delete"))
            getattr(sut, f)(k)
            getattr(orc, f)(k)
        elif op == 8:
            blob_ = blob()
            sut.put_log_data(blob_)
            orc.put_log_data(blob_)
        elif op == 9:
            x, y = side_batch()
            if rng.random() < 0.5:
                sut.append_from(x)
                orc.append_from(y)
            else:  # the pending side is the one appended to
                x.append_from(sut)
                y.append_from(orc)
                sut, orc = x, y
                spill = None  # the spill offset was into the old batch
        elif op == 10:
            if rng.random() < 0.5:
                sut.attach_protection(8)
                orc.attach_protection(8)
            sut.verify_protection()
            orc.verify_protection()
        elif op == 11:
            data = sut.data()
            sut = WriteBatch(data, protection_bytes_per_key=pb)
            orc = _Eager(data, protection_bytes_per_key=pb)
        elif op == 12 and rng.random() < 0.2:
            sut.clear()
            orc.clear()
            spill = None
        elif op == 13:
            spill = (sut.data_size(), sut.count())
        elif op == 14 and spill is not None:
            puts(sut, orc)
            _same(_unspilled(sut, *spill), _unspilled(orc, *spill))
        elif op == 15:
            puts(sut, orc)
            seq = rng.randrange(1 << 40)
            sut.set_sequence(seq)
            orc.set_sequence(seq)
            assert sut.sequence() == seq
        assert not orc._pend
        _same(sut, orc)
        if sut._prot is not None and sut._prot_n == sut.count():
            assert wbm._prot_eq(sut._prot, orc._prot)
