"""Write-path tests: dirty tracking, the per-chunk RMW lock, the inline
map path, and the incremental-pyramid == full-rebuild oracle.

The read path has been exercised since PR 1 (test_core / test_properties);
this module covers what the continuous-ingest wheel woke up — everything
here was dormant-and-broken while the repo was read-only.
"""

import threading

import numpy as np
import pytest

from repro.core import ChunkStore, Festivus, FestivusConfig, InMemoryObjectStore
from repro.core.chunkstore import parse_chunk_key
from repro.core.metadata import MetadataStore


def _world(shape=(13, 11, 2), chunks=(4, 4, 2), levels=3, seed=0,
           inline=False, write=True):
    store = InMemoryObjectStore()
    meta = MetadataStore()
    fs = Festivus(store, meta=meta,
                  config=FestivusConfig(inline_fetch=inline, cache_bytes=0,
                                        readahead_blocks=0))
    cs = ChunkStore(fs, "arrays")
    arr = cs.create("a", shape, np.float32, chunks, pyramid_levels=levels)
    data = None
    if write:
        data = np.random.default_rng(seed).random(shape, dtype=np.float32)
        arr.write_region((0,) * len(shape), data)
    return store, meta, cs, arr, data


def _pyramid_objects(store):
    """Every pyramid-level chunk object, key -> bytes."""
    return {k: store.get(k) for k in store.list("arrays/a/p")}


# ---------------------------------------------------------------------------
# parse_chunk_key (the invalidation bus depends on this inverse)
# ---------------------------------------------------------------------------
def test_parse_chunk_key_roundtrip():
    assert parse_chunk_key("arrays", "arrays/a/c/1.2.0") == ("a", 0, (1, 2, 0))
    assert parse_chunk_key("arrays", "arrays/a/p2/c/0.3.0") == ("a", 2, (0, 3, 0))
    # nested array names keep their path; the p-suffix only strips as a level
    assert parse_chunk_key("arrays", "arrays/x/y/c/0.0") == ("x/y", 0, (0, 0))
    assert parse_chunk_key("arrays", "arrays/x/p1/c/4.5") == ("x", 1, (4, 5))


def test_parse_chunk_key_rejects_foreign_objects():
    assert parse_chunk_key("arrays", "arrays/a/.manifest.json") is None
    assert parse_chunk_key("arrays", "other/a/c/0.0") is None
    assert parse_chunk_key("arrays", "arrays/a/c/not.an.index") is None
    assert parse_chunk_key("arrays", "arrays/shallow") is None


# ---------------------------------------------------------------------------
# inline map path (satellite: no thread pool under the DES)
# ---------------------------------------------------------------------------
def test_inline_map_bit_identical_to_pooled():
    """The forced-inline path (virtual mode) and the thread-pool path must
    produce byte-identical stores and reads."""
    worlds = {}
    for inline in (False, True):
        store, meta, cs, arr, data = _world(inline=inline)
        arr.build_pyramid()
        # an unaligned region rewrite through both paths too
        patch = np.full((3, 5, 2), 0.25, dtype=np.float32)
        arr.write_region((2, 3, 0), patch)
        read = arr.read_region((0, 0, 0), arr.spec.shape)
        worlds[inline] = ({k: store.get(k) for k in store.list("")},
                          read.tobytes())
    objs_pooled, read_pooled = worlds[False]
    objs_inline, read_inline = worlds[True]
    assert read_pooled == read_inline
    assert objs_pooled == objs_inline


def test_inline_mode_never_creates_a_pool():
    store, meta, cs, arr, data = _world(inline=True)
    arr.build_pyramid()
    arr.read_region((0, 0, 0), arr.spec.shape)
    assert cs._pool_obj is None  # lazy pool never materialized inline


# ---------------------------------------------------------------------------
# dirty tracking + generations
# ---------------------------------------------------------------------------
def test_dirty_tracking_lifecycle():
    store, meta, cs, arr, data = _world()
    assert set(arr.dirty_chunks()) == set(arr.chunk_indices())
    gen0 = arr.generation()
    assert gen0 > 0
    arr.build_pyramid()
    assert arr.dirty_chunks() == []  # build consumes the dirty set
    assert arr.generation() > gen0  # and bumps the generation
    arr.write_region((0, 0, 0), np.zeros((4, 4, 2), dtype=np.float32))
    assert arr.dirty_chunks() == [(0, 0, 0)]


def test_stale_handle_sees_rebuilt_levels():
    """A handle opened before a rewrite must serve the *new* level data
    after another handle rebuilds — the `_built_levels` per-handle cache
    revalidates through the KV generation (satellite bugfix)."""
    store, meta, cs, arr, data = _world()
    arr.build_pyramid()
    stale = cs.open("a")
    before = stale.read_level(1).copy()
    # another writer rewrites a chunk and re-runs the wheel's rebuild
    writer = cs.open("a")
    writer.write_region((0, 0, 0), np.zeros((4, 4, 2), dtype=np.float32))
    writer.build_pyramid()
    after = stale.read_level(1)
    assert not np.array_equal(before, after)
    assert np.allclose(after[:2, :2, :], 0.0)


def test_invalidate_pyramid_fails_stale_reads():
    store, meta, cs, arr, data = _world()
    arr.build_pyramid()
    handle = cs.open("a")
    handle.read_level(1)  # warm the per-handle cache
    arr.invalidate_pyramid()
    with pytest.raises(KeyError):
        handle.read_level(1)


# ---------------------------------------------------------------------------
# per-chunk RMW lock (satellite: the two-writer lost update)
# ---------------------------------------------------------------------------
def test_unaligned_rmw_blocks_on_held_lock():
    """Deterministic two-writer interleave: writer A 'pauses' mid-RMW
    (we hold its per-chunk KV lock), writer B's unaligned write into the
    same chunk must block until the lock releases — pre-fix B would read,
    modify, and put concurrently, losing A's update."""
    store, meta, cs, arr, data = _world()
    lock_key = "lock:" + arr._key((1, 0, 0))
    assert meta.setnx(lock_key, 1)  # A holds the chunk
    done = threading.Event()

    def writer_b():
        # rows [5, 7) live inside chunk (1, 0): unaligned -> RMW path
        arr.write_region((5, 0, 0),
                         np.full((2, 4, 2), 7.0, dtype=np.float32))
        done.set()

    t = threading.Thread(target=writer_b, daemon=True)
    t.start()
    assert not done.wait(0.15)  # blocked while A is mid-RMW
    meta.delete(lock_key)  # A completes, releasing the chunk
    assert done.wait(5.0)
    t.join(5.0)
    assert np.allclose(arr.read_region((5, 0, 0), (7, 4, 2)), 7.0)
    assert meta.peek(lock_key) is None  # lock released after the write


def test_two_concurrent_writers_lose_no_update():
    """Both writers' disjoint cells survive a shared boundary chunk."""
    store, meta, cs, arr, data = _world(shape=(16, 8, 2), chunks=(8, 8, 2),
                                        levels=0)
    barrier = threading.Barrier(2)

    def write(y0, value):
        barrier.wait()
        # rows [y0, y0+2) — both land inside chunk (0, 0, 0): RMW races
        arr.write_region((y0, 0, 0),
                         np.full((2, 8, 2), value, dtype=np.float32))

    threads = [threading.Thread(target=write, args=(0, 1.0)),
               threading.Thread(target=write, args=(2, 2.0))]
    for t in threads:
        t.start()
    for t in threads:
        t.join(10.0)
    out = arr.read_region((0, 0, 0), (4, 8, 2))
    assert np.allclose(out[0:2], 1.0)
    assert np.allclose(out[2:4], 2.0)


def test_read_after_rewrite_never_joins_the_fetch_before_it():
    """A finished fetch stays registered until the pool thread runs its
    done-callback, after its reader has woken.  A rewrite of the object
    must not let the next read join it: sequential unaligned writes to
    one chunk read the chunk straight after PUTting it."""
    from concurrent.futures import Future

    store, meta, cs, arr, data = _world(shape=(8, 8, 2), chunks=(8, 8, 2),
                                        levels=0)
    key = arr._key((0, 0, 0))
    stale = Future()
    stale.set_result(cs.fs.read_view(key))  # the fetch, not yet unregistered
    cs.fs._inflight[(key, 0)] = stale
    arr.write_region((0, 0, 0), np.full((8, 8, 2), 5.0, dtype=np.float32))
    arr.write_region((0, 0, 0), np.full((2, 8, 2), 7.0, dtype=np.float32))
    out = arr.read_all()
    assert np.all(out[:2] == 7.0) and np.all(out[2:] == 5.0)


# ---------------------------------------------------------------------------
# incremental pyramid == full rebuild (the oracle)
# ---------------------------------------------------------------------------
def _oracle_check(shape, chunks, levels, writes, seed=0):
    """Apply `writes` to twin worlds; rebuild one incrementally and one
    from scratch; every pyramid object must be byte-identical."""
    stores = []
    counts = []
    for full in (False, True):
        store, meta, cs, arr, data = _world(shape=shape, chunks=chunks,
                                            levels=levels, seed=seed)
        arr.build_pyramid()
        for (start, wshape, value) in writes:
            arr.write_region(start, np.full(wshape, value, dtype=np.float32))
        counts.append(arr.build_pyramid(full=full))
        stores.append(_pyramid_objects(store))
    incr, full_objs = stores
    assert incr == full_objs
    return counts  # (incremental writes, full writes)


def test_incremental_equals_full_deterministic_twin():
    writes = [((0, 0, 0), (4, 4, 2), 3.0),     # aligned chunk rewrite
              ((9, 5, 0), (3, 3, 2), -1.0)]    # unaligned, fringe-adjacent
    incr, full = _oracle_check((13, 11, 2), (4, 4, 2), 3, writes)
    assert incr < full  # only dirty ancestors re-encoded
    assert incr > 0


def test_incremental_noop_when_clean():
    store, meta, cs, arr, data = _world()
    arr.build_pyramid()
    assert arr.build_pyramid() == 0  # nothing dirty, nothing written


def test_incremental_random_dirty_sets_seeded():
    """Deterministic face of the hypothesis property below: seeded random
    write batches over odd (fringe-clipped) geometry."""
    shape, chunks = (21, 17, 2), (5, 4, 2)
    for seed in range(4):
        rng = np.random.default_rng(1000 + seed)
        writes = []
        for _ in range(int(rng.integers(1, 5))):
            y0 = int(rng.integers(0, shape[0] - 1))
            x0 = int(rng.integers(0, shape[1] - 1))
            h = int(rng.integers(1, shape[0] - y0 + 1))
            w = int(rng.integers(1, shape[1] - x0 + 1))
            writes.append(((y0, x0, 0), (h, w, 2),
                           float(rng.normal())))
        _oracle_check(shape, chunks, 3, writes, seed=seed)


def test_full_rebuild_counts_every_level_chunk():
    store, meta, cs, arr, data = _world()
    n = arr.build_pyramid(full=True)
    expected = sum(
        int(np.prod([-(-s // c) for s, c in
                     zip(arr.level_shape(lvl), arr.spec.chunks)]))
        for lvl in range(1, arr.spec.pyramid_levels + 1))
    assert n == expected


# ---------------------------------------------------------------------------
# hypothesis property (optional dev dependency, skips when absent)
# ---------------------------------------------------------------------------
try:
    from hypothesis import given, settings
    from hypothesis import strategies as st
    _HAVE_HYPOTHESIS = True
except ImportError:
    _HAVE_HYPOTHESIS = False

if _HAVE_HYPOTHESIS:
    _region = st.tuples(st.integers(0, 12), st.integers(0, 10),
                        st.integers(1, 9), st.integers(1, 7),
                        st.floats(-10, 10, allow_nan=False))

    @settings(max_examples=20, deadline=None)
    @given(st.lists(_region, min_size=1, max_size=4))
    def test_incremental_equals_full_property(regions):
        writes = []
        for (y0, x0, h, w, value) in regions:
            h = min(h, 13 - y0)
            w = min(w, 11 - x0)
            writes.append(((y0, x0, 0), (h, w, 2), value))
        _oracle_check((13, 11, 2), (4, 4, 2), 3, writes)
