"""Hot-needle read cache (storage/read_cache.py): segmented-LRU
semantics (scan resistance, size-capped admission, epoch-gated fills)
and STRICT coherence through the storage-layer chokepoints — delete,
overwrite, bulk-frame append, tail replay, vacuum/compaction, unmount —
plus the eviction accounting proving SeaweedFS_read_cache_bytes can
never scrape negative (the PR 6/7 gauge-delta lessons)."""

import os
import threading

import numpy as np
import pytest

from seaweedfs_tpu.stats import (READ_CACHE_BYTES, READ_CACHE_EVICTIONS,
                                 READ_CACHE_HITS, READ_CACHE_MISSES)
from seaweedfs_tpu.storage import read_cache
from seaweedfs_tpu.storage.disk_location import DiskLocation
from seaweedfs_tpu.storage.needle import Needle
from seaweedfs_tpu.storage.store import Store
from seaweedfs_tpu.storage.vacuum import commit_compact, compact
from seaweedfs_tpu.storage.volume import Volume


def _needle(key: int, data: bytes, cookie: int = 7) -> Needle:
    n = Needle(id=key, cookie=cookie, data=data)
    n.to_bytes()  # stamp checksum/append_at_ns like a stored needle
    return n


# ---------------------------------------------------------------------------
# cache structure: SLRU admission / eviction / accounting
# ---------------------------------------------------------------------------

def test_hit_miss_and_promotion():
    c = read_cache.ReadCache(1 << 20)
    assert c.get(1, 10, 7) is None  # miss
    c.put(1, 10, _needle(10, b"abc"))
    got = c.get(1, 10, 7)
    assert got is not None and got.data == b"abc"
    st = c.stats()
    # first hit promotes probation -> protected (the frequency gate)
    assert st["protected"] == 1 and st["probation"] == 0


def test_cookie_mismatch_is_a_miss():
    c = read_cache.ReadCache(1 << 20)
    c.put(1, 10, _needle(10, b"abc", cookie=7))
    assert c.get(1, 10, 99) is None      # wrong cookie: storage answers
    assert c.get(1, 10, 7).data == b"abc"
    assert c.get(1, 10, None).data == b"abc"  # cookie-less probe allowed


def test_scan_does_not_flush_hot_set():
    """One sequential pass over many cold keys must not evict the
    re-referenced hot set: cold entries die on probation, the protected
    segment survives — the whole point of the segmented LRU."""
    c = read_cache.ReadCache(100 * 100)  # room for ~100 hundred-byte objs
    hot = list(range(10))
    for k in hot:
        c.put(1, k, _needle(k, b"h" * 100))
        assert c.get(1, k, 7) is not None  # second touch -> protected
    # the scan: 500 distinct cold keys, never re-referenced
    for k in range(1000, 1500):
        c.put(1, k, _needle(k, b"c" * 100))
    for k in hot:
        assert c.get(1, k, 7) is not None, f"scan evicted hot key {k}"


def test_size_capped_admission():
    c = read_cache.ReadCache(1 << 20, max_obj_bytes=100)
    assert not c.put(1, 1, _needle(1, b"x" * 101))
    assert c.get(1, 1, 7) is None
    assert c.put(1, 2, _needle(2, b"x" * 100))
    assert c.get(1, 2, 7) is not None


def test_eviction_counter_and_capacity():
    before = READ_CACHE_EVICTIONS.value()
    c = read_cache.ReadCache(1000)
    for k in range(20):  # 20 x 100 B into a 1000 B cache
        c.put(1, k, _needle(k, b"e" * 100))
    assert c.bytes_used <= 1000
    assert READ_CACHE_EVICTIONS.value() > before
    assert len(c) <= 10


def test_epoch_rejects_stale_fill():
    """The read-old-bytes -> invalidate -> fill race: a fill whose
    storage read began before an invalidation must be rejected."""
    c = read_cache.ReadCache(1 << 20)
    e = c.epoch(1)
    # mutation lands between the read and the fill
    c.invalidate(1, 10)
    assert not c.put(1, 10, _needle(10, b"stale"), epoch=e)
    assert c.get(1, 10, 7) is None
    # a fresh fill with a current epoch is admitted
    assert c.put(1, 10, _needle(10, b"fresh"), epoch=c.epoch(1))
    assert c.get(1, 10, 7).data == b"fresh"


def test_whole_volume_invalidation_bumps_epoch():
    c = read_cache.ReadCache(1 << 20)
    e = c.epoch(3)
    c.put(3, 1, _needle(1, b"a"))
    c.put(3, 2, _needle(2, b"b"))
    c.put(4, 1, _needle(1, b"other-vid"))
    c.invalidate(3)
    assert c.get(3, 1, 7) is None and c.get(3, 2, 7) is None
    assert c.get(4, 1, 7) is not None  # other volume untouched
    assert not c.put(3, 1, _needle(1, b"stale"), epoch=e)


def test_bytes_gauge_never_negative_under_churn():
    """Concurrent put/get/invalidate/clear churn across two caches: the
    shared delta-accounted gauge must stay >= 0 at every sample and
    return to its baseline once both caches are cleared."""
    base = READ_CACHE_BYTES.value()
    caches = [read_cache.ReadCache(50_000), read_cache.ReadCache(30_000)]
    stop = threading.Event()
    floor = [0.0]

    def sampler():
        while not stop.is_set():
            floor[0] = min(floor[0], READ_CACHE_BYTES.value() - base)

    def churn(c, seed):
        rng = __import__("random").Random(seed)
        for i in range(2000):
            k = rng.randrange(100)
            op = rng.random()
            if op < 0.5:
                c.put(1, k, _needle(k, b"z" * rng.randrange(1, 400)))
            elif op < 0.8:
                c.get(1, k, 7)
            elif op < 0.95:
                c.invalidate(1, k)
            else:
                c.invalidate(1)

    ts = [threading.Thread(target=churn, args=(c, i))
          for i, c in enumerate(caches) for _ in range(2)]
    smp = threading.Thread(target=sampler)
    smp.start()
    for t in ts:
        t.start()
    for t in ts:
        t.join()
    stop.set()
    smp.join()
    assert floor[0] >= 0, f"gauge dipped {floor[0]} below baseline"
    for c in caches:
        c.clear()
        assert c.bytes_used == 0
    assert READ_CACHE_BYTES.value() - base == pytest.approx(0)


# ---------------------------------------------------------------------------
# storage-layer coherence: every mutation path invalidates
# ---------------------------------------------------------------------------

@pytest.fixture
def vol_and_cache(tmp_path):
    cache = read_cache.ReadCache(1 << 20)
    v = Volume(str(tmp_path), "", 42)
    yield v, cache
    v.close()


def _cache_fill(cache, v, key, cookie=7):
    """Fill the cache the way the volume server does: epoch before the
    storage read, put after."""
    e = cache.epoch(v.id)
    n = v.read_needle(key, cookie=cookie)
    cache.put(v.id, key, n, epoch=e)
    return n


def test_invalidate_on_delete(vol_and_cache):
    v, cache = vol_and_cache
    v.write_needle(Needle(id=1, cookie=7, data=b"live"))
    _cache_fill(cache, v, 1)
    assert cache.get(42, 1, 7).data == b"live"
    v.delete_needle(1)
    assert cache.get(42, 1, 7) is None
    with pytest.raises(KeyError):
        v.read_needle(1)


def test_invalidate_on_overwrite(vol_and_cache):
    v, cache = vol_and_cache
    v.write_needle(Needle(id=1, cookie=7, data=b"old"))
    _cache_fill(cache, v, 1)
    v.write_needle(Needle(id=1, cookie=7, data=b"new"))
    assert cache.get(42, 1, 7) is None
    assert _cache_fill(cache, v, 1).data == b"new"
    assert cache.get(42, 1, 7).data == b"new"


def test_invalidate_on_bulk_frame_append(vol_and_cache):
    v, cache = vol_and_cache
    v.write_needle(Needle(id=1, cookie=7, data=b"old-1"))
    v.write_needle(Needle(id=2, cookie=7, data=b"old-2"))
    for k in (1, 2):
        _cache_fill(cache, v, k)
    # a bulk frame overwriting both keys (put_many path)
    v.write_needles([Needle(id=1, cookie=7, data=b"bulk-1"),
                     Needle(id=2, cookie=7, data=b"bulk-2")])
    assert cache.get(42, 1, 7) is None and cache.get(42, 2, 7) is None
    assert v.read_needle(1).data == b"bulk-1"
    assert v.read_needle(2).data == b"bulk-2"


def test_invalidate_on_tail_replay(vol_and_cache, tmp_path):
    v, cache = vol_and_cache
    v.write_needle(Needle(id=1, cookie=7, data=b"old"))
    _cache_fill(cache, v, 1)
    # build a donor record for the same key and replay it (tail path)
    ddir = tmp_path / "donor"
    ddir.mkdir()
    donor = Volume(str(ddir), "", 42)
    off = donor.write_needle(Needle(id=1, cookie=7, data=b"replayed"))
    donor.sync()
    rec = donor.read_raw(off, donor._append_offset - off)
    donor.close()
    v.append_records(rec)
    assert cache.get(42, 1, 7) is None
    assert v.read_needle(1).data == b"replayed"


def test_invalidate_on_vacuum_compaction(tmp_path):
    cache = read_cache.ReadCache(1 << 20)
    v = Volume(str(tmp_path), "", 43)
    for k in range(1, 6):
        v.write_needle(Needle(id=k, cookie=7, data=b"v%d" % k))
    v.delete_needle(1)  # garbage so compaction moves offsets
    for k in range(2, 6):
        e = cache.epoch(43)
        cache.put(43, k, v.read_needle(k), epoch=e)
    compact(v)
    newv = commit_compact(v)
    try:
        # every cached entry for the volume dropped (offsets moved)
        for k in range(2, 6):
            assert cache.get(43, k, 7) is None
        for k in range(2, 6):
            assert newv.read_needle(k).data == b"v%d" % k
    finally:
        newv.close()


def test_invalidate_on_unmount(tmp_path):
    cache = read_cache.ReadCache(1 << 20)
    store = Store("127.0.0.1", 0, "",
                  [DiskLocation(str(tmp_path), max_volume_count=4)])
    v = store.add_volume(44)
    v.write_needle(Needle(id=9, cookie=7, data=b"bye"))
    e = cache.epoch(44)
    cache.put(44, 9, store.read_needle(44, 9), epoch=e)
    assert cache.get(44, 9, 7) is not None
    assert store.unmount_volume(44)
    assert cache.get(44, 9, 7) is None
    store.close()


# a read-through loop (get, fill on a miss) over a key sequence: the
# counters move by exactly the gets, and a warm Zipfian(1.2) workload
# over 2000 x 1 KB needles lives in a cache an eighth of its size:
# at least every second read hits
@pytest.mark.parametrize("keys,capacity", [
    ([1, 1], 1 << 20),
    (((np.random.default_rng(1000).zipf(1.2, 5296) - 1) % 2000).tolist(),
     2000 * 1000 // 8),
], ids=["cold-then-warm", "warm-zipfian"])
def test_hit_miss_counters_move(keys, capacity):
    h0, m0 = READ_CACHE_HITS.value(), READ_CACHE_MISSES.value()
    c = read_cache.ReadCache(capacity)
    for k in keys:
        if c.get(5, k, 7) is None:
            c.put(5, k, _needle(k, b"x" * 1000))
    hits = READ_CACHE_HITS.value() - h0
    misses = READ_CACHE_MISSES.value() - m0
    assert hits + misses == len(keys)
    assert misses >= len(set(keys))
    assert hits >= len(keys) // 2
