"""A rebuild's read path allocates nothing a batch: the batch buffers
outlive a rebuild (ec/buffers.py) and every loader puts its survivor's
bytes straight into its rows (ec/repair.py: `land`, the `load` form of
`make_readers`; server/volume_server.py: the range fetch with `into`).
Held here: bytes rebuilt through dirty reused buffers, who owns a set
and for how long, `warm_batches`, and the remote landing for any message
size."""

import os
import threading
import time
from contextlib import contextmanager

import numpy as np
import pytest

from seaweedfs_tpu import tracing
from seaweedfs_tpu.ec import buffers, files, repair
from seaweedfs_tpu.ec.encoder import encode_volume, rebuild_shards
from seaweedfs_tpu.ec.locate import EcGeometry
from seaweedfs_tpu.ops.coder import NumpyCoder
from seaweedfs_tpu.ops.piggyback import PiggybackCoder
from seaweedfs_tpu.pb import volume_server_pb2 as vpb
from seaweedfs_tpu.server import volume_server
from seaweedfs_tpu.stats import REPAIR_BYTES_READ
from seaweedfs_tpu.utils import failpoints, retry

D, P = 10, 4
GEO = EcGeometry(d=D, p=P, large_block=1 << 14, small_block=512)
# 768 divides no shard size of 512-byte blocks evenly: the last batch is
# short and the range ends inside its last row
CHUNK, BATCH = 768, 4


@pytest.fixture(autouse=True)
def own_holder(monkeypatch):
    """Each test starts as a process does: nothing kept."""
    kept = buffers.KeptBuffers()
    monkeypatch.setattr(buffers, "REBUILD", kept)
    yield kept
    with kept._lock:
        kept._cancel_timer()


def sealed(tmp_path, name, rows, coder, seed=7):
    """A volume of `rows` small-block rows and a tail, sealed by `coder`;
    returns (base, {sid: bytes})."""
    base = str(tmp_path / name)
    rng = np.random.default_rng(seed + rows)
    rng.integers(0, 256, D * 512 * rows + 77, dtype=np.uint8).tofile(
        base + ".dat")
    encode_volume(base + ".dat", base, GEO, coder, chunk=512, batch=4)
    shards = {}
    for sid in range(GEO.n):
        with open(base + files.shard_ext(sid), "rb") as f:
            shards[sid] = f.read()
    return base, shards


def lose(base, sids):
    for sid in sids:
        os.unlink(base + files.shard_ext(sid))


def hold_even(base, shards, lost):
    """The even survivors leave the disk for a holder's memory."""
    held = {sid: shards[sid] for sid in shards
            if sid % 2 == 0 and sid not in lost}
    lose(base, held)
    return held


def dirty(kept, byte=0xA5):
    """Everything the kept set holds is overwritten: what a rebuild finds
    there must never reach a shard."""
    with kept._lock:
        assert kept._kept is not None, "no set was kept"
        for flat in kept._kept.flat:
            flat[:] = byte


def rebuild(base, coder, lost, held=None, reader=None, **kw):
    stats: dict = {}
    held = held or {}
    if reader is None:
        def reader(sid, off, ln):
            return held[sid][off:off + ln]
    got = rebuild_shards(base, GEO, coder, wanted=list(lost), chunk=CHUNK,
                         batch=BATCH, shard_reader=reader,
                         remote_shards=sorted(held), stats=stats, **kw)
    assert got == sorted(lost)
    return stats


def same(base, shards, lost):
    for sid in lost:
        with open(base + files.shard_ext(sid), "rb") as f:
            assert f.read() == shards[sid], sid


FEEDERS = {
    "rs_one_shard": (lambda: NumpyCoder(D, P), [0], "full"),
    "rs_four_shards": (lambda: NumpyCoder(D, P), [0, 1, D, D + 1], "full"),
    "piggyback_group_of_four": (lambda: PiggybackCoder(D, P), [0], "ranged"),
    "piggyback_group_of_three": (lambda: PiggybackCoder(D, P), [5],
                                 "ranged"),
}


@pytest.mark.parametrize("survivors", ["local", "mixed"])
@pytest.mark.parametrize("feeder", sorted(FEEDERS))
def test_a_rebuild_through_dirty_reused_buffers_is_byte_identical(
        tmp_path, own_holder, feeder, survivors):
    make, lost, path = FEEDERS[feeder]
    coder = make()
    base, shards = sealed(tmp_path, "v", 49, coder)
    lose(base, lost)
    held = hold_even(base, shards, lost) if survivors == "mixed" else {}
    first = rebuild(base, coder, lost, held)
    same(base, shards, lost)
    assert first["path"] == path
    extent = len(shards[0]) if path == "full" else len(shards[0]) // 2
    assert extent % (CHUNK * BATCH) and extent % CHUNK  # a short last batch
    assert first["batches"] == -(-extent // (CHUNK * BATCH)) > 4
    assert first["warm_batches"] == 0
    dirty(own_holder)
    lose(base, lost)
    again = rebuild(base, coder, lost, held)
    same(base, shards, lost)
    assert again["warm_batches"] == again["batches"] == first["batches"]
    assert again["bytes_read"] == first["bytes_read"]
    assert again["bytes_written"] == first["bytes_written"] \
        == len(lost) * len(shards[0])


@pytest.mark.parametrize("order", ["small_then_large", "large_then_small"])
def test_two_rebuilds_of_different_shard_sizes_in_a_row(tmp_path, own_holder,
                                                        order):
    coder = NumpyCoder(D, P)
    small = sealed(tmp_path, "s", 3, coder)
    large = sealed(tmp_path, "l", 23, coder)
    assert len(small[1][0]) < CHUNK * BATCH < len(large[1][0])
    pair = [small, large] if order == "small_then_large" else [large, small]
    stats = []
    for base, shards in pair:
        lose(base, [1, D])
        held = hold_even(base, shards, [1, D])
        stats.append(rebuild(base, coder, [1, D], held))
        same(base, shards, [1, D])
        dirty(own_holder, 0x3C)
    first, second = stats
    assert first["warm_batches"] == 0
    if order == "small_then_large":
        # one short batch touched the head of the first buffer only: the
        # large one's first batch is longer than what was warm
        assert first["batches"] == 1 and second["warm_batches"] == 0
    else:
        assert second["warm_batches"] == second["batches"] == 1


def test_a_set_serves_any_shape_it_has_the_bytes_for(own_holder):
    """13 and 14 rows alternate where a piggyback volume loses a shard of
    a group of three, then of four: the larger set serves both."""
    with own_holder.lease((4, 14, 8), 4) as big:
        views = big.views((4, 14, 8))
        assert [v.shape for v in views] == [(4, 14, 8)] * 4
        assert all(v.flags.c_contiguous and v.flags.writeable for v in views)
        big.touched[0] = 4 * 14 * 8
    with own_holder.lease((4, 13, 8), 4) as again:
        assert again is big and again.touched[0] == 4 * 14 * 8
        small = again.views((4, 13, 8))
        assert all(np.shares_memory(s, f) for s, f in zip(small, big.flat))
    with own_holder.lease((4, 15, 8), 4) as larger:
        assert larger is not big and larger.touched == [0] * 4
    assert own_holder.kept_bytes() == 4 * 4 * 15 * 8
    with own_holder.lease((4, 15, 8), 3) as other_depth:
        assert other_depth is not larger


def test_two_rebuilds_at_the_same_time_never_share_a_buffer(
        tmp_path, own_holder, monkeypatch):
    coder = NumpyCoder(D, P)
    vols = [sealed(tmp_path, f"v{i}", 21, coder, seed=i) for i in range(2)]
    helds = []
    for base, shards in vols:
        lose(base, [0])
        helds.append(hold_even(base, shards, [0]))
    # a set is kept and free when the two start: one of them takes it
    base0, shards0 = vols[0]
    rebuild(base0, coder, [0], helds[0])
    lose(base0, [0])
    leased, inside = [], threading.Barrier(2, timeout=30)
    real = buffers.KeptBuffers.lease

    @contextmanager
    def spy(self, shape, count):
        with real(self, shape, count) as held:
            leased.append(held)
            yield held

    monkeypatch.setattr(buffers.KeptBuffers, "lease", spy)
    errors = []

    def run(i):
        first = [True]

        def reader(sid, off, ln):
            if first[0]:  # both rebuilds hold their buffers by now
                first[0] = False
                inside.wait()
            return helds[i][sid][off:off + ln]
        try:
            rebuild(vols[i][0], coder, [0], helds[i], reader=reader)
        except BaseException as e:  # noqa: BLE001
            errors.append(e)

    threads = [threading.Thread(target=run, args=(i,)) for i in range(2)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=60)
    assert not errors and not any(t.is_alive() for t in threads)
    a, b = leased
    assert a is not b
    assert not any(np.shares_memory(x, y) for x in a.flat for y in b.flat)
    for base, shards in vols:
        same(base, shards, [0])
    # and one of the two is the kept one now: the one handed back last
    with own_holder._lock:
        assert own_holder._kept in (a, b)


def test_the_kept_set_is_dropped_after_the_idle_time(tmp_path, own_holder,
                                                     monkeypatch):
    monkeypatch.setattr(buffers, "IDLE_DROP_S", 0.15)
    coder = NumpyCoder(D, P)
    base, shards = sealed(tmp_path, "v", 21, coder)
    lose(base, [2])
    rebuild(base, coder, [2])
    assert own_holder.kept_bytes() == 4 * BATCH * D * CHUNK
    lose(base, [2])
    time.sleep(0.05)
    again = rebuild(base, coder, [2])  # inside the idle time: warm
    assert again["warm_batches"] == again["batches"]
    deadline = time.monotonic() + 10
    while own_holder.kept_bytes() and time.monotonic() < deadline:
        time.sleep(0.02)
    assert own_holder.kept_bytes() == 0
    assert not [t for t in threading.enumerate()
                if isinstance(t, threading.Timer) and t.is_alive()]
    lose(base, [2])
    cold = rebuild(base, coder, [2])
    same(base, shards, [2])
    assert cold["warm_batches"] == 0


def test_a_set_in_use_is_not_dropped_and_its_timer_starts_at_the_end(
        own_holder, monkeypatch):
    monkeypatch.setattr(buffers, "IDLE_DROP_S", 0.1)
    with own_holder.lease((2, 2, 4), 4):
        pass
    with own_holder.lease((2, 2, 4), 4) as held:
        time.sleep(0.3)  # the first lease's timer would have fired
        assert own_holder.kept_bytes() == 0  # taken, not kept
    with own_holder._lock:
        assert own_holder._kept is held


def test_a_failed_rebuild_hands_no_set_back(tmp_path, own_holder):
    coder = NumpyCoder(D, P)
    base, shards = sealed(tmp_path, "v", 21, coder)
    lose(base, [0])
    held = hold_even(base, shards, [0])
    rebuild(base, coder, [0], held)
    assert own_holder.kept_bytes()
    lose(base, [0])

    def failing(sid, off, ln):
        if off:
            raise OSError("holder gone")
        return held[sid][off:off + ln]

    with pytest.raises(OSError, match="holder gone"):
        rebuild(base, coder, [0], held, reader=failing)
    # the set it had taken is gone: a transfer might still read from it
    assert own_holder.kept_bytes() == 0
    lose(base, [0])  # what the failed one had begun to write
    stats = rebuild(base, coder, [0], held)
    same(base, shards, [0])
    assert stats["warm_batches"] == 0


def test_warm_batches_reads_0_then_batches_on_the_span_too(tmp_path,
                                                           own_holder):
    coder = NumpyCoder(D, P)
    base, shards = sealed(tmp_path, "v", 21, coder)
    seen = []
    for _ in range(3):
        lose(base, [3])
        tracing.BUFFER.clear()
        stats = rebuild(base, coder, [3])
        (span,) = [s for s in tracing.BUFFER.snapshot(limit=5000)
                   if s["name"] == "ec.rebuild"]
        assert span["attrs"]["warm_batches"] == stats["warm_batches"]
        seen.append((stats["warm_batches"], stats["batches"]))
    n = seen[0][1]
    assert seen == [(0, n), (n, n), (n, n)]


def test_the_stages_still_account_for_a_warm_rebuild(tmp_path, own_holder):
    """read + dispatch + drain + write cover the call when the buffers
    are reused and the remote survivors land through `readinto`."""
    coder = NumpyCoder(D, P)
    base, shards = sealed(tmp_path, "v", 41, coder)
    lose(base, [0])
    held = hold_even(base, shards, [0])

    def reader(sid, off, ln):
        raise AssertionError("the landing form was there to be used")

    def readinto(sid, off, ln, rows):
        time.sleep(0.01)
        return repair.land(rows, 0, held[sid][off:off + ln], ln)
    reader.readinto = readinto
    for warm in (False, True):
        stats: dict = {}
        t0 = time.perf_counter()
        assert rebuild_shards(base, GEO, coder, wanted=[0], chunk=CHUNK,
                              batch=BATCH, shard_reader=reader,
                              remote_shards=sorted(held),
                              stats=stats) == [0]
        wall = time.perf_counter() - t0
        same(base, shards, [0])
        four = (stats["read_s"] + stats["dispatch_s"] + stats["drain_s"]
                + stats["write_s"])
        assert 0.5 * wall <= four <= wall
        assert stats["read_s"] >= stats["batches"] * 0.01
        asked = set(sorted(set(shards) - {0})[:D]) & set(held)
        assert len(asked) == 5 and stats["read_remote_busy_s"] >= \
            len(asked) * stats["batches"] * 0.01
        assert stats["read_busy_s"] == pytest.approx(
            stats["read_local_busy_s"] + stats["read_remote_busy_s"],
            abs=2e-4)
        assert (stats["warm_batches"] == stats["batches"]) == warm
        lose(base, [0])


def test_async_pipe_takes_the_buffers_it_is_given():
    from seaweedfs_tpu.ec.stream import AsyncPipe
    acct = tracing.StageAccount("t")
    mine = [np.full((2, 3, 4), i, dtype=np.uint8) for i in range(4)]
    pipe = AsyncPipe((2, 3, 4), acct, buffers=mine)
    got = [pipe.next_buffer() for _ in range(5)]
    assert all(g is m for g, m in zip(got, mine + mine[:1]))
    assert [int(m[0, 0, 0]) for m in mine] == [0, 1, 2, 3]  # untouched
    own = AsyncPipe((2, 3, 4), acct)  # the seal's feed: its own, zeroed
    assert len(own.pool) == 4 and not any(b.any() for b in own.pool)
    with pytest.raises(ValueError):
        AsyncPipe((2, 3, 4), acct, buffers=mine[:3])
    with pytest.raises(ValueError):
        AsyncPipe((2, 3, 5), acct, buffers=mine)


# -- landing a range in the rows --------------------------------------------

def rows_of(n, width, r=1, of=3):
    """Survivor r's rows of a dirty [n, of, width] batch."""
    arr = np.full((n, of, width), 0xEE, dtype=np.uint8)
    return arr, arr[:, r]


@pytest.mark.parametrize("width,msg,length", [
    (1000, 1000, 4000),   # messages are rows
    (1000, 333, 3500),    # divide nothing, the range ends inside a row
    (1000, 2500, 4000),   # a message spans rows
    (1000, 7, 999),       # less than a row
    (64, 4096, 64 * 5),   # one message, all rows
])
def test_land_puts_any_message_size_in_its_place(width, msg, length):
    n = -(-length // width)
    arr, rows = rows_of(n, width)
    src = np.random.default_rng(width + msg).integers(
        0, 256, length, dtype=np.uint8).tobytes()
    pos = 0
    for at in range(0, length, msg):
        pos = repair.land(rows, pos, src[at:at + msg], length)
    assert pos == length
    assert rows.reshape(-1)[:length].tobytes() == src
    # nothing else of the batch was written
    assert (rows.reshape(-1)[length:] == 0xEE).all()
    assert (arr[:, 0] == 0xEE).all() and (arr[:, 2] == 0xEE).all()


def test_land_drops_what_is_past_the_range_and_still_counts_it():
    arr, rows = rows_of(2, 10)
    assert repair.land(rows, 0, bytes(range(8)), 15) == 8
    assert repair.land(rows, 8, bytes(range(8, 20)), 15) == 20
    assert rows.reshape(-1)[:15].tolist() == list(range(15))
    assert (rows.reshape(-1)[15:] == 0xEE).all()
    assert repair.land(rows, 20, b"xyz", 15) == 23


class Peers:
    """Stands in for `Stub`: each address streams what the test says."""

    def __init__(self, monkeypatch, plays):
        self.plays = plays   # addr -> generator function(offset, size)
        self.asked = []
        monkeypatch.setattr(volume_server, "Stub", self.stub)
        retry.reset_breakers()

    def stub(self, addr, service):
        peers = self

        class _Stub:
            def call_stream(self, method, req, resp_cls, timeout=300.0):
                assert method == "VolumeEcShardRead"
                peers.asked.append((addr, req.offset, req.size))
                for data in peers.plays[addr](req.offset, req.size):
                    yield vpb.VolumeEcShardReadResponse(data=data)
        return _Stub()


def in_messages(shard, msg, cut=None, fail_after=None):
    def play(off, size):
        data = shard[off:off + size][:cut]
        for i, at in enumerate(range(0, len(data), msg)):
            if fail_after is not None and i == fail_after:
                raise ConnectionError("peer went away mid-range")
            yield data[at:at + msg]
    return play


@pytest.fixture
def server():
    vs = object.__new__(volume_server.VolumeServer)  # the fetch needs no state
    yield vs
    retry.reset_breakers()
    failpoints.clear_all()


SHARD = np.random.default_rng(38).integers(0, 256, 10_000,
                                           dtype=np.uint8).tobytes()


@pytest.mark.parametrize("msg", [1000, 333, 4096])
def test_a_remote_range_lands_in_the_rows_message_by_message(
        server, monkeypatch, msg):
    peers = Peers(monkeypatch, {"b:1": in_messages(SHARD, msg)})
    arr, rows = rows_of(4, 1000)
    got = server._fetch_range_or_raise(7, 2, 123, 3500, ["b:1"], into=rows)
    assert got == 3500 and peers.asked == [("b:1", 123, 3500)]
    assert rows.reshape(-1)[:3500].tobytes() == SHARD[123:3623]
    assert (rows.reshape(-1)[3500:] == 0xEE).all()
    # the returning form is what it was
    assert server._fetch_range_or_raise(7, 2, 123, 3500, ["b:1"]) \
        == SHARD[123:3623]


def test_a_peer_that_fails_mid_range_is_overwritten_and_bytes_count_once(
        server, monkeypatch):
    other = bytes(b ^ 0xFF for b in SHARD)  # what the failing peer sent
    peers = Peers(monkeypatch, {
        "bad:1": in_messages(other, 700, fail_after=3),
        "good:1": in_messages(SHARD, 700)})
    tracing.BUFFER.clear()

    def reader(sid, off, ln):
        raise AssertionError("the landing form was there to be used")
    reader.readinto = lambda sid, off, ln, rows: \
        server._fetch_range_or_raise(7, sid, off, ln, ["bad:1", "good:1"],
                                     into=rows)
    counter = repair.RepairCounter("rs")
    before = REPAIR_BYTES_READ.value("rs")
    _r, loaders, _f, close = repair.make_readers("x", {}, reader, [2],
                                                 counter)
    arr, rows = rows_of(4, 1000)
    loaders[2](500, 3300, rows)
    close()
    assert rows.reshape(-1)[:3300].tobytes() == SHARD[500:3800]
    assert (rows.reshape(-1)[3300:] == 0xEE).all()
    assert [a for a, _, _ in peers.asked] == ["bad:1", "good:1"]
    assert counter.bytes_read == 3300
    assert REPAIR_BYTES_READ.value("rs") - before == 3300
    # the breakers' books and the fetch span are what they were
    assert retry.breaker("bad:1")._failures == 1
    assert retry.breaker("good:1")._failures == 0
    (span,) = [s for s in tracing.BUFFER.snapshot(limit=100)
               if s["name"] == "ec.shard.fetch"]
    assert span["attrs"]["holder"] == "good:1"
    assert [e["name"] for e in span["events"]] == ["holder_failed"]


@pytest.mark.parametrize("cut,what", [(2000, "short"), (None, "long")])
def test_a_stream_of_the_wrong_length_is_an_error(server, monkeypatch, cut,
                                                  what):
    def long_play(off, size):
        yield from in_messages(SHARD, 900)(off, size)
        yield b"more than was asked for"
    play = in_messages(SHARD, 900, cut=cut) if what == "short" else long_play
    Peers(monkeypatch, {"b:1": play})

    def reader(sid, off, ln):
        return server._fetch_range_or_raise(7, sid, off, ln, ["b:1"])
    reader.readinto = lambda sid, off, ln, rows: \
        server._fetch_range_or_raise(7, sid, off, ln, ["b:1"], into=rows)
    counter = repair.RepairCounter("rs")
    readers, loaders, _f, close = repair.make_readers("x", {}, reader, [4],
                                                      counter)
    arr, rows = rows_of(3, 1000)
    with pytest.raises(OSError, match="short remote read of shard 4"):
        loaders[4](0, 2500, rows)
    with pytest.raises(OSError, match="short remote read of shard 4"):
        readers[4](0, 2500)  # as the returning form says it
    close()
    assert counter.bytes_read == 0
    assert (arr[:, 0] == 0xEE).all() and (arr[:, 2] == 0xEE).all()


def test_no_holder_left_is_an_error_and_both_failpoints_still_bite(
        server, monkeypatch):
    peers = Peers(monkeypatch, {"b:1": in_messages(SHARD, 512)})
    arr, rows = rows_of(4, 1000)
    with failpoints.inject("ec.shard.read", "error:injected-down"):
        with pytest.raises(OSError, match="unreachable"):
            server._fetch_range_or_raise(7, 2, 0, 3500, ["b:1"], into=rows)
    assert peers.asked == [] and (arr == 0xEE).all()
    with pytest.raises(OSError, match="unreachable"):
        server._fetch_range_or_raise(7, 2, 0, 3500, [], into=rows)
    failpoints.seed(38)
    with failpoints.inject("ec.shard.read.data", "times:1:corrupt:1"):
        assert server._fetch_range_or_raise(7, 2, 0, 3500, ["b:1"],
                                            into=rows) == 3500
    landed = np.frombuffer(rows.reshape(-1)[:3500].tobytes(), dtype=np.uint8)
    flipped = np.unpackbits(landed ^ np.frombuffer(SHARD[:3500], np.uint8))
    assert flipped.sum() == 1  # one bit of what landed, nothing around it
    assert (rows.reshape(-1)[3500:] == 0xEE).all()
    assert failpoints.fired("ec.shard.read.data") >= 1
    assert not failpoints.armed("ec.shard.read.data")
    assert server._fetch_range_or_raise(7, 2, 0, 3500, ["b:1"],
                                        into=rows) == 3500
    assert rows.reshape(-1)[:3500].tobytes() == SHARD[:3500]


def test_a_local_survivor_is_one_preadv_over_the_rows(tmp_path, monkeypatch):
    path = str(tmp_path / "s")
    with open(path, "wb") as f:
        f.write(SHARD)
    calls = []
    real = os.preadv
    monkeypatch.setattr(os, "preadv",
                        lambda fd, bufs, off: calls.append(
                            [len(b) for b in bufs]) or real(fd, bufs, off))
    monkeypatch.setattr(os, "pread", lambda *a: pytest.fail(
        "a load makes no bytes of its own"))
    counter = repair.RepairCounter("rs")
    _r, loaders, _f, close = repair.make_readers(str(tmp_path / "x"),
                                                 {9: path}, None, [], counter)
    arr, rows = rows_of(4, 1000)
    loaders[9](200, 3400, rows)
    assert calls == [[1000, 1000, 1000, 400]]
    assert rows.reshape(-1)[:3400].tobytes() == SHARD[200:3600]
    assert (rows.reshape(-1)[3400:] == 0xEE).all()
    assert (arr[:, 0] == 0xEE).all() and (arr[:, 2] == 0xEE).all()
    assert counter.bytes_read == 3400
    with pytest.raises(OSError, match="short read of shard 9"):
        loaders[9](len(SHARD) - 100, 3000, rows)  # runs off the file's end
    close()
    assert counter.bytes_read == 3400
