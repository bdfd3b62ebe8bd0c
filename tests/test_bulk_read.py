"""Read-path data plane (ISSUE 9): the SWBR/SWBG bulk-GET framing, the
lock-free (seqlock) volume read protocol, the /bulk-read volume-server
handler + operation.read_batch client, and the Range-request semantics
that must hold identically across cache / pread / EC read paths."""

import socket
import threading
import time

import pytest
from conftest import wait_until

from seaweedfs_tpu.client import http_util, operation
from seaweedfs_tpu.client.master_client import MasterClient
from seaweedfs_tpu.master.master_server import MasterServer
from seaweedfs_tpu.server.volume_server import VolumeServer
from seaweedfs_tpu.storage import bulk
from seaweedfs_tpu.storage.disk_location import DiskLocation
from seaweedfs_tpu.storage.needle import Needle
from seaweedfs_tpu.storage.store import Store
from seaweedfs_tpu.storage.types import file_id, parse_file_id
from seaweedfs_tpu.storage.volume import Volume


def free_port():
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


# ---------------------------------------------------------------------------
# wire frames
# ---------------------------------------------------------------------------

def test_read_request_roundtrip():
    pairs = [(100 + i, 0xC0FFEE + i) for i in range(50)]
    frame = bulk.pack_read_request(9, pairs)
    vid, got = bulk.unpack_read_request(frame)
    assert vid == 9 and got == pairs


def test_read_request_rejects_malformed():
    frame = bulk.pack_read_request(1, [(5, 7)])
    with pytest.raises(bulk.FrameError):
        bulk.unpack_read_request(frame[:-1])  # truncated
    with pytest.raises(bulk.FrameError):
        bulk.unpack_read_request(frame + b"x")  # trailing bytes
    with pytest.raises(bulk.FrameError):
        bulk.unpack_read_request(b"NOPE" + frame[4:])  # bad magic
    with pytest.raises(bulk.FrameError):
        bulk.pack_read_request(1, [])  # empty


def test_read_response_roundtrip_and_statuses():
    results = [
        (1, 7, bulk.READ_OK, 0x01, b"gzipped-bytes"),
        (2, 7, bulk.READ_NOT_FOUND, 0, b""),
        (3, 7, bulk.READ_ERROR, 0, b"ignored-for-non-ok"),
        (4, 7, bulk.READ_OK, 0, b""),  # empty live needle stays OK
    ]
    frame = bulk.pack_read_response(5, results)
    vid, got = bulk.unpack_read_response(frame)
    assert vid == 5
    assert [(r.key, r.status, r.flags, bytes(r.data)) for r in got] == [
        (1, bulk.READ_OK, 0x01, b"gzipped-bytes"),
        (2, bulk.READ_NOT_FOUND, 0, b""),
        (3, bulk.READ_ERROR, 0, b""),  # non-OK never carries payload
        (4, bulk.READ_OK, 0, b""),
    ]


def test_read_response_crc_rejects_corruption():
    frame = bytearray(bulk.pack_read_response(
        1, [(1, 7, bulk.READ_OK, 0, b"payload-bytes")]))
    frame[-1] ^= 0xFF
    with pytest.raises(bulk.FrameError):
        bulk.unpack_read_response(bytes(frame))


# ---------------------------------------------------------------------------
# seqlock read protocol (storage layer)
# ---------------------------------------------------------------------------

def test_bulk_read_statuses_from_volume(tmp_path):
    v = Volume(str(tmp_path), "", 3)
    v.write_needle(Needle(id=1, cookie=7, data=b"one"))
    v.write_needle(Needle(id=2, cookie=8, data=b"two"))
    v.delete_needle(2)
    got = v.read_needles([(1, 7), (2, 8), (99, 0), (1, 999)])
    assert [s for s, _ in got] == [bulk.READ_OK, bulk.READ_NOT_FOUND,
                                   bulk.READ_NOT_FOUND, bulk.READ_ERROR]
    assert got[0][1].data == b"one"
    v.close()


def test_parallel_reads_while_writer_fsyncs(tmp_path):
    """The seqlock guarantee: concurrent readers stay correct (and make
    progress) while a writer appends + fsyncs + deletes in a loop. The
    stable key set must read back byte-identical on every attempt."""
    v = Volume(str(tmp_path), "", 4)
    stable = {k: b"stable-%04d" % k + bytes([k & 0xFF]) * 100
              for k in range(1, 101)}
    for k, data in stable.items():
        v.write_needle(Needle(id=k, cookie=1, data=data))
    stop = threading.Event()
    errors: list = []

    def writer():
        i = 1000
        while not stop.is_set():
            v.write_needle(Needle(id=i, cookie=1, data=b"churn" * 50))
            v.sync()  # the fsync readers must NOT queue behind
            if i % 3 == 0:
                v.delete_needle(i)
            i += 1

    def reader(seed):
        import random
        rng = random.Random(seed)
        while not stop.is_set():
            k = rng.randrange(1, 101)
            try:
                n = v.read_needle(k, cookie=1)
                if n.data != stable[k]:
                    errors.append((k, "bytes diverged"))
            except Exception as e:  # noqa: BLE001
                errors.append((k, repr(e)))

    ts = [threading.Thread(target=writer)] + \
         [threading.Thread(target=reader, args=(s,)) for s in range(3)]
    for t in ts:
        t.start()
    time.sleep(1.0)
    stop.set()
    for t in ts:
        t.join(timeout=10)
    v.close()
    assert not errors, errors[:5]


def test_reads_survive_vacuum_commit_swap(tmp_path):
    """A read racing the vacuum commit's volume-object swap retries
    through the store's refreshed mapping (VolumeClosedError path)
    instead of 500ing."""
    from seaweedfs_tpu.storage.vacuum import commit_compact, compact

    store = Store("127.0.0.1", 0, "",
                  [DiskLocation(str(tmp_path), max_volume_count=4)])
    v = store.add_volume(5)
    for k in range(1, 51):
        v.write_needle(Needle(id=k, cookie=1, data=b"x%04d" % k * 20))
    v.delete_needle(1)
    stop = threading.Event()
    errors: list = []

    def reader(seed):
        import random
        rng = random.Random(seed)
        while not stop.is_set():
            k = rng.randrange(2, 51)
            try:
                n = store.read_needle(5, k, cookie=1)
                assert n.data == b"x%04d" % k * 20
            except Exception as e:  # noqa: BLE001
                errors.append(repr(e))

    ts = [threading.Thread(target=reader, args=(s,)) for s in range(3)]
    for t in ts:
        t.start()
    loc = store.locations[0]
    for _ in range(3):  # several swaps while readers hammer
        vol = store.find_volume(5)
        compact(vol)
        newv = commit_compact(vol)
        loc.volumes[5] = newv
    time.sleep(0.2)
    stop.set()
    for t in ts:
        t.join(timeout=10)
    store.close()
    assert not errors, errors[:5]


def test_compactmap_get_safe_during_merge(monkeypatch):
    """Lock-free nm.get racing CompactMap._merge: the base triple is
    swapped atomically, so a reader can never index the new keys against
    the old offsets (wrong record / IndexError for a healthy needle)."""
    from seaweedfs_tpu.storage.needle_map import CompactMap

    monkeypatch.setattr(CompactMap, "MERGE_THRESHOLD", 64)
    m = CompactMap()
    # a broad stable base so merges rebuild large arrays while readers
    # binary-search them
    for k in range(1, 2001):
        m.set(k, k, 100 + (k % 50))
    stop = threading.Event()
    errors: list = []

    def writer():
        k = 10_000
        while not stop.is_set():
            m.set(k, k, 100)  # every 64 sets triggers a merge
            k += 1

    def reader(seed):
        import random
        rng = random.Random(seed)
        while not stop.is_set():
            k = rng.randrange(1, 2001)
            try:
                nv = m.get(k)
                if nv is None or nv.size != 100 + (k % 50):
                    errors.append((k, nv))
            except Exception as e:  # noqa: BLE001
                errors.append((k, repr(e)))

    ts = [threading.Thread(target=writer)] + \
         [threading.Thread(target=reader, args=(s,)) for s in range(3)]
    for t in ts:
        t.start()
    time.sleep(1.0)
    stop.set()
    for t in ts:
        t.join(timeout=10)
    assert not errors, errors[:5]


# ---------------------------------------------------------------------------
# mini-cluster e2e: /bulk-read + read_batch + Range cross-path equality
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def cluster(tmp_path_factory):
    import os
    mport = free_port()
    master = MasterServer(port=mport, volume_size_limit_mb=64,
                          pulse_seconds=0.3)
    master.start()
    d = tmp_path_factory.mktemp("bulkread")
    vport = free_port()
    store = Store("127.0.0.1", vport, "",
                  [DiskLocation(str(d), max_volume_count=8)],
                  coder_name="numpy")
    vs = VolumeServer(store, f"127.0.0.1:{mport}", port=vport,
                      grpc_port=free_port(), pulse_seconds=0.3)
    vs.start()
    deadline = time.time() + 15
    while time.time() < deadline:
        try:
            if http_util.get(f"http://{vs.url}/status", timeout=1).ok:
                break
        except Exception:  # noqa: BLE001
            time.sleep(0.1)
    mc = MasterClient(f"127.0.0.1:{mport}").start()
    mc.wait_connected()
    # an assign before the server's first heartbeat finds no slots to grow
    wait_until(lambda: master.topo.all_nodes(), timeout=15,
               msg="the volume server registered")
    yield master, vs, mc
    mc.stop()
    vs.stop()
    master.stop()


def test_read_batch_e2e(cluster):
    _, vs, mc = cluster
    payloads = [b"bulk-%03d-" % i + bytes([i]) * (i * 7 % 900)
                for i in range(64)]
    res = operation.submit_batch(mc, payloads)
    fids = [r.fid for r in res]
    vid, _, cookie = parse_file_id(fids[0])
    ghost = file_id(vid, 0xDEAD_BEEF, cookie)  # never-written key
    operation.delete(mc, fids[3])
    wait_until(lambda: True, timeout=0.1)
    got = operation.read_batch(mc, fids + [ghost])
    for i, data in enumerate(got[:64]):
        if i == 3:
            assert data is None  # deleted -> per-needle miss, not an error
        else:
            assert data == payloads[i], f"fid {i} diverged"
    assert got[64] is None


def test_read_batch_matches_read_for_gzip(cluster):
    """submit() gzips compressible payloads; read() and read_batch()
    must return identical identity bytes."""
    _, _, mc = cluster
    text = (b"compress me " * 200)
    r = operation.submit(mc, text, name="doc.txt", mime="text/plain")
    assert operation.read(mc, r.fid) == text
    assert operation.read_batch(mc, [r.fid]) == [text]


def test_bulk_read_handler_rejects(cluster):
    _, vs, mc = cluster
    r = http_util.request("POST", f"http://{vs.url}/bulk-read",
                          body=b"garbage")
    assert r.status == 400
    frame = bulk.pack_read_request(1, [(1, 2)])
    r = http_util.request("POST", f"http://{vs.url}/bulk-read?vid=999",
                          body=frame)
    assert r.status == 400  # query/frame vid mismatch
    r = http_util.request("POST", f"http://{vs.url}/bulk-read",
                          body=bulk.pack_read_request(424242, [(1, 2)]))
    assert r.status == 404  # vid not local: client fails over, no proxy


def test_bulk_read_frame_byte_budget_overflow(cluster, monkeypatch):
    """A frame of needles larger than the server's byte budget comes
    back READ_OVERFLOW past the cap (never materialized server-side)
    and read_batch transparently re-fetches those per-needle — the
    caller still sees every byte."""
    _, vs, mc = cluster
    payloads = [b"big-%d-" % i + bytes([i]) * 4000 for i in range(6)]
    res = operation.submit_batch(mc, payloads, collection="ovf")
    fids = [r.fid for r in res]
    if vs.read_cache is not None:
        vs.read_cache.clear()  # budget applies to storage reads
    monkeypatch.setenv("SWTPU_BULK_READ_FRAME_BYTES", "9000")
    # raw frame: past ~9000 payload bytes the server answers OVERFLOW
    vid, _, _ = parse_file_id(fids[0])
    frame = bulk.pack_read_request(
        vid, [parse_file_id(f)[1:] for f in fids])
    if vs.read_cache is not None:
        vs.read_cache.invalidate(vid)
    r = http_util.request("POST", f"http://{vs.url}/bulk-read", body=frame)
    assert r.status == 200
    _, results = bulk.unpack_read_response(r.content)
    statuses = [rr.status for rr in results]
    assert bulk.READ_OVERFLOW in statuses, statuses
    assert statuses[0] == bulk.READ_OK  # budget admits the first reads
    # the client-side path papers over the overflow per-needle
    got = operation.read_batch(mc, fids)
    assert got == payloads


def test_read_batch_fails_over_on_corrupt_replica(tmp_path):
    """A needle whose record is corrupt on one holder must come back
    intact from the replica (READ_ERROR triggers frame failover), never
    as None — corruption is not 'deleted'."""
    import os as _os

    mport = free_port()
    master = MasterServer(port=mport, volume_size_limit_mb=64,
                          pulse_seconds=0.3)
    master.start()
    servers = []
    try:
        for i in range(2):
            d = tmp_path / f"v{i}"
            d.mkdir()
            vport = free_port()
            store = Store("127.0.0.1", vport, "",
                          [DiskLocation(str(d), max_volume_count=4)],
                          coder_name="numpy")
            vs = VolumeServer(store, f"127.0.0.1:{mport}", port=vport,
                              grpc_port=free_port(), pulse_seconds=0.3)
            vs.start()
            servers.append(vs)
        for vs in servers:
            deadline = time.time() + 15
            while time.time() < deadline:
                try:
                    if http_util.get(f"http://{vs.url}/status",
                                     timeout=1).ok:
                        break
                except Exception:  # noqa: BLE001
                    time.sleep(0.1)
        mc = MasterClient(f"127.0.0.1:{mport}").start()
        mc.wait_connected()
        payload = b"keep me intact " * 100
        r = operation.submit(mc, payload, replication="001")
        vid, key, cookie = parse_file_id(r.fid)
        wait_until(lambda: sum(1 for vs in servers
                               if vs.store.find_volume(vid) is not None)
                   == 2, msg="both replicas mounted")
        # corrupt the payload bytes on ONE holder (CRC now fails there)
        victim = next(vs for vs in servers
                      if vs.store.find_volume(vid) is not None)
        v = victim.store.find_volume(vid)
        nv = v.nm.get(key)
        _os.pwrite(v._fileno, b"\xde\xad\xbe\xef", nv.offset + 20)
        if victim.read_cache is not None:
            victim.read_cache.invalidate(vid)
        for _ in range(4):  # whatever holder order the client picks
            assert operation.read_batch(mc, [r.fid]) == [payload]
        mc.stop()
    finally:
        for vs in servers:
            vs.stop()
        master.stop()


def test_invalidate_many_single_epoch_bump(tmp_path):
    from seaweedfs_tpu.storage import read_cache as rc
    c = rc.ReadCache(1 << 20)
    for k in range(5):
        n = Needle(id=k, cookie=7, data=b"x%d" % k)
        n.to_bytes()
        c.put(9, k, n)
    e = c.epoch(9)
    c.invalidate_many(9, [0, 1, 2])
    assert c.epoch(9) == e + 1  # one bump for the whole batch
    assert c.get(9, 0, 7) is None and c.get(9, 2, 7) is None
    assert c.get(9, 3, 7) is not None
    assert c.bytes_used >= 0


def test_proxy_read_serves_identity_for_gzip_needles(tmp_path):
    """A gzip-stored needle proxied through a non-holder must reach a
    client that never advertised gzip as IDENTITY bytes — the proxy hop
    must not let aiohttp's default Accept-Encoding header widen what the
    client asked for (auto_decompress is off on the hop)."""
    mport = free_port()
    master = MasterServer(port=mport, volume_size_limit_mb=64,
                          pulse_seconds=0.3)
    master.start()
    servers = []
    try:
        for i in range(2):
            d = tmp_path / f"v{i}"
            d.mkdir()
            vport = free_port()
            store = Store("127.0.0.1", vport, "",
                          [DiskLocation(str(d), max_volume_count=4)],
                          coder_name="numpy")
            vs = VolumeServer(store, f"127.0.0.1:{mport}", port=vport,
                              grpc_port=free_port(), pulse_seconds=0.3)
            vs.start()
            servers.append(vs)
        for vs in servers:
            deadline = time.time() + 15
            while time.time() < deadline:
                try:
                    if http_util.get(f"http://{vs.url}/status",
                                     timeout=1).ok:
                        break
                except Exception:  # noqa: BLE001
                    time.sleep(0.1)
        mc = MasterClient(f"127.0.0.1:{mport}").start()
        mc.wait_connected()
        text = b"gzip me please " * 300  # compressible: stored gzipped
        r = operation.submit(mc, text, name="doc.txt", mime="text/plain")
        holder_url = mc.lookup_file_id(r.fid)[0]
        non_holder = next(vs for vs in servers
                          if f":{vs.port}/" not in holder_url + "/")
        # no Accept-Encoding header: the client wants identity
        got = http_util.get(f"http://{non_holder.url}/{r.fid}")
        assert got.status == 200
        assert got.headers.get("content-encoding") is None, got.headers
        assert got.content == text, \
            f"proxied gzip needle not identity ({len(got.content)}B)"
        mc.stop()
    finally:
        for vs in servers:
            vs.stop()
        master.stop()


def test_bulk_read_guard_enforces_per_fid_scope(tmp_path):
    """A read token for fid A admits a bulk-read frame of exactly {A}
    and nothing wider — /bulk-read must not widen per-fid read tokens
    into a read-everything pass."""
    from seaweedfs_tpu.security import Guard
    from seaweedfs_tpu.security.jwt import gen_jwt_for_volume_server

    mport = free_port()
    master = MasterServer(port=mport, volume_size_limit_mb=64,
                          pulse_seconds=0.3)
    master.start()
    vs = None
    try:
        d = tmp_path / "v"
        d.mkdir()
        vport = free_port()
        store = Store("127.0.0.1", vport, "",
                      [DiskLocation(str(d), max_volume_count=4)],
                      coder_name="numpy")
        vs = VolumeServer(store, f"127.0.0.1:{mport}", port=vport,
                          grpc_port=free_port(), pulse_seconds=0.3,
                          guard=Guard(read_signing_key="rk"))
        vs.start()
        deadline = time.time() + 15
        while time.time() < deadline:
            try:
                if http_util.get(f"http://{vs.url}/status", timeout=1).ok:
                    break
            except Exception:  # noqa: BLE001
                time.sleep(0.1)
        v = store.add_volume(1)
        v.write_needle(Needle(id=10, cookie=5, data=b"A"))
        v.write_needle(Needle(id=11, cookie=5, data=b"B"))
        fid_a = file_id(1, 10, 5)
        tok = gen_jwt_for_volume_server("rk", 60, fid_a)
        url = f"http://{vs.url}/bulk-read"
        # no token: 401
        r = http_util.request("POST", url,
                              body=bulk.pack_read_request(1, [(10, 5)]))
        assert r.status == 401
        # token for A, frame {A}: allowed
        r = http_util.request("POST", url,
                              body=bulk.pack_read_request(1, [(10, 5)]),
                              params={"jwt": tok})
        assert r.status == 200
        _, res = bulk.unpack_read_response(r.content)
        assert bytes(res[0].data) == b"A"
        # token for A, frame {A, B}: rejected whole (scope violation)
        r = http_util.request(
            "POST", url, body=bulk.pack_read_request(1, [(10, 5), (11, 5)]),
            params={"jwt": tok})
        assert r.status == 401
    finally:
        if vs is not None:
            vs.stop()
        master.stop()


def test_range_semantics_identical_across_paths(cluster):
    """The cross-path equality gate: a ranged GET returns identical
    bytes/status/headers whether the needle comes from the volume pread
    (cold), the hot-needle cache (warm), or an EC volume read after the
    volume is converted — and suffix/open/unsatisfiable forms behave."""
    _, vs, mc = cluster
    payload = bytes(range(256)) * 8  # 2048 distinctive bytes
    r = operation.submit(mc, payload, collection="rng")
    fid = r.fid
    vid, key, _ = parse_file_id(fid)
    url = f"http://{vs.url}/{fid}"

    def ranged(spec):
        resp = http_util.request("GET", url, headers={"Range": spec})
        return (resp.status, resp.content,
                resp.headers.get("content-range"))

    vs.read_cache.invalidate(vid)  # cold: pread path
    cold = {spec: ranged(spec) for spec in
            ("bytes=0-9", "bytes=100-1999", "bytes=2000-",
             "bytes=-17", "bytes=4000-5000", "bytes=0-999999")}
    warm = {spec: ranged(spec) for spec in cold}  # cache path
    assert cold == warm
    assert cold["bytes=0-9"] == (206, payload[:10], "bytes 0-9/2048")
    assert cold["bytes=100-1999"][1] == payload[100:2000]
    assert cold["bytes=2000-"] == (206, payload[2000:],
                                   "bytes 2000-2047/2048")
    assert cold["bytes=-17"] == (206, payload[-17:],
                                 "bytes 2031-2047/2048")
    assert cold["bytes=4000-5000"][0] == 416
    assert cold["bytes=0-999999"] == (206, payload, "bytes 0-2047/2048")
    # full (un-ranged) read still 200
    full = http_util.get(url)
    assert full.status == 200 and full.content == payload

    # convert the volume to EC on the same server: reads now resolve
    # through the EC volume — the ranged answers must not move
    store = vs.store
    store.mark_readonly(vid)
    store.generate_ec_shards(vid, "rng")
    store.mount_ec_shards(vid, "rng")
    store.delete_volume(vid)
    assert store.find_volume(vid) is None
    assert store.find_ec_volume(vid) is not None
    ec = {spec: ranged(spec) for spec in cold}
    assert ec == cold
    # bulk read across the EC path serves the same bytes too
    assert operation.read_batch(mc, [fid]) == [payload]
