"""Operational CRC scrub (storage/scrub.py, VolumeScrub RPC,
volume.scrub shell command) — BASELINE config 4 wired into operations.

device="auto" follows the process's resolved backend (ops/device.py):
here the test env's CPU-jax, the same kernel the chip compiles, reported
as mode "xla-cpu" — "device" is a TPU's word. device="off" is the host
loop. Both must agree with the stored CRCs and both must catch injected
bit rot.
"""

import os
import socket
import struct

import pytest

from seaweedfs_tpu.storage.needle import Needle
from seaweedfs_tpu.storage.scrub import scrub_volume
from seaweedfs_tpu.storage.volume import Volume


def _fp():
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def _fill(v: Volume, n: int = 50) -> dict[int, bytes]:
    import numpy as np
    rng = np.random.default_rng(7)
    out = {}
    for i in range(1, n + 1):
        data = rng.integers(0, 256, int(rng.integers(1, 9000)),
                            dtype=np.uint8).tobytes()
        v.write_needle(Needle(id=i, cookie=1, data=data))
        out[i] = data
    return out


class TestScrubVolume:
    @pytest.fixture(autouse=True)
    def _resolved_backend(self):
        from seaweedfs_tpu.ops import device
        device.info()  # a device-coder process: "auto" means the kernel

    @pytest.mark.parametrize("device", ["off", "auto"])
    def test_clean_volume_scans_all(self, tmp_path, device):
        v = Volume(str(tmp_path), "", 1)
        _fill(v, 60)
        res = scrub_volume(v, device=device)
        assert res.scanned == 60
        assert res.corrupt == []
        assert res.bytes_checked > 0
        assert res.mode == ("cpu" if device == "off" else "xla-cpu")
        v.close()

    @pytest.mark.parametrize("device", ["off", "auto"])
    def test_detects_flipped_bytes(self, tmp_path, device):
        v = Volume(str(tmp_path), "", 1)
        _fill(v, 20)
        # flip one payload byte of needle 7 directly in the .dat
        nv = v.nm.get(7)
        with open(v.dat_path, "r+b") as f:
            # header(16) + dlen(4) -> first data byte
            f.seek(nv.offset + 20)
            b = f.read(1)
            f.seek(nv.offset + 20)
            f.write(bytes([b[0] ^ 0xFF]))
        res = scrub_volume(v, device=device)
        assert res.scanned == 20
        assert res.corrupt == [7]
        v.close()

    def test_tombstones_and_empty_needles_skipped(self, tmp_path):
        v = Volume(str(tmp_path), "", 1)
        v.write_needle(Needle(id=1, cookie=1, data=b"keep"))
        v.write_needle(Needle(id=2, cookie=1, data=b"gone"))
        v.write_needle(Needle(id=3, cookie=1, data=b""))  # zero-length
        v.delete_needle(2, cookie=1)
        res = scrub_volume(v, device="off")
        # needle 2's pre-vacuum garbage record is SKIPPED (liveness via
        # the needle map): rot in unreachable data must not alarm. Only
        # the two live needles are scanned; the tombstone is skipped too.
        assert res.scanned == 2
        assert res.corrupt == []
        assert res.error == ""
        v.close()

    def test_torn_walk_reported(self, tmp_path):
        """Header rot that desyncs the record chain is surfaced as a
        volume-level error, not silently reported clean."""
        v = Volume(str(tmp_path), "", 1)
        _fill(v, 10)
        nv = v.nm.get(5)
        with open(v.dat_path, "r+b") as f:
            f.seek(nv.offset + 12)  # the header's u32 size field
            f.write(struct.pack("<I", 0x0FFFFFFF))
        res = scrub_volume(v, device="off")
        assert "torn" in res.error
        assert res.scanned < 10  # the tail past the rot went unscanned
        v.close()

    def test_device_and_cpu_agree(self, tmp_path):
        v = Volume(str(tmp_path), "", 1)
        _fill(v, 40)
        r_cpu = scrub_volume(v, device="off")
        r_dev = scrub_volume(v, device="auto")
        assert r_cpu.scanned == r_dev.scanned == 40
        assert r_cpu.corrupt == r_dev.corrupt == []
        v.close()


# -- the pipeline: a block with the device thread while the next is walked
# and packed (PR 35). Blocks of 32 KiB here, so that a volume of a few MB
# fills its buckets many times; the program is the same whatever the shape.

_SMALL_BLOCK = 32 << 10
_ROTTEN = (3, 64, 65, 200, 411, 590, 600)


@pytest.fixture
def seeded(tmp_path, monkeypatch):
    """(volume, sizes by id in walk order) with the needles of _ROTTEN
    flipped on disk, the device gate resolved and small blocks."""
    import numpy as np

    from seaweedfs_tpu.ops import device
    from seaweedfs_tpu.storage import scrub
    device.info()
    monkeypatch.setattr(scrub, "_DISPATCH_BYTES", _SMALL_BLOCK)
    v = Volume(str(tmp_path), "", 1)
    rng = np.random.default_rng(35)
    sizes = {}
    for i in range(1, 601):
        sizes[i] = int(rng.integers(1, 9000))
        v.write_needle(Needle(id=i, cookie=1, data=rng.integers(
            0, 256, sizes[i], dtype=np.uint8).tobytes()))
    with open(v.dat_path, "r+b") as f:
        for nid in _ROTTEN:
            at = v.nm.get(nid).offset + 20 + sizes[nid] // 2
            f.seek(at)
            b = f.read(1)
            f.seek(at)
            f.write(bytes([b[0] ^ 0x5A]))
    yield v, sizes
    v.close()


def _dispatch_order(sizes: "dict[int, int]") -> "list[tuple[tuple, list]]":
    """(shape, ids) of every block in the order a sweep hands them over:
    a bucket's block when its last row fills, the partial ones at the
    volume's end in the order their first needle was walked."""
    from seaweedfs_tpu.storage import scrub
    pending, out = {}, []
    for nid, size in sizes.items():
        shape = scrub._block_shape(size)
        ids = pending.setdefault(shape, [])
        ids.append(nid)
        if len(ids) == shape[0]:
            out.append((shape, pending.pop(shape)))
    return out + list(pending.items())


def test_pipelined_sweep_agrees_with_host_loop_in_dispatch_order(seeded):
    v, sizes = seeded
    blocks = _dispatch_order(sizes)
    fills = {}
    for shape, ids in blocks:
        fills.setdefault(shape, []).append(len(ids) == shape[0])
    # several buckets fill more than twice, and blocks are left partial
    assert sum(sum(full) > 2 for full in fills.values()) >= 3
    assert sum(not full[-1] for full in fills.values()) >= 3
    host = scrub_volume(v, device="off")
    piped = scrub_volume(v, device="auto")
    assert piped.mode == "xla-cpu" and host.mode == "cpu"
    assert piped.scanned == host.scanned == len(sizes)
    assert piped.bytes_checked == host.bytes_checked == sum(sizes.values())
    assert set(piped.corrupt) == set(host.corrupt) == set(_ROTTEN)
    assert piped.blocks == len(blocks)
    assert piped.bytes_dispatched == sum(r * w for (r, w), _ in blocks)
    assert piped.corrupt == [nid for _, ids in blocks for nid in ids
                             if nid in _ROTTEN]
    assert host.blocks == host.bytes_dispatched == 0
    assert host.device_s == host.device_busy_s == 0.0


def _boom(*_a, **_kw):
    raise RuntimeError("XLA said no")


def _device_fails_on_third_block(monkeypatch):
    from seaweedfs_tpu.storage import scrub
    real, calls = scrub._crc_jit(), []

    def flaky(blocks):
        calls.append(1)
        return _boom() if len(calls) == 3 else real(blocks)
    monkeypatch.setattr(scrub, "_crc_jit", lambda: flaky)


def _walk_fails_midway(monkeypatch):
    from seaweedfs_tpu.storage import scrub
    real = scrub._iter_needles

    def walk(v, res):
        for n, needle in enumerate(real(v, res)):
            if n == 300:
                _boom()
            yield needle
    monkeypatch.setattr(scrub, "_iter_needles", walk)


def _tear_the_walk(v):
    with open(v.dat_path, "r+b") as f:
        f.seek(v.nm.get(400).offset + 12)  # the header's u32 size field
        f.write(struct.pack("<I", 0x0FFFFFFF))


@pytest.mark.parametrize("way_out", ["device_error", "walk_error",
                                     "torn_walk", "clean"])
def test_no_thread_outlives_a_sweep(seeded, monkeypatch, way_out):
    """What the device stage raises comes out of scrub_volume (the RPC's
    per-volume `except` isolates it there), and on every way out the
    sweep's device thread has been joined."""
    import threading
    v, _ = seeded
    if way_out == "device_error":
        _device_fails_on_third_block(monkeypatch)
    elif way_out == "walk_error":
        _walk_fails_midway(monkeypatch)
    elif way_out == "torn_walk":
        _tear_the_walk(v)
    before = threading.enumerate()
    if way_out.endswith("_error"):
        with pytest.raises(RuntimeError, match="XLA said no"):
            scrub_volume(v, device="auto")
    else:
        res = scrub_volume(v, device="auto")
        assert ("torn" in res.error) == (way_out == "torn_walk")
        assert res.blocks > 0
        assert set(res.corrupt) == {n for n in _ROTTEN
                                    if way_out == "clean" or n < 400}
    assert threading.enumerate() == before


def test_slow_device_stage_holds_the_sweep_at_its_bound(seeded, monkeypatch):
    """With the device side the slower one, the sweep's thread runs ahead
    by the bound and no further; its wait is `device_s`, under the device
    thread's own `device_busy_s`, and its four stages still partition the
    sweep."""
    import threading
    import time

    from seaweedfs_tpu.storage import scrub
    v, _ = seeded
    real_jit, real_pack = scrub._crc_jit(), scrub._pack
    real_finalize = scrub.crcmod.finalize
    alive, packed, compared, threads = [], [], [], set()

    def slow(blocks):
        threads.add(threading.current_thread().name)
        time.sleep(0.004)
        return real_jit(blocks)

    def pack(shape, datas):
        packed.append(1)  # this block, and those not yet compared
        alive.append(len(packed) - len(compared))
        return real_pack(shape, datas)

    def finalize(raw, lengths):
        compared.append(1)
        return real_finalize(raw, lengths)
    monkeypatch.setattr(scrub, "_crc_jit", lambda: slow)
    monkeypatch.setattr(scrub, "_pack", pack)
    monkeypatch.setattr(scrub.crcmod, "finalize", finalize)
    res = scrub_volume(v, device="auto")
    assert set(res.corrupt) == set(_ROTTEN)
    assert len(packed) == len(compared) == res.blocks
    assert max(alive) == scrub._IN_FLIGHT + 1  # reached, never passed
    assert len(threads) == 1 and threading.current_thread().name not in threads
    assert res.device_busy_s >= 0.004 * res.blocks
    assert 0 < res.device_s <= res.device_busy_s
    assert (res.walk_s + res.pack_s + res.device_s
            + res.compare_s) >= 0.9 * res.elapsed_s


@pytest.mark.parametrize("device", ["off", "auto_without_backend"])
def test_host_loop_starts_no_thread(seeded, monkeypatch, device):
    import threading

    from seaweedfs_tpu.storage import scrub
    v, sizes = seeded
    if device != "off":
        monkeypatch.setattr(scrub.devgate, "current", lambda: None)
    monkeypatch.setattr(scrub, "ThreadPoolExecutor", _boom)
    started = []
    monkeypatch.setattr(threading.Thread, "start",
                        lambda th: started.append(th.name))
    res = scrub_volume(v, device=device.split("_")[0])
    assert not started
    assert res.mode == "cpu" and set(res.corrupt) == set(_ROTTEN)
    assert res.scanned == len(sizes)


def test_concurrent_sweeps_do_not_mix_their_blocks(seeded):
    """Each sweep owns its device thread and its account: four at once,
    the interpreter switching threads every 10 us, report what one does."""
    import sys
    import threading
    v, _ = seeded
    want = scrub_volume(v, device="auto")
    before = threading.enumerate()
    got, errors = [], []

    def sweep():
        try:
            got.append(scrub_volume(v, device="auto"))
        except Exception as e:  # noqa: BLE001 — reported below
            errors.append(e)
    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        sweeps = [threading.Thread(target=sweep) for _ in range(4)]
        for th in sweeps:
            th.start()
        for th in sweeps:
            th.join(timeout=120)
    finally:
        sys.setswitchinterval(interval)
    assert not errors and not any(th.is_alive() for th in sweeps)
    assert [(r.corrupt, r.scanned, r.blocks, r.bytes_dispatched)
            for r in got] == [(want.corrupt, want.scanned, want.blocks,
                               want.bytes_dispatched)] * 4
    assert threading.enumerate() == before


@pytest.mark.parametrize("n", [1, 512, 513, 64 << 10, 1 << 20, 4 << 20])
def test_crc_program_has_no_loop_and_keeps_its_name(n):
    """Every block shape of the scrub lowers to a program with no `while`
    (no step of the CRC depends on another), and the program is still the
    unnamed lambda behind _crc_jit(): `jit__lambda` is the name the
    benchmark's roofline readers find it by in a device trace."""
    import jax
    import jax.numpy as jnp

    from seaweedfs_tpu.storage import scrub

    rows, pad_l = scrub._block_shape(n)
    assert rows * pad_l == scrub._DISPATCH_BYTES and pad_l >= n
    text = scrub._crc_jit().lower(
        jax.ShapeDtypeStruct((rows, pad_l), jnp.uint8)).as_text()
    assert text.startswith("module @jit__lambda"), text[:80]
    assert "while" not in text


def test_scrub_rpc_and_shell(tmp_path):
    """VolumeScrub RPC on a live server + the volume.scrub shell verb."""
    from conftest import wait_cluster_up

    from seaweedfs_tpu.client.master_client import MasterClient
    from seaweedfs_tpu.client.operation import submit
    from seaweedfs_tpu.master.master_server import MasterServer
    from seaweedfs_tpu.pb import volume_server_pb2 as vpb
    from seaweedfs_tpu.server.volume_server import VolumeServer
    from seaweedfs_tpu.shell.commands import CommandEnv
    from seaweedfs_tpu.storage.disk_location import DiskLocation
    from seaweedfs_tpu.storage.store import Store
    from seaweedfs_tpu.storage.types import parse_file_id
    from seaweedfs_tpu.utils.rpc import Stub, VOLUME_SERVICE

    ms = MasterServer(port=_fp(), volume_size_limit_mb=64,
                      pulse_seconds=0.5)
    ms.start()
    vp = _fp()
    store = Store("127.0.0.1", vp, "",
                  [DiskLocation(str(tmp_path / "v"), max_volume_count=8)],
                  coder_name="numpy")
    vs = VolumeServer(store, ms.address, port=vp, grpc_port=_fp(),
                      pulse_seconds=0.5)
    vs.start()
    wait_cluster_up(ms, [vs])
    mc = MasterClient(ms.address).start()
    try:
        fids = [submit(mc, os.urandom(2000)).fid for _ in range(10)]
        stub = Stub(f"127.0.0.1:{vs.grpc_port}", VOLUME_SERVICE)
        resp = stub.call("VolumeScrub", vpb.VolumeScrubRequest(device="off"),
                         vpb.VolumeScrubResponse, timeout=60)
        assert sum(r.scanned for r in resp.results) == 10
        assert all(not r.corrupt_needle_ids for r in resp.results)

        # corrupt one needle on disk, re-scrub: the RPC reports it
        vid, key, _ = parse_file_id(fids[0])
        v = store.find_volume(vid)
        nv = v.nm.get(key)
        with open(v.dat_path, "r+b") as f:
            f.seek(nv.offset + 20)
            f.write(b"\xde\xad")
        resp = stub.call("VolumeScrub",
                         vpb.VolumeScrubRequest(volume_id=vid, device="off"),
                         vpb.VolumeScrubResponse, timeout=60)
        assert list(resp.results[0].corrupt_needle_ids) == [key]

        # shell verb surfaces the corruption as a failure
        import io
        out = io.StringIO()
        env = CommandEnv(ms.address, mc=mc, out=out)
        with pytest.raises(RuntimeError, match="corrupt"):
            from seaweedfs_tpu.shell.volume_commands import cmd_volume_scrub
            cmd_volume_scrub(env, ["-device", "off"])
        text = out.getvalue()
        assert "CORRUPT" in text and "needles/s" in text
    finally:
        mc.stop()
        vs.stop()
        ms.stop()
