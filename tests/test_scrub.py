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
