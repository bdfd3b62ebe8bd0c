"""The scrub's walker (storage/scrub_walk.py) against the benchmark's plain
reference (benchmark/reference_scrub.py), on seeded volumes of
every kind a sweep meets: the device path through its walker process
(`device="auto"`, the CRC program on the CPU backend), the host loop
(`device="off"`) and the reference agree on the needles scanned, the
corrupt ids and a torn walk. Then the walker process itself: it never
imports jax, one killed mid-volume is a volume error with no process
left behind, and a vacuum that swaps the `.dat` after the sweep's
snapshot does not reach the walk."""

import struct
import sys

import numpy as np
import psutil
import pytest

from benchmark.reference_scrub import scrub_reference
from seaweedfs_tpu.storage import scrub
from seaweedfs_tpu.storage.needle import Needle
from seaweedfs_tpu.storage.scrub import scrub_volume
from seaweedfs_tpu.storage.volume import Volume

# blocks of 32 KiB, so that a volume of a few MB fills its buckets many
# times (the program is the same whatever the shape)
_SMALL_BLOCK = 32 << 10


def _flip(v: Volume, nid: int, at: int) -> None:
    """One byte of needle `nid`'s data, `at` bytes in, inverted on disk."""
    pos = v.nm.get(nid).offset + 20 + at
    with open(v.dat_path, "r+b") as f:
        f.seek(pos)
        b = f.read(1)
        f.seek(pos)
        f.write(bytes([b[0] ^ 0xFF]))


def _write(v: Volume, rng, sizes) -> "list[int]":
    ids = []
    for size in sizes:
        nid = len(ids) + 1
        v.write_needle(Needle(id=nid, cookie=1, data=rng.integers(
            0, 256, int(size), dtype=np.uint8).tobytes()))
        ids.append(nid)
    return ids


def _rot(v, rng, ids, sizes, n):
    for i in rng.choice(len(ids), n, replace=False):
        if sizes[i]:
            _flip(v, ids[i], int(rng.integers(0, sizes[i])))


def one_kb(v, rng):
    """1 KB needles, as the small-file server holds them; 3 rotten."""
    sizes = [1024] * 300
    _rot(v, rng, _write(v, rng, sizes), sizes, 3)


def size_mix(v, rng):
    """The log-uniform 1 KiB-4 MiB mix of the cold tier, cut to 256 KiB."""
    sizes = np.exp(rng.uniform(np.log(1024), np.log(256 << 10), 40))
    sizes = [int(s) for s in sizes]
    _rot(v, rng, _write(v, rng, sizes), sizes, 4)


def garbage(v, rng):
    """Overwrites and deletes: rot in the garbage they leave must not
    alarm, rot in what is live must."""
    sizes = [int(s) for s in rng.integers(1, 5000, 120)]
    ids = _write(v, rng, sizes)
    old = [int(i) for i in rng.choice(ids, 20, replace=False)]
    offsets = {nid: v.nm.get(nid).offset for nid in old}
    for nid in old[:10]:  # overwritten: the first record is garbage
        v.write_needle(Needle(id=nid, cookie=1, data=rng.integers(
            0, 256, 700, dtype=np.uint8).tobytes()))
    for nid in old[10:]:  # deleted: the record is garbage, plus a tombstone
        v.delete_needle(nid, cookie=1)
    with open(v.dat_path, "r+b") as f:
        for nid in old:
            f.seek(offsets[nid] + 20)
            f.write(b"\xde\xad")
    _flip(v, old[0], 5)  # the overwrite's live record
    live = [nid for nid in ids if nid not in old]
    _flip(v, live[0], 0)


def empty_needles(v, rng):
    """Zero-length needles between ordinary ones."""
    sizes = [0 if i % 3 == 0 else int(rng.integers(1, 3000))
             for i in range(60)]
    _rot(v, rng, _write(v, rng, sizes), sizes, 2)


def rotted_length(v, rng):
    """A live needle whose length field overruns its body: corrupt at
    once, never packed."""
    sizes = [int(s) for s in rng.integers(10, 3000, 50)]
    ids = _write(v, rng, sizes)
    for nid in (ids[7], ids[30]):
        nv = v.nm.get(nid)
        with open(v.dat_path, "r+b") as f:
            f.seek(nv.offset + 16)
            f.write(struct.pack("<I", nv.size))


def torn_tail(v, rng):
    """Header rot three quarters in: the record chain runs past the end,
    and what follows the rot goes unscanned."""
    sizes = [int(s) for s in rng.integers(1, 4000, 80)]
    ids = _write(v, rng, sizes)
    _rot(v, rng, ids[:50], sizes[:50], 2)
    with open(v.dat_path, "r+b") as f:
        f.seek(v.nm.get(ids[60]).offset + 12)
        f.write(struct.pack("<I", 0x0FFFFFFF))


KINDS = [one_kb, size_mix, garbage, empty_needles, rotted_length, torn_tail]


@pytest.fixture
def small_blocks(monkeypatch):
    from seaweedfs_tpu.ops import device
    device.info()  # a device-coder process: "auto" means the kernel
    monkeypatch.setattr(scrub, "_DISPATCH_BYTES", _SMALL_BLOCK)


def _transport(monkeypatch, transport):
    """`shared`: the slots are a memory file both processes map (the
    default); `streamed`: none could be made (a file size limit under the
    slots), and each block follows its message down the pipe."""
    if transport == "streamed":
        monkeypatch.setattr(scrub, "_shared_slots",
                            lambda size: (-1, bytearray(size)))


TRANSPORTS = ["shared", "streamed"]


def _volume(tmp_path, kind, seed):
    v = Volume(str(tmp_path), "", 1)
    kind(v, np.random.default_rng(seed))
    v.nm.flush()
    v._dat.flush()
    return v


def _verdict(res):
    return res.scanned, sorted(res.corrupt), "torn" in res.error


@pytest.mark.parametrize("transport", TRANSPORTS)
@pytest.mark.parametrize("seed", [4301, 4302])
@pytest.mark.parametrize("kind", KINDS, ids=[k.__name__ for k in KINDS])
def test_walker_host_loop_and_reference_agree(tmp_path, small_blocks,
                                              monkeypatch, kind, seed,
                                              transport):
    _transport(monkeypatch, transport)
    v = _volume(tmp_path, kind, seed)
    try:
        want = scrub_reference(v.dat_path, v.dat_path[:-4] + ".idx")
        piped = scrub_volume(v, device="auto")
        host = scrub_volume(v, device="off")
    finally:
        v.close()
    assert _verdict(piped) == _verdict(host) == want
    assert piped.mode == "xla-cpu" and host.mode == "cpu"
    assert piped.bytes_checked == host.bytes_checked
    assert want[0] > 0 and (want[1] or kind is torn_tail)
    assert (piped.error == "") == (not want[2]) == (host.error == "")


def _children() -> "list[int]":
    return [p.pid for p in psutil.Process().children(recursive=True)]


def test_the_walker_process_never_imports_jax(tmp_path, small_blocks,
                                              monkeypatch):
    """The walker, run as the sweep runs it, lists the modules it ended
    with: the standard library and the package's storage types."""
    out = tmp_path / "modules.txt"
    monkeypatch.setattr(scrub, "_WALKER_CMD", (
        "-S", "-c",
        "import atexit, runpy, sys\n"
        f"atexit.register(lambda: open({str(out)!r}, 'w').write("
        "'\\n'.join(sys.modules)))\n"
        "runpy.run_module('seaweedfs_tpu.storage.scrub_walk', "
        "run_name='__main__')"))
    v = _volume(tmp_path, one_kb, 4303)
    try:
        res = scrub_volume(v, device="auto")
    finally:
        v.close()
    assert res.error == "" and res.blocks > 0 and len(res.corrupt) == 3
    modules = set(out.read_text().split())
    assert "seaweedfs_tpu.storage.types" in modules
    assert not {m for m in modules if m.split(".")[0] in (
        "jax", "jaxlib", "numpy")}
    assert "jax" in sys.modules  # this process, which owns the device


@pytest.mark.parametrize("transport", TRANSPORTS)
def test_a_walker_killed_mid_volume_is_a_volume_error(tmp_path, small_blocks,
                                                      monkeypatch, transport):
    """Never a clean result with fewer needles; no process left behind."""
    _transport(monkeypatch, transport)
    real = scrub._Walker.block
    killed = []

    def block(self, msg):
        if not killed:
            self.proc.kill()
            killed.append(self.proc.wait())
        return real(self, msg)
    monkeypatch.setattr(scrub._Walker, "block", block)
    v = _volume(tmp_path, one_kb, 4304)
    before = _children()
    try:
        res = scrub_volume(v, device="auto")
    finally:
        v.close()
    assert killed == [-9]
    assert "walker exited -9 before the volume's end" in res.error
    assert res.scanned < 300 and res.blocks >= 1
    assert _children() == before


def test_a_vacuum_swap_after_the_snapshot_does_not_reach_the_walk(
        tmp_path, small_blocks, monkeypatch):
    """The sweep opens the `.dat` under the volume's lock beside its
    snapshot and the walker inherits that file: a compaction committed
    right after (a new `.dat` at new offsets, while the walker is still
    starting) leaves the walk what the snapshot saw."""
    from seaweedfs_tpu.storage import vacuum
    v = _volume(tmp_path, garbage, 4307)
    want = scrub_reference(v.dat_path, v.dat_path[:-4] + ".idx")
    vacuum.compact(v)
    real = scrub._Walker.__init__
    swapped = []

    def init(self, vol):
        real(self, vol)
        swapped.append(vacuum.commit_compact(vol))
    monkeypatch.setattr(scrub._Walker, "__init__", init)
    try:
        res = scrub_volume(v, device="auto")
    finally:
        for vol in swapped:
            vol.close()
    assert swapped and res.error == ""
    assert _verdict(res) == want
