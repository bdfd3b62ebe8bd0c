"""Shards that leave the encoder as it writes them.

A seal places most of a stripe's shards on other servers. Instead of
writing those shards here, fsyncing them and letting each holder copy
them back off this disk (`VolumeEcShardsCopy` after the generate), the
writer thread that would have pwritten a run hands it to the holder's
`ShardSink`: one client stream (`VolumeEcShardsReceive`) per volume and
holder, fed from a bounded queue. The holder pwrites each message at its
offset, and at the seal fsyncs its shards and sidecars before it writes
its `.vif` and replies (`receive`). The pipeline itself only sees
`Holder.open` and the sink's `write / seal / cancel / result`.
"""

from __future__ import annotations

import json
import os
import queue
import threading
import time
from dataclasses import dataclass, field

import numpy as np

from ..utils import failpoints
from . import files

# bytes of shard data per message: runs are split at row boundaries
MSG_BYTES = 4 << 20
# messages queued per sink before a writer blocks (backpressure into the
# writer pool, and from there into the pipeline's `write_block`)
SINK_DEPTH = 8
SIDECARS = (".ecx", ".ecj")


def _varint(n: int) -> bytes:
    out = bytearray()
    while True:
        b, n = n & 0x7F, n >> 7
        if not n:
            out.append(b)
            return bytes(out)
        out.append(b | 0x80)


def data_message(shard_id: int, offset: int, views: list) -> bytes:
    """A serialized `VolumeEcShardsReceiveRequest` (shard_id, offset,
    data) whose data is the concatenation of `views`: the fields are
    written by hand so that the payload is copied once, by the join,
    and not again by a message object and its serialization (that
    second copy made the seal 7-12% slower on a TPU v5e host, PERF.md
    §6)."""
    n = sum(len(v) for v in views)
    head = (b"\x28" + _varint(shard_id) + b"\x30" + _varint(offset)
            + b"\x3a" + _varint(n))
    return b"".join([head, *views])


def _run_messages(shard_id: int, offset: int, arr: np.ndarray):
    """One writer-pool run (1-D, or [k, chunk] rows at consecutive
    offsets) as messages of at most MSG_BYTES, split at row ends."""
    if arr.ndim == 1:
        for o in range(0, arr.size, MSG_BYTES):
            yield data_message(shard_id, offset + o,
                               [memoryview(arr[o:o + MSG_BYTES])])
        return
    step = arr.shape[-1]
    per = max(1, MSG_BYTES // step)
    for r in range(0, arr.shape[0], per):
        rows = [memoryview(np.ascontiguousarray(row))
                for row in arr[r:r + per]]
        yield data_message(shard_id, offset + r * step, rows)


@dataclass
class Holder:
    """Another server that is to keep `shard_ids` of volume `vid`."""
    address: str
    vid: int
    collection: str
    shard_ids: list
    # what `open` streamed to it: the pipeline may keep some of the
    # shards placed there to write here (an overlay codec's parities)
    pushed: list = field(default_factory=list)

    def open(self, shard_ids: list, shard_size: int) -> "ShardSink":
        self.pushed = sorted(shard_ids)
        return ShardSink(self.address, self.vid, self.collection,
                         self.pushed, shard_size)


class ShardSink:
    """The encoding server's end of one volume's stream to one holder.

    `write` is called by the writer pool's threads (each shard always
    from the same one), `seal` and `cancel` by the pipeline's thread. A
    write blocks while SINK_DEPTH messages wait, and raises once the
    call has ended early (the holder failed or the call was cancelled):
    the pool is then poisoned and the encode fails as on ENOSPC."""

    def __init__(self, address: str, vid: int, collection: str,
                 shard_ids: list, shard_size: int):
        from ..pb import volume_server_pb2 as vpb
        from ..utils.rpc import VOLUME_SERVICE, Stub
        self.address = address
        self.bytes = 0      # shard bytes queued to the holder
        self.seconds = 0.0  # writers' seconds inside write()
        self._lock = threading.Lock()
        self._q: queue.Queue = queue.Queue(maxsize=SINK_DEPTH)
        self._closed = False
        self._q.put(vpb.VolumeEcShardsReceiveRequest(
            volume_id=vid, collection=collection, shard_ids=shard_ids,
            shard_size=shard_size).SerializeToString())
        self._fut = Stub(address, VOLUME_SERVICE).stream_unary_future(
            "VolumeEcShardsReceive", self._requests(),
            vpb.VolumeEcShardsReceiveResponse)

    def _requests(self):
        while True:
            item = self._q.get()
            if item is None:
                return
            yield item

    def _failed(self) -> BaseException:
        if self._fut.cancelled():
            return RuntimeError(f"push to {self.address} cancelled")
        exc = self._fut.exception()
        return exc if exc is not None else RuntimeError(
            f"push to {self.address} ended before its seal")

    def _put(self, item) -> None:
        while True:
            if self._fut.done():
                raise self._failed()
            try:
                self._q.put(item, timeout=0.2)
                return
            except queue.Full:
                continue

    def write(self, shard_id: int, offset: int, arr: np.ndarray) -> None:
        t0 = time.perf_counter()
        for msg in _run_messages(shard_id, offset, arr):
            self._put(msg)
        with self._lock:
            self.bytes += arr.nbytes
            self.seconds += time.perf_counter() - t0

    def seal(self, base: str, vif: dict) -> None:
        """After every shard run was written: the sidecars this server
        sealed (`base` + .ecx, .ecj if there is one) and the .vif
        document, then the end of the stream. Does not wait for the
        holder's reply (`result`)."""
        from ..pb import volume_server_pb2 as vpb
        for ext in SIDECARS:
            if not os.path.exists(base + ext):
                continue
            with open(base + ext, "rb") as f:
                off, chunk = 0, f.read(MSG_BYTES)
                while True:  # the first even if empty: the file exists
                    self._put(vpb.VolumeEcShardsReceiveRequest(
                        ext=ext, offset=off, data=chunk).SerializeToString())
                    off += len(chunk)
                    chunk = f.read(MSG_BYTES)
                    if not chunk:
                        break
        self._put(vpb.VolumeEcShardsReceiveRequest(
            vif=json.dumps(vif).encode()).SerializeToString())
        self._close()

    def _close(self) -> None:
        if not self._closed:
            self._closed = True
            self._put(None)

    def cancel(self) -> None:
        """Abort: the holder removes what this stream wrote."""
        self._fut.cancel()
        self._closed = True
        while True:  # let grpc's consumer thread end, whatever is queued
            try:
                self._q.get_nowait()
            except queue.Empty:
                break
        try:
            self._q.put_nowait(None)
        except queue.Full:
            pass

    def result(self):
        """The holder's reply; raises if it failed or was cancelled."""
        if self._fut.cancelled():
            raise self._failed()
        return self._fut.result()

    def ok(self) -> bool:
        """True once the holder has replied that the volume is sealed."""
        return (self._fut.done() and not self._fut.cancelled()
                and self._fut.exception() is None)


def receive(requests, base_for, hold) -> "tuple[list[int], int]":
    """A holder's end of a push: `requests` the stream's messages,
    `base_for(collection, vid)` where the volume's files go, `hold(vid)`
    the lock a delete of the volume's shards here takes too, held until
    this ends (a delete never races the seal). Returns (shard ids, bytes
    received) once the shards and sidecars are fsynced and the .vif
    written. On any other end (the sender cancelled, the stream ended
    without its seal, a write failed, the failpoint `ec.shard.receive`)
    every file this stream created is removed, no .vif is written, and
    the error propagates."""
    it = iter(requests)
    head = next(it)
    with hold(head.volume_id):
        return _receive(head, it, base_for(head.collection, head.volume_id))


def _receive(head, it, base: str) -> "tuple[list[int], int]":
    sids = list(head.shard_ids)
    fds: dict = {}       # shard id or sidecar ext -> fd
    created: list = []
    got = 0
    try:
        for sid in sids:
            path = base + files.shard_ext(sid)
            fds[sid] = os.open(path, os.O_WRONLY | os.O_CREAT | os.O_TRUNC,
                               0o644)
            created.append(path)
            os.ftruncate(fds[sid], head.shard_size)
        for req in it:
            failpoints.check("ec.shard.receive")
            if req.vif:
                for key in (*sids, *(e for e in SIDECARS if e in fds)):
                    os.fsync(fds[key])
                _close_all(fds)
                # the .vif last: a sealed-looking volume only over
                # durable shards and index (as on the encoding server)
                files.write_vif(base + ".vif", **json.loads(req.vif))
                return sids, got
            if req.ext:
                if req.ext not in SIDECARS:
                    raise ValueError(f"push: no sidecar {req.ext!r}")
                if req.ext not in fds:
                    fds[req.ext] = os.open(
                        base + req.ext,
                        os.O_WRONLY | os.O_CREAT | os.O_TRUNC, 0o644)
                    created.append(base + req.ext)
                fd = fds[req.ext]
            else:
                fd = fds.get(req.shard_id)
                if fd is None:
                    raise ValueError(f"push: shard {req.shard_id} was not "
                                     f"announced ({sids})")
                got += len(req.data)
            _pwrite_full(fd, req.data, req.offset)
        raise RuntimeError(f"push of volume {head.volume_id} ended before "
                           "its seal")
    except BaseException:
        _close_all(fds)
        for path in created:
            try:
                os.unlink(path)
            except FileNotFoundError:
                pass
        raise


def _pwrite_full(fd: int, data: bytes, off: int) -> None:
    mv = memoryview(data)
    while mv:
        n = os.pwrite(fd, mv, off)
        mv, off = mv[n:], off + n


def _close_all(fds: dict) -> None:
    for key in list(fds):
        os.close(fds.pop(key))
