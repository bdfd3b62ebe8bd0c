"""The plain-RS rebuild loads a batch's survivors side by side, one task
per survivor shard (ec/encoder.py:_rebuild_positional): the bytes rebuilt,
what a task inherits from the rebuild's thread, what a failing survivor
leaves behind, and the byte count from d threads at once."""

import os
import threading
import time

import numpy as np
import pytest

from seaweedfs_tpu import qos, tracing
from seaweedfs_tpu.ec import files, repair
from seaweedfs_tpu.ec.encoder import encode_volume, rebuild_shards
from seaweedfs_tpu.ec.locate import EcGeometry
from seaweedfs_tpu.ops.coder import NumpyCoder

# 768 divides no shard size of 512-byte blocks evenly: the last batch is
# short and its last row padded
CHUNK, BATCH = 768, 4
WORKER = "ec-rebuild-read"


def sealed(tmp_path, d, p, remote_of=lambda sid: sid % 2 == 0):
    """A volume sealed at RS(d, p); the survivors `remote_of` names are
    taken off the disk and held in memory, for a fake holder to serve."""
    geo = EcGeometry(d=d, p=p, large_block=4096, small_block=512)
    base = str(tmp_path / "1")
    rng = np.random.default_rng(d * 100 + p)
    with open(base + ".dat", "wb") as f:
        f.write(rng.integers(0, 256, 3 * d * 4096 + 4 * 512 * d + 77,
                             dtype=np.uint8).tobytes())
    coder = NumpyCoder(d, p)
    encode_volume(base + ".dat", base, geo, coder, chunk=512, batch=4)
    shards = {}
    for sid in range(geo.n):
        with open(base + files.shard_ext(sid), "rb") as f:
            shards[sid] = f.read()
    size = len(shards[0])
    assert size % (CHUNK * BATCH) and size % CHUNK and size > CHUNK * BATCH
    held = {sid: shards[sid] for sid in shards if remote_of(sid)}
    for sid in held:
        os.remove(base + files.shard_ext(sid))
    return geo, base, coder, shards, held


def lose(base, held, lost):
    for sid in lost:
        held.pop(sid, None)
        if os.path.exists(base + files.shard_ext(sid)):
            os.remove(base + files.shard_ext(sid))


def workers():
    return [t for t in threading.enumerate() if t.name.startswith(WORKER)]


CASES = sorted({(d, p, lost) for d, p in ((10, 4), (14, 2))
                for lost in (1, 2, p)})


@pytest.mark.parametrize("d,p,lost", CASES)
def test_rebuilt_shards_are_the_lost_ones(tmp_path, d, p, lost):
    geo, base, coder, shards, held = sealed(tmp_path, d, p)
    gone = [0, d, 1, d + 1][:lost]  # data and parity, local and remote
    lose(base, held, gone)
    order = []

    def holder(sid, off, ln):
        # the higher the id the sooner it answers: loads end out of order
        time.sleep(0.002 * (geo.n - sid))
        order.append(sid)
        return held[sid][off:off + ln]

    stats: dict = {}
    rebuilt = rebuild_shards(base, geo, coder, chunk=CHUNK, batch=BATCH,
                             shard_reader=holder, remote_shards=sorted(held),
                             stats=stats)
    assert rebuilt == sorted(gone)
    for sid in gone:
        with open(base + files.shard_ext(sid), "rb") as f:
            assert f.read() == shards[sid], sid
    size = len(shards[0])
    batches = -(-size // (CHUNK * BATCH))
    assert stats["path"] == "full" and stats["batches"] == batches
    assert stats["bytes_read"] == d * size
    assert stats["bytes_written"] == lost * size
    # the first d survivors by id, remote ones among them, each once a
    # batch, and not in the order they were asked
    use = sorted(set(range(geo.n)) - set(gone))[:d]
    asked = sorted(set(use) & set(held))
    assert len(asked) >= 2 and sorted(order) == sorted(asked * batches)
    assert order[:len(asked)] != asked
    assert not workers()


def test_a_task_runs_under_the_callers_class_and_span(tmp_path):
    geo, base, coder, shards, held = sealed(tmp_path, 10, 4)
    lose(base, held, [1])
    seen = []

    def holder(sid, off, ln):
        with tracing.start_span("ec.shard.fetch", component="ec") as sp:
            seen.append((qos.current_class(), sp.context.trace_id, sp.parent_id,
                         threading.current_thread().name))
        return held[sid][off:off + ln]

    tracing.BUFFER.clear()
    assert qos.current_class() != qos.CLASS_MAINTENANCE
    with tracing.start_span("test.root") as root, \
            qos.tagged(qos.CLASS_MAINTENANCE):
        rebuild_shards(base, geo, coder, chunk=CHUNK, batch=BATCH,
                       shard_reader=holder, remote_shards=sorted(held))
    (rebuild,) = [s for s in tracing.BUFFER.snapshot(limit=5000)
                  if s["name"] == "ec.rebuild"]
    assert rebuild["parent_id"] == root.context.span_id
    assert seen and {s[0] for s in seen} == {qos.CLASS_MAINTENANCE}
    assert {s[1] for s in seen} == {root.context.trace_id}
    assert {s[2] for s in seen} == {rebuild["span_id"]}
    # and they did run on the rebuild's own loaders, not on its thread
    assert all(s[3].startswith(WORKER) for s in seen)
    assert not workers()


def test_a_failing_survivor_fails_the_rebuild_and_leaves_nothing_running(
        tmp_path):
    geo, base, coder, shards, held = sealed(tmp_path, 10, 4)
    lose(base, held, [0])
    started, ended = [], []

    def holder(sid, off, ln):
        if sid == 4 and off:  # the second batch
            raise OSError("survivor 4 unreachable")
        started.append(sid)
        time.sleep(0.03)
        ended.append(sid)
        return held[sid][off:off + ln]

    stats: dict = {}
    with pytest.raises(OSError, match="survivor 4 unreachable"):
        rebuild_shards(base, geo, coder, chunk=CHUNK, batch=BATCH,
                       shard_reader=holder, remote_shards=sorted(held),
                       stats=stats)
    # every load that began had ended before the error left: the fds
    # the local ones read are closed by now
    assert len(started) > 4 and sorted(started) == sorted(ended)
    assert not workers()
    assert stats == {}  # nothing published as a success


class _SlowSum(int):
    """An int whose `+` lets every other thread in: a read-modify-write
    around it loses updates unless it is locked."""

    def __add__(self, other):
        time.sleep(0.0005)
        return _SlowSum(int(self) + other)


def test_bytes_read_is_exact_from_d_threads(tmp_path, monkeypatch):
    class Racy(repair.RepairCounter):
        def __init__(self, codec):
            super().__init__(codec)
            self.bytes_read = _SlowSum(0)

    monkeypatch.setattr(repair, "RepairCounter", Racy)
    # every survivor remote: 14 loaders count into one counter at once
    geo, base, coder, shards, held = sealed(tmp_path, 14, 2,
                                            remote_of=lambda sid: True)
    lose(base, held, [3, 15])
    stats: dict = {}
    rebuild_shards(base, geo, coder, chunk=CHUNK, batch=BATCH,
                   shard_reader=lambda sid, off, ln: held[sid][off:off + ln],
                   remote_shards=sorted(held), stats=stats)
    assert stats["bytes_read"] == 14 * len(shards[0])
    for sid in (3, 15):
        with open(base + files.shard_ext(sid), "rb") as f:
            assert f.read() == shards[sid]
