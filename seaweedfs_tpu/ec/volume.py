"""EcVolume: serve needle reads from an erasure-coded shard set.

Reference: weed/storage/erasure_coding/ec_volume.go:28-48 (EcVolume),
:267 (`LocateEcShardNeedle`), :321 (.ecx binary search), ec_shard.go (shard
file handles), ec_volume_info.go:73-118 (ShardBits). Cross-node shard reads
and degraded reconstruction plug in via `shard_reader` — the Store wires that
to remote RPCs / the device reconstruct path (reference store_ec.go:154-402).
"""

from __future__ import annotations

import os
import threading
import time
from dataclasses import dataclass
from typing import Callable, Optional

import numpy as np

from ..storage import types as t
from ..storage.needle import Needle
from ..storage.types import record_size_from_header
from ..utils.log import logger
from . import files
from .locate import EcGeometry, locate

log = logger("ec.volume")


class ShardBits:
    """Bitmask of shard ids on one (server, volume) — ec_volume_info.go:73."""

    def __init__(self, bits: int = 0):
        self.bits = bits

    def add(self, *ids: int) -> "ShardBits":
        for i in ids:
            self.bits |= 1 << i
        return self

    def remove(self, *ids: int) -> "ShardBits":
        for i in ids:
            self.bits &= ~(1 << i)
        return self

    def has(self, i: int) -> bool:
        return bool(self.bits >> i & 1)

    def ids(self) -> list[int]:
        return [i for i in range(32) if self.bits >> i & 1]

    def count(self) -> int:
        return bin(self.bits).count("1")

    def __repr__(self) -> str:
        return f"ShardBits({self.ids()})"


# shard_reader(shard_id, offset, length) -> bytes; raises KeyError if the
# shard is unreachable (triggers degraded reconstruction upstream).
ShardReader = Callable[[int, int, int], bytes]


@dataclass
class EcVolumeShard:
    shard_id: int
    path: str

    def __post_init__(self):
        self._f = open(self.path, "rb")
        self.size = os.path.getsize(self.path)
        self._mu = threading.Lock()  # read vs idle-close race

    def read_at(self, offset: int, length: int) -> bytes:
        with self._mu:
            if self._f.closed:  # lazily reopen after an idle close
                self._f = open(self.path, "rb")
            self._f.seek(offset)
            return self._f.read(length)

    def close(self):
        with self._mu:
            if not self._f.closed:
                self._f.close()


class RemoteEcVolumeShard:
    """An EC shard whose payload lives in a remote tier (lifecycle
    EC→remote offload). Same read_at/close surface as EcVolumeShard so
    EcVolume's stripe map, degraded reads and the heartbeat shard_bits
    are tier-blind: this holder still OWNS the shard, it just serves it
    through lazy ranged reads (RemoteDatFile's LRU block cache) instead
    of a local fd. `reads` feeds the promote-on-heat policy."""

    def __init__(self, shard_id: int, client, key: str, size: int):
        from ..storage.backend import RemoteDatFile
        self.shard_id = shard_id
        self.key = key
        self.size = size
        self._f = RemoteDatFile(client, key, size)
        self._mu = threading.Lock()  # _pos is shared; reads serialize
        self.reads = 0

    def read_at(self, offset: int, length: int) -> bytes:
        with self._mu:
            self.reads += 1
            self._f.seek(offset)
            return self._f.read(length)

    def close(self):
        # nothing to release but the block cache; an idle close must
        # not force a re-fetch storm, so keep it
        pass


class EcVolume:
    def __init__(self, base: str, vid: int, collection: str = "",
                 geo: EcGeometry | None = None):
        self.base = base
        self.id = vid
        self.collection = collection
        info = files.read_vif(base + ".vif")
        if geo is None:
            geo = EcGeometry.from_vif(info)
        self.geo = geo
        self.dat_size = info.get("dat_size", 0) or files.max_ecx_extent(base + ".ecx")
        # codec the shards were sealed with (the .vif is the source of
        # truth — rebuild/degraded reads must decode with the codec that
        # encoded; pre-codec .vifs are plain RS by construction)
        self.codec = info.get("codec", "rs")
        self.destroy_time = info.get("destroy_time", 0)  # fork TTL reap
        self.shards: dict[int, EcVolumeShard] = {}
        for i, p in sorted(self._scan_shards().items()):
            self.shards[i] = EcVolumeShard(i, p)
        # lifecycle EC→remote: shards whose payload was offloaded keep
        # serving through ranged remote reads (.vif `remote_shards` is
        # the source of truth: {"spec":, "keys": {sid: key}, "sizes":
        # {sid: size}}). A shard present BOTH locally and remotely —
        # a promote raced a crash — serves local (fresher is identical,
        # local is cheaper); the stale remote copy is cleaned up by the
        # next offload/promote pass.
        self.remote_spec: dict | None = info.get("remote_shards") or None
        if self.remote_spec:
            from ..storage.backend import open_remote
            client = open_remote(self.remote_spec["spec"])
            for sid_s, key in self.remote_spec.get("keys", {}).items():
                sid = int(sid_s)
                if sid not in self.shards:
                    self.shards[sid] = RemoteEcVolumeShard(
                        sid, client, key,
                        int(self.remote_spec.get("sizes", {}).get(
                            sid_s, 0)) or self.shard_size)
        self.last_read_at = time.monotonic()
        self.reads = 0  # needle reads since mount (lifecycle heat)
        # last-read instant persisted across restarts (stamped into the
        # .vif on idle-close): without it a remount would reset the
        # read-age clock to zero and postpone every EC→remote offload
        # by a full remote_after_s after a restart
        self._last_read_wall = float(info.get("last_read_wall", 0.0))
        self._idle_stamped = False

    def _scan_shards(self) -> dict[int, str]:
        return {i: self.base + files.shard_ext(i)
                for i in range(self.geo.n)
                if os.path.exists(self.base + files.shard_ext(i))}

    @property
    def shard_size(self) -> int:
        """Per-shard file size implied by the stripe geometry (repair
        byte-costing; local shard files agree by construction)."""
        return self.geo.shard_file_size(self.dat_size)

    @property
    def ecx_path(self) -> str:
        return self.base + ".ecx"

    @property
    def ecj_path(self) -> str:
        return self.base + ".ecj"

    def shard_bits(self) -> ShardBits:
        return ShardBits().add(*self.shards.keys())

    def remote_shard_ids(self) -> list[int]:
        """Shard ids this holder serves from the remote tier."""
        return sorted(i for i, s in self.shards.items()
                      if isinstance(s, RemoteEcVolumeShard))

    def remote_reads(self) -> int:
        """Ranged remote reads served since mount — the promote-on-heat
        signal (a cold volume that keeps getting read belongs local)."""
        return sum(s.reads for s in self.shards.values()
                   if isinstance(s, RemoteEcVolumeShard))

    def read_age_s(self) -> float:
        """Seconds since the last KNOWN needle read. In-process reads
        drive the monotonic clock; with none since mount, the
        `last_read_wall` stamp the idle-close persisted into the .vif
        extends the quiet period across restarts (no stamp = the mount
        instant is the conservative floor)."""
        mono_age = time.monotonic() - self.last_read_at
        if self.reads == 0 and self._last_read_wall:
            wall_age = time.time() - self._last_read_wall  # swtpu-lint: disable=wallclock-duration (stamp is persisted wall-clock)
            return max(mono_age, wall_age)
        return mono_age

    def close_idle(self, idle_s: float) -> bool:
        """Fork behavior (ec_volume.go:303-319,348-353 IsExpire/idle close):
        release file handles of EC volumes nobody read recently; reads
        lazily reopen. Returns True if handles were closed. Crossing
        into idle also persists the last-read instant into the .vif
        (once per quiet period) so read_age_s survives a restart."""
        if time.monotonic() - self.last_read_at < idle_s:
            # reads resumed: a persisted stamp is now STALE — left in
            # place it would survive a restart and make this hot volume
            # read as cold-for-days (offloading warm data is the
            # expensive mistake). Cleared here, off the read path, at
            # most once per busy period.
            if self._idle_stamped or self._last_read_wall:
                try:
                    files.update_vif(self.base + ".vif",
                                     remove=("last_read_wall",))
                except OSError as e:
                    log.debug("stale read stamp clear for %d: %s",
                              self.id, e)
                self._last_read_wall = 0.0
            self._idle_stamped = False
            return False
        if not self._idle_stamped:
            try:
                last_wall = time.time() - (  # swtpu-lint: disable=wallclock-duration (persisting a wall-clock stamp)
                    time.monotonic() - self.last_read_at)
                files.update_vif(self.base + ".vif",
                                 {"last_read_wall": last_wall})
                self._last_read_wall = last_wall
            except OSError as e:
                log.debug("idle last-read stamp for %d: %s", self.id, e)
            self._idle_stamped = True
        closed = False
        for shard in self.shards.values():
            if isinstance(shard, RemoteEcVolumeShard):
                continue  # no fd to release; block cache stays warm
            if not shard._f.closed:
                shard.close()
                closed = True
        return closed

    # -- lookup ------------------------------------------------------------
    def find_needle(self, needle_id: int) -> tuple[int, int] | None:
        """(offset, size) in logical volume space, or None."""
        return files.search_ecx(self.ecx_path, needle_id)

    # -- read --------------------------------------------------------------
    def read_needle(self, needle_id: int, cookie: int | None = None,
                    shard_reader: Optional[ShardReader] = None,
                    verify_crc: bool = True) -> Needle:
        """Read + parse one needle, fetching intervals shard by shard.

        Reference store_ec.go:154 ReadEcShardNeedle -> readEcShardIntervals.
        """
        self.last_read_at = time.monotonic()
        self.reads += 1
        loc = self.find_needle(needle_id)
        if loc is None:
            raise KeyError(f"needle {needle_id:x} not in ec volume {self.id}")
        offset, size = loc
        rec_len = record_size_from_header(size)
        buf = self.read_logical(offset, rec_len, shard_reader)
        n = Needle.from_bytes(buf, verify_crc=verify_crc)
        if n.id != needle_id:
            raise ValueError(f"needle id mismatch in ec volume {self.id}")
        if cookie is not None and n.cookie != cookie:
            raise PermissionError(f"cookie mismatch for needle {needle_id:x}")
        return n

    def read_logical(self, offset: int, length: int,
                     shard_reader: Optional[ShardReader] = None) -> bytes:
        """Read a logical [offset, offset+length) span via the stripe map."""
        out = bytearray(length)
        pos = 0
        for iv in locate(self.geo, self.dat_size, offset, length):
            shard_id, shard_off = iv.shard_and_offset(self.geo)
            chunk = self._read_shard(shard_id, shard_off, iv.size, shard_reader)
            out[pos:pos + iv.size] = chunk
            pos += iv.size
        return bytes(out)

    def _read_shard(self, shard_id: int, offset: int, length: int,
                    shard_reader: Optional[ShardReader]) -> bytes:
        local = self.shards.get(shard_id)
        if local is not None:
            return local.read_at(offset, length)
        if shard_reader is None:
            raise KeyError(f"shard {shard_id} of volume {self.id} not local")
        return shard_reader(shard_id, offset, length)

    # -- delete (reference ec_volume_delete.go) ----------------------------
    def delete_needle(self, needle_id: int) -> bool:
        if files.search_ecx(self.ecx_path, needle_id) is None:
            return False
        files.append_ecj(self.ecj_path, needle_id)
        files.mark_deleted_in_ecx(self.ecx_path, needle_id)
        return True

    def close(self):
        for s in self.shards.values():
            s.close()

    def destroy(self, to_trash: str | None = None):
        """Remove (or soft-move, fork behavior ec_volume.go:184-198) all files.

        Offloaded shard payloads: a soft-delete to trash keeps the
        remote objects (the .vif rides into the trash dir, so a restore
        before the grace expires remounts the remote tier intact); a
        hard destroy deletes them best-effort, like Volume.destroy."""
        if to_trash is None and self.remote_spec:
            try:
                from ..storage.backend import open_remote
                client = open_remote(self.remote_spec["spec"])
                for key in self.remote_spec.get("keys", {}).values():
                    client.delete_object(key)
            except Exception as e:  # noqa: BLE001 — orphan object, not data
                log.warning("delete remote shards of ec volume %d: %s",
                            self.id, e)
        self.close()
        exts = [files.shard_ext(i) for i in range(self.geo.n)] + [".ecx", ".ecj", ".vif"]
        for ext in exts:
            p = self.base + ext
            if os.path.exists(p):
                if to_trash:
                    os.makedirs(to_trash, exist_ok=True)
                    # destroy path: a crash resurrecting the un-trashed
                    # shard is harmless (worst case the destroy re-runs)
                    os.replace(p, os.path.join(to_trash, os.path.basename(p)))  # swtpu-lint: disable=rename-no-dir-fsync
                else:
                    os.remove(p)
