"""Store: the per-server aggregate over disk locations.

Reference: weed/storage/store.go:83 (NewStore), :259 (CollectHeartbeat),
:436/:460 (write/read dispatch), store_ec.go (EC mount/read), :389
(deleteExpiredEcVolumes, fork). Serves both the volume server daemon and the
single-binary dev mode.
"""

from __future__ import annotations

import os
import time

from ..ec import files as ec_files
from ..ec.encoder import decode_volume, encode_volume, rebuild_shards
from ..ec.locate import EcGeometry
from ..ec.volume import EcVolume
from ..ops import device
from ..ops.coder import ErasureCoder, codec_coder, get_coder
from ..utils import failpoints, fsutil
from ..utils.log import logger
from . import types as t
from .disk_location import DiskLocation
from .needle import Needle
from .volume import Volume, VolumeClosedError

log = logger("store")


class Store:
    def __init__(self, ip: str, port: int, public_url: str,
                 locations: list[DiskLocation],
                 ec_geometry: EcGeometry | None = None,
                 coder_name: str = "auto", ec_codec: str = "rs"):
        self.ip = ip
        self.port = port
        self.public_url = public_url or f"{ip}:{port}"
        self.locations = locations
        self.ec_geometry = ec_geometry or EcGeometry()
        # -coder resolves HERE, once (ops/device.py): `auto` becomes the
        # device coder on a TPU and native/numpy without one; a device
        # coder without a TPU raises instead of starting a server that
        # would encode on the host and say nothing
        self.backend = device.resolve_coder(coder_name)
        log.info("coder %s -> %s %s", coder_name, self.backend,
                 device.status())
        # erasure CODEC for new encodes ("rs" | "piggyback") — orthogonal
        # to coder_name, which picks the compute backend. Reads/rebuilds
        # always follow the codec sealed in each volume's .vif.
        self.ec_codec = ec_codec or "rs"
        # lifecycle heat: per-volume read counters + last-read clock
        # (monotonic — the planner consumes AGES, never absolute times).
        # vid -> [reads_total, last_read_monotonic]; plain dict ops are
        # GIL-atomic and a lost increment under contention only shades
        # a heat score, so no lock on the read hot path.
        self._access: dict[int, list] = {}
        for loc in locations:
            loc.load_existing()

    # -- lifecycle access stats ---------------------------------------------
    def note_read(self, vid: int, n: int = 1) -> None:
        """Record needle reads against a volume (called by the storage
        read paths below AND by the volume server's cache-hit path,
        which never reaches the store). Only vids RESOLVED to a local
        volume are noted, and removal paths prune their entry, so the
        dict is bounded by volumes this server ever served — probes of
        unknown vids must not grow it forever."""
        ent = self._access.get(vid)
        if ent is None:
            ent = self._access[vid] = [0, 0.0]
        ent[0] += n
        ent[1] = time.monotonic()

    def _drop_access(self, vid: int) -> None:
        self._access.pop(vid, None)

    def access_snapshot(self) -> dict:
        """vid -> {"reads": total, "last_read_age_s": seconds | None}."""
        now = time.monotonic()
        return {vid: {"reads": ent[0],
                      "last_read_age_s": round(now - ent[1], 3)}
                for vid, ent in list(self._access.items())}

    # -- coder selection (the pluggable north-star seam) --------------------
    def coder(self, d: int | None = None, p: int | None = None,
              codec: str | None = None) -> ErasureCoder:
        """The resolved backend's coder; layered codecs (piggyback, msr)
        wrap it as their GF engine. A coder that fails to construct or
        compute raises to the RPC — there is no host retry."""
        d = d or self.ec_geometry.d
        p = p or self.ec_geometry.p
        codec = codec or self.ec_codec
        if codec and codec != "rs":
            return codec_coder(codec, d, p, backend=self.backend)
        return get_coder(self.backend, d, p)

    # -- volume lifecycle ---------------------------------------------------
    def find_volume(self, vid: int) -> Volume | None:
        for loc in self.locations:
            v = loc.volumes.get(vid)
            if v is not None:
                return v
        return None

    def unmount_volume(self, vid: int) -> bool:
        """Close a volume and drop it from serving; files stay on disk
        (reference volume_grpc_admin.go VolumeUnmount)."""
        for loc in self.locations:
            v = loc.volumes.get(vid)
            if v is not None:
                with loc.lock:
                    loc.volumes.pop(vid, None)
                v.close()
                self._drop_access(vid)
                return True
        return False

    def mount_volume(self, vid: int, collection: str = "") -> Volume:
        """(Re)open an on-disk volume into serving (VolumeMount)."""
        v = self.find_volume(vid)
        if v is not None:
            return v
        for loc in self.locations:
            base = Volume.path_for(loc.directory, collection, vid)
            if os.path.exists(base + ".dat"):
                v = Volume(loc.directory, collection, vid,
                           create_if_missing=False)
                with loc.lock:
                    loc.volumes[vid] = v
                return v
        raise KeyError(f"volume {vid} not found on disk")

    def reload_volume(self, vid: int) -> Volume | None:
        """Re-open a volume whose backing changed (tier upload/download
        swaps the .dat between local disk and a remote backend)."""
        for loc in self.locations:
            v = loc.volumes.get(vid)
            if v is not None:
                try:
                    v.close()
                except Exception as e:  # noqa: BLE001
                    log.debug("stale volume handle close failed: %s", e)
                nv = Volume(loc.directory, v.collection, vid,
                            create_if_missing=False)
                loc.volumes[vid] = nv
                return nv
        return None

    def find_ec_volume(self, vid: int) -> EcVolume | None:
        for loc in self.locations:
            ev = loc.ec_volumes.get(vid)
            if ev is not None:
                return ev
        return None

    def _location_for(self, disk_type: str | None = None) -> DiskLocation:
        cands = [l for l in self.locations
                 if (disk_type is None or l.disk_type == disk_type)
                 and l.free_slots() > 0 and l.has_free_space()]
        if not cands:
            raise OSError(f"no free slots for disk type {disk_type}")
        return max(cands, key=lambda l: l.free_slots())

    def add_volume(self, vid: int, collection: str = "",
                   replication: str = "000", ttl: str = "",
                   disk_type: str | None = None) -> Volume:
        if self.find_volume(vid) is not None:
            raise FileExistsError(f"volume {vid} exists")
        loc = self._location_for(disk_type)
        v = Volume(loc.directory, collection, vid,
                   needle_map_kind=loc.needle_map_kind,
                   replica_placement=t.ReplicaPlacement.parse(replication),
                   ttl=t.TTL.parse(ttl))
        with loc.lock:
            loc.volumes[vid] = v
        log.info("allocated volume %d (col=%r) at %s", vid, collection, loc.directory)
        return v

    def delete_volume(self, vid: int, only_empty: bool = False) -> None:
        for loc in self.locations:
            v = loc.volumes.get(vid)
            if v is None:
                continue
            if only_empty and v.file_count > 0:
                raise OSError(f"volume {vid} not empty")
            with loc.lock:
                loc.volumes.pop(vid, None)
            v.destroy()
            if self.find_ec_volume(vid) is None:
                self._drop_access(vid)  # ec conversion keeps the heat
            return
        raise KeyError(f"volume {vid} not found")

    def mark_readonly(self, vid: int, read_only: bool = True) -> None:
        v = self.find_volume(vid)
        if v is None:
            raise KeyError(f"volume {vid} not found")
        v.read_only = read_only

    # -- data path ----------------------------------------------------------
    def write_needle(self, vid: int, n: Needle, sync: bool = False) -> int:
        # slow/failing disk on the single-needle write path (the chaos
        # read-storm's store.read twin; a delay armed here models a slow
        # disk deterministically)
        failpoints.check("store.write")
        v = self.find_volume(vid)
        if v is None:
            raise KeyError(f"volume {vid} not found")
        return v.write_needle(n, sync=sync)

    def write_needles_bulk(self, vid: int, needles: "list[Needle]",
                           ) -> "list[int]":
        """Bulk-PUT storage path: one lock, one .dat write, one batched
        needle-map update, one fsync for the whole frame."""
        failpoints.check("volume.bulk.write")  # bad disk mid-frame
        v = self.find_volume(vid)
        if v is None:
            raise KeyError(f"volume {vid} not found")
        return v.write_needles(needles)

    def read_needle(self, vid: int, needle_id: int, cookie: int | None = None,
                    shard_reader=None) -> Needle:
        failpoints.check("store.read")  # delay = slow disk; error = bad disk
        for v in self._read_volumes(vid):
            self.note_read(vid)  # the vid resolved locally: it is heat
            try:
                return v.read_needle(needle_id, cookie=cookie)
            except VolumeClosedError:
                continue  # retry through the refreshed mapping
        ev = self.find_ec_volume(vid)
        if ev is not None:
            self.note_read(vid)
            return ev.read_needle(needle_id, cookie=cookie,
                                  shard_reader=shard_reader)
        raise KeyError(f"volume {vid} not found")

    def read_needles_bulk(self, vid: int, pairs: "list[tuple[int, int]]",
                          shard_reader=None,
                          byte_budget: "int | None" = None):
        """Bulk-GET storage path: resolve + read a whole (key, cookie)
        batch through the lock-free read protocol (volume.read_needles).
        EC volumes answer per needle (each read may take the degraded
        reconstruct path). `byte_budget` bounds materialized payload
        bytes — past it, found needles report READ_OVERFLOW unread.
        Returns [(status, Needle | None)]."""
        failpoints.check("store.read")
        from .bulk import (READ_ERROR, READ_NOT_FOUND, READ_OK,
                           READ_OVERFLOW)
        for v in self._read_volumes(vid):
            self.note_read(vid, n=len(pairs))
            try:
                return v.read_needles(pairs, byte_budget=byte_budget)
            except VolumeClosedError:
                continue
        ev = self.find_ec_volume(vid)
        if ev is None:
            raise KeyError(f"volume {vid} not found")
        self.note_read(vid, n=len(pairs))
        out = []
        used = 0
        for key, cookie in pairs:
            if byte_budget is not None and used >= byte_budget:
                out.append((READ_OVERFLOW, None))
                continue
            try:
                n = ev.read_needle(key, cookie=cookie,
                                   shard_reader=shard_reader)
                used += len(n.data)
                out.append((READ_OK, n))
            except KeyError:
                out.append((READ_NOT_FOUND, None))
            except Exception as e:  # noqa: BLE001 — per-needle status
                log.debug("bulk ec read %d/%x: %s", vid, key, e)
                out.append((READ_ERROR, None))
        return out

    def _read_volumes(self, vid: int):
        """Volume objects to try for a read: the current mapping, then
        — if a lock-free read lost the race against a vacuum-commit /
        remount swap (VolumeClosedError) — the refreshed mapping, until
        the swap window passes. The mapping is re-consulted IMMEDIATELY
        after a failure (the replacement volume usually landed while the
        failed read was in flight); the sleep only covers the case where
        the old closed object is still mapped mid-swap. The deadline
        bounds BOTH branches — back-to-back swaps of a hot volume must
        not spin a read past the window."""
        deadline = time.monotonic() + 1.0
        last = None
        while True:
            if time.monotonic() > deadline:
                raise VolumeClosedError(
                    f"volume {vid} kept closing under reads")
            v = self.find_volume(vid)
            if v is None:
                return
            if v is not last:
                last = v
                yield v
                continue  # consumer failed on a fresh object: re-check now
            time.sleep(0.01)  # swap in flight: the new mapping lands soon

    def delete_needle(self, vid: int, needle_id: int) -> bool:
        failpoints.check("store.delete")  # bad disk on the tombstone path
        v = self.find_volume(vid)
        if v is None:
            raise KeyError(f"volume {vid} not found")
        return v.delete_needle(needle_id)

    # -- EC operations (reference volume_grpc_erasure_coding.go) -----------
    def generate_ec_shards(self, vid: int, collection: str = "",
                           d: int | None = None, p: int | None = None,
                           stats: "dict | None" = None,
                           codec: str | None = None) -> str:
        v = self.find_volume(vid)
        if v is None:
            raise KeyError(f"volume {vid} not found")
        geo = EcGeometry(d or self.ec_geometry.d, p or self.ec_geometry.p,
                         self.ec_geometry.large_block,
                         self.ec_geometry.small_block)
        v.sync()
        base = v.file_name()
        encode_volume(base + ".dat", base, geo,
                      self.coder(geo.d, geo.p, codec=codec),
                      idx_path=base + ".idx", stats=stats)
        return base

    def generate_ec_shards_batch(self, vids: "list[int]", collection: str = "",
                                 d: int | None = None, p: int | None = None,
                                 stats: "dict | None" = None,
                                 codec: str | None = None,
                                 targets: "dict | None" = None,
                                 ) -> "list[int]":
        """Encode many local volumes through ONE shared device stream.

        TPU extension over the reference's per-volume VolumeEcShardsGenerate
        (volume_grpc_erasure_coding.go:39): slabs from all volumes are batched
        into fixed-shape [B, d, C] device calls so the MXU never idles on a
        volume boundary (ec/stream.py). Returns the vids encoded.

        `targets` maps a vid to its other holders, [(grpc address, shard
        ids)]: those shards are streamed to them as they are written
        (ec/push.py) and sealed there before this returns; `stats["pushed"]`
        then lists (vid, address, shard ids) of what went.
        """
        from ..ec import push, stream
        geo = EcGeometry(d or self.ec_geometry.d, p or self.ec_geometry.p,
                         self.ec_geometry.large_block,
                         self.ec_geometry.small_block)
        holders: dict = {}
        jobs, done = [], []
        for vid in vids:
            v = self.find_volume(vid)
            if v is None:
                # volume may have been deleted/moved since the caller's
                # topology snapshot; encode the rest (the response's
                # encoded_volume_ids tells the caller what actually ran)
                continue
            v.sync()
            base = v.file_name()
            jobs.append((base + ".dat", base, base + ".idx"))
            done.append(vid)
            holders[base + ".dat"] = [
                push.Holder(addr, vid, collection, list(sids))
                for addr, sids in (targets or {}).get(vid, [])]
        if jobs:
            stream.encode_volumes(jobs, geo,
                                  self.coder(geo.d, geo.p, codec=codec),
                                  stats=stats, push=holders)
        if stats is not None:
            stats["pushed"] = [(h.vid, h.address, h.pushed)
                               for hs in holders.values() for h in hs
                               if h.pushed]
        return done

    def mount_ec_shards(self, vid: int, collection: str = "") -> EcVolume:
        for loc in self.locations:
            old = loc.ec_volumes.get(vid)
            if old is not None:  # remount: rescan shard files on disk
                old.close()
                ev = EcVolume(old.base, vid, collection, old.geo)
                with loc.lock:
                    loc.ec_volumes[vid] = ev
                return ev
        for loc in self.locations:
            base = loc.base_name(collection, vid)
            if os.path.exists(base + ".ecx") or any(
                    os.path.exists(base + ec_files.shard_ext(i))
                    for i in range(32)):
                ev = EcVolume(base, vid, collection)
                with loc.lock:
                    loc.ec_volumes[vid] = ev
                return ev
        raise KeyError(f"no ec shards for volume {vid}")

    def unmount_ec_shards(self, vid: int, shard_ids: list[int] | None = None) -> None:
        for loc in self.locations:
            ev = loc.ec_volumes.get(vid)
            if ev is None:
                continue
            if shard_ids is None:
                with loc.lock:
                    loc.ec_volumes.pop(vid, None)
                ev.close()
                self._drop_access(vid)
            else:
                for sid in shard_ids:
                    sh = ev.shards.pop(sid, None)
                    if sh:
                        sh.close()
                if not ev.shards:
                    with loc.lock:
                        loc.ec_volumes.pop(vid, None)
                    ev.close()
                    self._drop_access(vid)
            return

    def rebuild_ec_shards(self, vid: int, collection: str = "",
                          shard_reader=None,
                          remote_shards: "list[int] | None" = None,
                          stats: "dict | None" = None,
                          fragment_reader=None,
                          fold_planner=None) -> list[int]:
        """Rebuild missing shards locally, decoding with the codec the
        .vif seal says encoded them. Survivors not on this disk are
        fetched by RANGE through `shard_reader` (the volume server wires
        it to VolumeEcShardRead), so a repair-efficient codec moves only
        its plan's byte ranges instead of d full shards; `fold_planner`
        (geo plane, ec/encoder.py contract) lets far-DC survivors fold
        behind a relay before crossing expensive links."""
        ev = self.find_ec_volume(vid)
        base = ev.base if ev else None
        if base is None:
            for loc in self.locations:
                cand = loc.base_name(collection, vid)
                if os.path.exists(cand + ".ecx"):
                    base = cand
                    break
        if base is None:
            raise KeyError(f"no ec files for volume {vid}")
        info = ec_files.read_vif(base + ".vif")
        geo = EcGeometry.from_vif(info, self.ec_geometry)
        if ev:
            ev.close()
        coder = self.coder(geo.d, geo.p, codec=info.get("codec", "rs"))
        rebuilt = rebuild_shards(base, geo, coder,
                                 shard_reader=shard_reader,
                                 remote_shards=remote_shards, stats=stats,
                                 fragment_reader=fragment_reader,
                                 fold_planner=fold_planner)
        if ev:
            for loc in self.locations:
                if loc.ec_volumes.get(vid) is ev:
                    loc.ec_volumes[vid] = EcVolume(base, vid, collection, geo)
        return rebuilt

    def ec_shards_to_volume(self, vid: int, collection: str = "") -> Volume:
        """Decode EC shards back into a normal volume (ShardsToVolume RPC)."""
        ev = self.find_ec_volume(vid)
        if ev is None:
            raise KeyError(f"no ec volume {vid}")
        base = ev.base
        geo = ev.geo
        coder = self.coder(geo.d, geo.p, codec=ev.codec)
        decode_volume(base, base + ".dat", geo, coder)
        if os.path.exists(base + ".ecx"):
            ec_files.write_idx_from_ecx(base + ".ecx", base + ".ecj", base + ".idx")
        else:
            # no index sidecar survived: rebuild the .idx by scanning the .dat
            # (reference `weed fix` behavior, command/fix.go:74), then replay
            # the delete journal so journal-only deletes stay deleted
            from .needle_map import _ENTRY
            from .volume import rebuild_idx_from_dat
            rebuild_idx_from_dat(base + ".dat", base + ".idx")
            journaled = ec_files.read_ecj(base + ".ecj")
            if journaled:
                with open(base + ".idx", "ab") as f:
                    for nid in journaled:
                        f.write(_ENTRY.pack(nid, 0, t.TOMBSTONE_SIZE))
        self.unmount_ec_shards(vid)
        for loc in self.locations:
            if os.path.dirname(base) == loc.directory:
                v = Volume(loc.directory, collection, vid, create_if_missing=False)
                with loc.lock:
                    loc.volumes[vid] = v
                return v
        raise RuntimeError("location vanished")

    # -- lifecycle tiering (EC→remote offload, remote→local promote) --------
    def offload_ec_shards(self, vid: int, spec: str, collection: str = ""
                          ) -> int:
        """Move this holder's LOCAL shard payloads of an EC volume to a
        remote tier. The .ecx/.ecj/.vif sidecars stay local (lookup is
        local, payload is remote), the .vif records the remote mapping,
        and the volume keeps serving through lazy ranged reads. Returns
        bytes offloaded (0 = nothing local to move; idempotent)."""
        from ..ec.volume import EcVolume, RemoteEcVolumeShard
        from .backend import open_remote
        ev = self.find_ec_volume(vid)
        if ev is None:
            raise KeyError(f"no ec volume {vid}")
        remote = dict(ev.remote_spec or {"spec": spec, "keys": {},
                                         "sizes": {}})
        if remote["spec"] != spec:
            # one remote tier per volume: mixing specs would strand the
            # earlier objects when the .vif only records one client
            raise ValueError(
                f"ec volume {vid} already offloaded to "
                f"{remote['spec']!r}; refusing {spec!r}")
        local = [(sid, sh) for sid, sh in sorted(ev.shards.items())
                 if not isinstance(sh, RemoteEcVolumeShard)]
        if not local:
            return 0
        client = open_remote(spec)
        prefix = f"{collection or ev.collection or 'default'}"
        moved = 0
        uploaded: list[tuple[int, str, int]] = []
        try:
            for sid, sh in local:
                key = f"{prefix}/{vid}{ec_files.shard_ext(sid)}"
                size = client.write_object(key, sh.path)
                uploaded.append((sid, key, size))
                moved += size
        except Exception:
            # roll back: local files are untouched, so the volume is
            # still whole — only already-uploaded objects are orphaned
            for _sid, key, _size in uploaded:
                try:
                    client.delete_object(key)
                except Exception as e:  # noqa: BLE001
                    log.warning("offload rollback of %s: %s", key, e)
            raise
        for sid, key, size in uploaded:
            remote["keys"][str(sid)] = key
            remote["sizes"][str(sid)] = size
        # seal the mapping BEFORE deleting local payloads: a crash in
        # between leaves both copies (served local, cleaned on the next
        # pass) — never neither. Locked update: the idle-close stamp on
        # the heartbeat thread must not lose this seal.
        ec_files.update_vif(ev.base + ".vif", {"remote_shards": remote})
        # unlink the local payloads, then swap in a fresh EcVolume that
        # scans remote read-through. The OLD object is deliberately NOT
        # closed: in-flight reads keep their open fds (posix unlink
        # semantics) and finish byte-identical mid-transition; the fds
        # release when the object is collected
        for _sid, sh in local:
            os.remove(sh.path)
        for loc in self.locations:
            if loc.ec_volumes.get(vid) is ev:
                nev = EcVolume(ev.base, vid, ev.collection, ev.geo)
                with loc.lock:
                    loc.ec_volumes[vid] = nev
        return moved

    def promote_ec_shards(self, vid: int, collection: str = "",
                          keep_remote: bool = False) -> int:
        """Pull this holder's offloaded shard payloads back to local
        disk (promote-on-heat). Downloads land beside the sidecars
        under a temp name and swap in atomically — a torn download
        never costs the remote copy. Returns bytes promoted."""
        from ..ec.volume import EcVolume, RemoteEcVolumeShard
        from .backend import open_remote
        ev = self.find_ec_volume(vid)
        if ev is None:
            raise KeyError(f"no ec volume {vid}")
        if not ev.remote_spec:
            return 0
        client = open_remote(ev.remote_spec["spec"])
        remote_shards = [(sid, sh) for sid, sh in sorted(ev.shards.items())
                         if isinstance(sh, RemoteEcVolumeShard)]
        moved = 0
        landed: list[tuple[int, str]] = []
        try:
            for sid, sh in remote_shards:
                path = ev.base + ec_files.shard_ext(sid)
                tmp = path + ".tiertmp"
                client.read_object_to(sh.key, tmp)
                got = os.path.getsize(tmp)
                if sh.size and got != sh.size:
                    raise OSError(f"short promote of shard {sid}: "
                                  f"{got} != {sh.size}")
                # the remote copy may be deleted below (keep_remote
                # False): the local bytes and their rename must be
                # durable before the last other copy goes away
                fsutil.fsync_path(tmp)
                os.replace(tmp, path)
                landed.append((sid, sh.key))
                moved += got
            fsutil.fsync_dir(ev.base + ".vif")
        except Exception:
            for sid, _key in landed:
                try:
                    os.remove(ev.base + ec_files.shard_ext(sid))
                except OSError:
                    pass
            raise
        ec_files.update_vif(ev.base + ".vif", remove=("remote_shards",))
        # swap in a fresh local-backed EcVolume; the old (remote-backed)
        # object is NOT closed so in-flight ranged reads finish — same
        # mid-transition contract as offload above
        for loc in self.locations:
            if loc.ec_volumes.get(vid) is ev:
                nev = EcVolume(ev.base, vid, ev.collection, ev.geo)
                with loc.lock:
                    loc.ec_volumes[vid] = nev
        if not keep_remote:
            # delete EVERY mapped key, not just the shards downloaded
            # this pass: a shard present both locally and remotely (a
            # promote raced a crash) still has a remote object, and the
            # mapping just popped was its last reference
            for key in (ev.remote_spec or {}).get("keys", {}).values():
                try:
                    client.delete_object(key)
                except Exception as e:  # noqa: BLE001 — orphan, not data
                    log.warning("delete promoted remote shard %s: %s",
                                key, e)
        return moved

    def move_volume_local(self, vid: int, disk_type: str) -> str:
        """Same-server cross-tier move: copy a volume's files to a
        location of `disk_type` on THIS server and retire the old copy
        (the disk-to-disk half of volume.tier.move that VolumeCopy's
        no-same-server rule used to refuse). Returns the new directory."""
        import shutil
        src_loc = None
        v = None
        for loc in self.locations:
            v = loc.volumes.get(vid)
            if v is not None:
                src_loc = loc
                break
        if v is None:
            raise KeyError(f"volume {vid} not found")
        if src_loc.disk_type == disk_type:
            return src_loc.directory  # already on the target tier
        dst_loc = self._location_for(disk_type)
        # freeze for the copy window (callers normally froze already —
        # volume.tier.move does — but an append landing between copy
        # and swap would otherwise be silently lost)
        was_read_only = v.read_only
        v.read_only = True
        v.sync()
        src_base = v.file_name()
        dst_base = dst_loc.base_name(v.collection, vid)
        exts = [e for e in (".dat", ".idx", ".vif")
                if os.path.exists(src_base + e)]
        copied = []
        try:
            for ext in exts:
                # copy + fsync under a temp name, then rename: a crash
                # mid-move leaves the source authoritative
                tmp = dst_base + ext + ".tiertmp"
                shutil.copyfile(src_base + ext, tmp)
                with open(tmp, "rb+") as f:
                    os.fsync(f.fileno())
                os.replace(tmp, dst_base + ext)
                copied.append(dst_base + ext)
            # the source files are removed once the swap commits: the
            # destination's directory entries must survive first
            fsutil.fsync_dir(dst_base + ".dat")
            # build the replacement FULLY (needle-map load, integrity
            # scan) before touching the mapping: reads must never find
            # the vid unmapped mid-move
            nv = Volume(dst_loc.directory, v.collection, vid,
                        needle_map_kind=dst_loc.needle_map_kind,
                        create_if_missing=False)
        except Exception:
            v.read_only = was_read_only
            for p in copied:
                try:
                    os.remove(p)
                except OSError:
                    pass
            raise
        nv.read_only = was_read_only
        # map the destination BEFORE unmapping the source — both serve
        # identical frozen bytes, so whichever a racing read resolves
        # is correct; closing the source then routes stragglers through
        # the refreshed mapping (VolumeClosedError retry)
        with dst_loc.lock:
            dst_loc.volumes[vid] = nv
        with src_loc.lock:
            src_loc.volumes.pop(vid, None)
        v.close()
        for ext in exts:
            try:
                os.remove(src_base + ext)
            except OSError as e:
                log.warning("retire source copy %s%s: %s", src_base, ext, e)
        return dst_loc.directory

    def close_idle_ec_handles(self, idle_s: float = 3600.0) -> int:
        """Idle-close EC shard handles (fork ec_volume.go:348 IsExpire)."""
        n = 0
        for loc in self.locations:
            for ev in loc.ec_volumes.values():
                if ev.close_idle(idle_s):
                    n += 1
        return n

    def delete_expired_ec_volumes(self, now: "float | None" = None
                                  ) -> "list[dict]":
        """Fork behavior (store.go:389): reap EC volumes past DestroyTime
        into the soft-delete trash dir. `now` is injectable so the TTL
        boundary is testable without sleeping: a volume reaps AT its
        destroy_time instant (<=), not one poll-interval later.

        Returns one record per reaped volume for the caller to journal:
        {"vid", "collection", "from" (ec|remote), "bytes" (local bytes
        soft-moved to trash)}."""
        from ..ec.volume import RemoteEcVolumeShard
        from ..lifecycle import TIER_EC, TIER_REMOTE
        if now is None:
            now = time.time()  # swtpu-lint: disable=wallclock-duration (destroy_time is persisted wall-clock)
        reaped = []
        for loc in self.locations:
            for vid, ev in list(loc.ec_volumes.items()):
                if ev.destroy_time and ev.destroy_time <= now:
                    with loc.lock:
                        loc.ec_volumes.pop(vid, None)
                    rec = {"vid": vid, "collection": ev.collection,
                           "from": (TIER_REMOTE if ev.remote_spec
                                    else TIER_EC),
                           "bytes": sum(
                               sh.size for sh in ev.shards.values()
                               if not isinstance(sh, RemoteEcVolumeShard))}
                    ev.destroy(to_trash=os.path.join(loc.directory, ".trash"))
                    self._drop_access(vid)
                    reaped.append(rec)
        return reaped

    def restore_ec_volume_from_trash(self, vid: int, collection: str = ""
                                     ) -> EcVolume:
        """Undo a DestroyTime reap before the trash grace expires: move
        the soft-deleted files back beside the live volumes and remount.
        (The reap keeps remote-tier objects, so an offloaded volume
        restores with its remote shards intact.)"""
        for loc in self.locations:
            trash = os.path.join(loc.directory, ".trash")
            if not os.path.isdir(trash):
                continue
            base = os.path.basename(loc.base_name(collection, vid))
            moved = False
            for fn in os.listdir(trash):
                stem, ext = os.path.splitext(fn)
                if stem == base:
                    # trash restore: a crash rolling the move back leaves
                    # the shard in .trash, restorable by re-running
                    os.replace(os.path.join(trash, fn),  # swtpu-lint: disable=rename-no-dir-fsync
                               os.path.join(loc.directory, fn))
                    moved = True
            if moved:
                return self.mount_ec_shards(vid, collection)
        raise KeyError(f"ec volume {vid} not in trash")

    # -- heartbeat assembly (store.go:259) ----------------------------------
    def collect_heartbeat(self) -> dict:
        volumes, ec_shards = [], []
        max_file_key = 0
        for loc in self.locations:
            for vid, v in loc.volumes.items():
                max_file_key = max(max_file_key, v.nm.max_key)
                volumes.append({
                    "id": vid, "size": v.content_size,
                    "collection": v.collection,
                    "file_count": v.file_count,
                    "delete_count": v.deleted_count,
                    "deleted_byte_count": v.nm.deleted_size,
                    "read_only": v.read_only,
                    "replica_placement": v.super_block.replica_placement.to_byte(),
                    "version": v.super_block.version,
                    "ttl": int.from_bytes(v.super_block.ttl.to_bytes(), "little"),
                    "compact_revision": v.super_block.compaction_revision,
                    "modified_at_second": int(v.last_append_at_ns // 1e9),
                    "disk_type": loc.disk_type,
                })
            for vid, ev in loc.ec_volumes.items():
                ec_shards.append({
                    "id": vid, "collection": ev.collection,
                    "ec_index_bits": ev.shard_bits().bits,
                    "disk_type": loc.disk_type,
                    "destroy_time": ev.destroy_time,
                })
        return {
            "volumes": volumes, "ec_shards": ec_shards,
            "max_file_key": max_file_key,
            "max_volume_counts": self._max_volume_counts(),
        }

    def _max_volume_counts(self) -> dict[str, int]:
        out: dict[str, int] = {}
        for loc in self.locations:
            out[loc.disk_type] = out.get(loc.disk_type, 0) + loc.max_volume_count
        return out

    def status(self) -> dict:
        return {
            "volumes": sum(len(l.volumes) for l in self.locations),
            "ec_volumes": sum(len(l.ec_volumes) for l in self.locations),
            "locations": [l.directory for l in self.locations],
            "coder": self.backend,
            **device.status(),
        }

    def close(self) -> None:
        for loc in self.locations:
            for v in loc.volumes.values():
                v.close()
            for ev in loc.ec_volumes.values():
                ev.close()
