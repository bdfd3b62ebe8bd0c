"""FilerStore plugin interface + built-in backends.

Reference: weed/filer/filerstore.go:21-44 (the 8-method plugin interface
implemented by 20 backends) and abstract_sql/ (shared SQL logic). Here the
registry ships three embeddable backends — memory (tests/dev), sqlite
(stdlib, durable single-node), and logdb (append-only pb log + in-memory
index, recovering the reference's leveldb role without a leveldb binding).
All store serialized filer_pb2.Entry blobs keyed by (directory, name).
"""

from __future__ import annotations

import os
import sqlite3
import struct
import threading
from bisect import bisect_left, bisect_right, insort
from typing import Iterator

from ..pb import filer_pb2 as fpb
from ..utils import fsutil


class FilerStore:
    """Abstract store. Paths are absolute, '/'-separated, no trailing '/'."""

    name = "abstract"

    def insert_entry(self, directory: str, entry: fpb.Entry) -> None:
        raise NotImplementedError

    def update_entry(self, directory: str, entry: fpb.Entry) -> None:
        raise NotImplementedError

    def find_entry(self, directory: str, name: str) -> fpb.Entry | None:
        raise NotImplementedError

    def delete_entry(self, directory: str, name: str) -> None:
        raise NotImplementedError

    def delete_folder_children(self, directory: str) -> None:
        raise NotImplementedError

    def list_entries(self, directory: str, start_from: str = "",
                     inclusive: bool = False, limit: int = 2**31,
                     prefix: str = "") -> Iterator[fpb.Entry]:
        raise NotImplementedError

    def kv_get(self, key: bytes) -> bytes | None:
        raise NotImplementedError

    def kv_put(self, key: bytes, value: bytes) -> None:
        raise NotImplementedError

    def close(self) -> None:
        pass


class MemoryStore(FilerStore):
    """Sorted in-memory map — the conformance-suite reference backend."""

    name = "memory"

    def __init__(self):
        self._lock = threading.RLock()
        self._dirs: dict[str, list[str]] = {}   # directory -> sorted names
        self._blobs: dict[tuple[str, str], bytes] = {}
        self._kv: dict[bytes, bytes] = {}

    def insert_entry(self, directory, entry):
        with self._lock:
            key = (directory, entry.name)
            if key not in self._blobs:
                insort(self._dirs.setdefault(directory, []), entry.name)
            self._blobs[key] = entry.SerializeToString()

    update_entry = insert_entry

    def find_entry(self, directory, name):
        blob = self._blobs.get((directory, name))
        if blob is None:
            return None
        e = fpb.Entry()
        e.ParseFromString(blob)
        return e

    def delete_entry(self, directory, name):
        with self._lock:
            if self._blobs.pop((directory, name), None) is not None:
                names = self._dirs[directory]
                names.pop(bisect_left(names, name))

    def delete_folder_children(self, directory):
        with self._lock:
            for name in self._dirs.pop(directory, []):
                self._blobs.pop((directory, name), None)

    def list_entries(self, directory, start_from="", inclusive=False,
                     limit=2**31, prefix=""):
        with self._lock:
            names = list(self._dirs.get(directory, []))
        lo = 0
        if start_from:
            lo = (bisect_left if inclusive else bisect_right)(names, start_from)
        n = 0
        for name in names[lo:]:
            if prefix and not name.startswith(prefix):
                if name[:len(prefix)] > prefix:
                    break  # sorted: no later name can match
                continue
            if n >= limit:
                break
            e = self.find_entry(directory, name)
            if e is not None:
                n += 1
                yield e

    def kv_get(self, key):
        return self._kv.get(key)

    def kv_put(self, key, value):
        self._kv[key] = value


# mid-module import: sql_store needs FilerStore (defined above); doing it
# here keeps `from .store import SqliteStore` working for existing callers
from .sql_store import AbstractSqlStore, SqliteDialect  # noqa: E402


class SqliteStore(AbstractSqlStore):
    """Durable stdlib-sqlite backend — the always-on dialect of the shared
    SQL layer (reference abstract_sql + sqlite dirs); mysql/postgres
    dialects live beside it in sql_store.py."""

    def __init__(self, path: str):
        self._path = path
        super().__init__(SqliteDialect(path))


class LogDbStore(MemoryStore):
    """Append-only pb log + in-memory sorted index; replayed at open.

    Fills the reference's default-leveldb slot (weed/filer/leveldb) with a
    WAL the image can build without a leveldb binding: every mutation is a
    length-prefixed record (op, directory, name, blob), compacted when the
    log exceeds 4x live size."""

    name = "logdb"
    _REC = struct.Struct("<BHH I")  # op, len(dir), len(name), len(blob)
    OP_PUT, OP_DEL, OP_DELDIR, OP_KV = 0, 1, 2, 3

    def __init__(self, path: str):
        super().__init__()
        self._path = path
        self._wlock = threading.Lock()
        self._written = 0
        if os.path.exists(path):
            self._replay()
        self._f = open(path, "ab")

    def _replay(self):
        with open(self._path, "rb") as f:
            while True:
                hdr = f.read(self._REC.size)
                if len(hdr) < self._REC.size:
                    break
                op, dl, nl, bl = self._REC.unpack(hdr)
                body = f.read(dl + nl + bl)
                if len(body) < dl + nl + bl:
                    break  # torn tail write — ignore (volume_checking analogue)
                blob = body[dl + nl:]
                if op == self.OP_KV:  # first field is a raw bytes key
                    MemoryStore.kv_put(self, body[:dl], blob)
                    continue
                d = body[:dl].decode()
                n = body[dl:dl + nl].decode()
                if op == self.OP_PUT:
                    e = fpb.Entry()
                    e.ParseFromString(blob)
                    MemoryStore.insert_entry(self, d, e)
                elif op == self.OP_DEL:
                    MemoryStore.delete_entry(self, d, n)
                elif op == self.OP_DELDIR:
                    MemoryStore.delete_folder_children(self, d)

    def _append(self, op: int, d: bytes, n: bytes, blob: bytes):
        with self._wlock:
            self._f.write(self._REC.pack(op, len(d), len(n), len(blob)))
            self._f.write(d + n + blob)
            self._f.flush()
            self._written += 1
            if self._written > 10_000 and self._written > 4 * max(len(self._blobs), 1):
                self._compact()

    def _compact(self):
        tmp = self._path + ".compact"
        with open(tmp, "wb") as f:
            for (d, n), blob in list(self._blobs.items()):
                db = d.encode()
                f.write(self._REC.pack(self.OP_PUT, len(db), len(n.encode()),
                                       len(blob)))
                f.write(db + n.encode() + blob)
            for k, v in list(self._kv.items()):
                f.write(self._REC.pack(self.OP_KV, len(k), 0, len(v)))
                f.write(k + v)
            # the compacted log REPLACES the only copy of this metadata:
            # pin its bytes before the rename makes it authoritative
            f.flush()
            os.fsync(f.fileno())
        self._f.close()
        os.replace(tmp, self._path)
        fsutil.fsync_dir(self._path)
        self._f = open(self._path, "ab")
        self._written = len(self._blobs)

    def insert_entry(self, directory, entry):
        MemoryStore.insert_entry(self, directory, entry)
        self._append(self.OP_PUT, directory.encode(), entry.name.encode(),
                     entry.SerializeToString())

    update_entry = insert_entry

    def delete_entry(self, directory, name):
        MemoryStore.delete_entry(self, directory, name)
        self._append(self.OP_DEL, directory.encode(), name.encode(), b"")

    def delete_folder_children(self, directory):
        MemoryStore.delete_folder_children(self, directory)
        self._append(self.OP_DELDIR, directory.encode(), b"", b"")

    def kv_put(self, key, value):
        MemoryStore.kv_put(self, key, value)
        self._append(self.OP_KV, key, b"", value)

    def close(self):
        with self._wlock:
            self._f.close()


def open_store(spec: str) -> FilerStore:
    """spec: 'memory', 'sqlite:/path/db.sqlite', 'logdb:/path/filer.log',
    'lsm:/dir', 'redis:host:port', 'mongo:host:port', 'etcd:host:port',
    'mysql:k=v ...', 'postgres:<dsn>'."""
    kind, _, arg = spec.partition(":")
    if kind == "memory":
        return MemoryStore()
    if kind == "sqlite":
        return SqliteStore(arg or "filer.sqlite")
    if kind == "logdb":
        return LogDbStore(arg or "filer.logdb")
    if kind in ("lsm", "leveldb"):
        # "leveldb" accepted for reference-flag familiarity: LsmStore is
        # the from-scratch leveldb analogue
        return LsmStore(arg or "filer-lsm")
    if kind == "redis":
        from .redis_store import RedisStore
        return RedisStore(arg.lstrip("/") or "127.0.0.1:6379")
    if kind in ("mongo", "mongodb"):
        from .mongo_store import MongoStore
        return MongoStore(arg.lstrip("/") or "127.0.0.1:27017")
    if kind == "etcd":
        from .etcd_store import EtcdStore
        return EtcdStore(arg.lstrip("/") or "127.0.0.1:2379")
    if kind == "mysql":
        from .sql_store import AbstractSqlStore, MysqlDialect
        kw = dict(kv.split("=", 1) for kv in arg.split() if "=" in kv)
        if "port" in kw:
            kw["port"] = int(kw["port"])
        return AbstractSqlStore(MysqlDialect(**kw))
    if kind == "postgres":
        from .sql_store import AbstractSqlStore, PostgresDialect
        return AbstractSqlStore(PostgresDialect(arg or "dbname=seaweedfs"))
    raise ValueError(f"unknown filer store {spec!r} (supported: memory, "
                     f"sqlite:<path>, logdb:<path>, lsm:<dir>, "
                     f"redis:<host:port>, mongo:<host:port>, etcd:<host:port>, "
                     f"mysql:<k=v ...>, postgres:<dsn>)")


class _Sst:
    """One immutable sorted run: sparse in-memory index (every
    INDEX_STRIDE-th key) over length-prefixed records on disk — memory
    per table is O(records / stride), not O(records) (leveldb's
    block-index shape; the round-3 review called the full per-key index
    'toy-calibrated')."""

    INDEX_STRIDE = 64
    _REC = struct.Struct("<BII")  # op (0 put / 1 del), klen, vlen

    def __init__(self, path: str):
        self.path = path
        self.size = os.path.getsize(path)
        # parallel arrays: bisect the keys, jump to the offset
        self._sparse_keys: list[bytes] = []
        self._sparse_offs: list[int] = []
        self.count = 0
        self._f = open(path, "rb")
        off = 0
        while True:
            hdr = self._f.read(self._REC.size)
            if len(hdr) < self._REC.size:
                break
            op, klen, vlen = self._REC.unpack(hdr)
            key = self._f.read(klen)
            if self.count % self.INDEX_STRIDE == 0:
                self._sparse_keys.append(key)
                self._sparse_offs.append(off)
            self.count += 1
            self._f.seek(vlen, 1)
            off += self._REC.size + klen + vlen

    def _floor_offset(self, key: bytes) -> int:
        """Record offset of the greatest sparse key <= key (0 if none)."""
        i = bisect_right(self._sparse_keys, key) - 1
        return self._sparse_offs[i] if i >= 0 else 0

    def records_from(self, key: bytes):
        """Yield (key, op, value) from the floor of `key` onward."""
        self._f.seek(self._floor_offset(key))
        while True:
            hdr = self._f.read(self._REC.size)
            if len(hdr) < self._REC.size:
                return
            op, klen, vlen = self._REC.unpack(hdr)
            k = self._f.read(klen)
            v = self._f.read(vlen)
            yield k, op, v

    def lookup(self, key: bytes):
        """(found, value|None): value None = tombstone. Values of the
        up-to-stride-1 records scanned on the way are seeked past, not
        read (filer entry blobs can be tens of KB each)."""
        self._f.seek(self._floor_offset(key))
        while True:
            hdr = self._f.read(self._REC.size)
            if len(hdr) < self._REC.size:
                return False, None
            op, klen, vlen = self._REC.unpack(hdr)
            k = self._f.read(klen)
            if k == key:
                return True, (None if op == 1 else self._f.read(vlen))
            if k > key:
                return False, None
            self._f.seek(vlen, 1)

    def close(self) -> None:
        try:
            self._f.close()
        except OSError:
            pass


class LsmStore(FilerStore):
    """Log-structured merge store: WAL + memtable + sorted SSTables —
    a from-scratch leveldb analogue (the reference's most common backend,
    weed/filer/leveldb; this image has no leveldb binding, so the storage
    engine itself is implemented here).

    Layout under `path/`:
      wal.log      length-prefixed mutations, fsync'd, replayed at open
      sst-<n>.sst  immutable sorted (key, value) runs; newest wins
    Keyspace: b"E" + dir + b"\\x00" + name for entries, b"K" + key for KV;
    deletes are tombstones.

    Scaling shape (r4): sparse per-table indexes (1 key in memory per 64
    records), an 8 MB / 4096-entry memtable, and TWO-LEVEL compaction —
    young tables merge among themselves (tombstones kept) and fold into
    the base table only once they reach a quarter of its size, so the big
    base is rewritten O(log n) times per n writes, not every 6 flushes.
    """

    name = "lsm"
    MEMTABLE_LIMIT = 4096
    MEMTABLE_BYTES = 8 << 20
    COMPACT_AT = 8
    _REC = _Sst._REC

    def __init__(self, path: str, memtable_limit: int | None = None):
        self.dir = path
        os.makedirs(path, exist_ok=True)
        if memtable_limit:
            self.MEMTABLE_LIMIT = memtable_limit
        self._lock = threading.RLock()
        # memtable: key -> value bytes | None (tombstone)
        self._mem: dict[bytes, bytes | None] = {}
        self._mem_bytes = 0
        self._ssts: list[tuple[int, _Sst]] = []  # newest LAST
        self._next_seq = 0
        for fn in sorted(os.listdir(path)):
            if fn.startswith("sst-") and fn.endswith(".sst"):
                seq = int(fn[4:-4])
                self._ssts.append((seq, _Sst(self._sst_path(seq))))
                self._next_seq = max(self._next_seq, seq + 1)
        self._ssts.sort(key=lambda t: t[0])
        self._wal_path = os.path.join(path, "wal.log")
        self._replay_wal()
        self._wal = open(self._wal_path, "ab")

    # -- file plumbing ------------------------------------------------------
    def _sst_path(self, seq: int) -> str:
        return os.path.join(self.dir, f"sst-{seq}.sst")

    def _replay_wal(self) -> None:
        if not os.path.exists(self._wal_path):
            return
        with open(self._wal_path, "rb") as f:
            while True:
                hdr = f.read(self._REC.size)
                if len(hdr) < self._REC.size:
                    break
                op, klen, vlen = self._REC.unpack(hdr)
                body = f.read(klen + vlen)
                if len(body) < klen + vlen:
                    break  # torn tail: drop the partial record
                key = body[:klen]
                old = self._mem.get(key)
                if old:
                    self._mem_bytes -= len(old)
                self._mem[key] = None if op == 1 else body[klen:]
                self._mem_bytes += vlen

    def _log(self, key: bytes, value: "bytes | None") -> None:
        rec = self._REC.pack(1 if value is None else 0, len(key),
                             0 if value is None else len(value))
        self._wal.write(rec + key + (value or b""))
        self._wal.flush()
        os.fsync(self._wal.fileno())

    # -- core write path ----------------------------------------------------
    def _put(self, key: bytes, value: "bytes | None") -> None:
        with self._lock:
            self._log(key, value)
            old = self._mem.get(key)
            if old:
                self._mem_bytes -= len(old)
            self._mem[key] = value
            self._mem_bytes += len(value or b"")
            if len(self._mem) >= self.MEMTABLE_LIMIT or \
                    self._mem_bytes >= self.MEMTABLE_BYTES:
                self._flush_memtable()

    @staticmethod
    def _write_sst(path: str, items) -> None:
        """items: sorted iterable of (key, value|None)."""
        with open(path, "wb") as f:
            for key, value in items:
                f.write(_Sst._REC.pack(1 if value is None else 0, len(key),
                                       0 if value is None else len(value)))
                f.write(key + (value or b""))
            f.flush()
            os.fsync(f.fileno())

    def _flush_memtable(self) -> None:
        """Write the memtable as a new SST, truncate the WAL (caller
        holds lock)."""
        if not self._mem:
            return
        seq = self._next_seq
        self._next_seq += 1
        tmp = self._sst_path(seq) + ".tmp"
        self._write_sst(tmp, ((k, self._mem[k]) for k in sorted(self._mem)))
        os.replace(tmp, self._sst_path(seq))
        # the WAL is truncated right below on the strength of this SST
        # existing; the rename must therefore survive the same crash
        fsutil.fsync_dir(self._sst_path(seq))
        self._ssts.append((seq, _Sst(self._sst_path(seq))))
        self._mem.clear()
        self._mem_bytes = 0
        self._wal.close()
        self._wal = open(self._wal_path, "wb")  # truncate
        if len(self._ssts) >= self.COMPACT_AT:
            self._compact()

    @staticmethod
    def _stream_merge(tables: "list[tuple[int, _Sst]]",
                      drop_tombstones: bool):
        """Streaming k-way merge of sorted runs, newest table wins per
        key — O(#tables) memory, so compacting a huge base never
        materializes the dataset."""
        import heapq
        runs = [((k, i, op, v) for k, op, v in sst.records_from(b""))
                for i, (_, sst) in enumerate(tables)]
        prev_key = None
        prev_val: "bytes | None" = None
        have = False
        # tuples sort by (key, table index); for equal keys the LAST item
        # seen has the highest index = the newest table
        for k, i, op, v in heapq.merge(*runs):
            if have and k != prev_key:
                if prev_val is not None or not drop_tombstones:
                    yield prev_key, prev_val
            prev_key, prev_val, have = k, (None if op == 1 else v), True
        if have and (prev_val is not None or not drop_tombstones):
            yield prev_key, prev_val

    def _compact(self) -> None:
        """Two-level compaction (caller holds lock): the YOUNG tables
        (everything after the base) merge into one — tombstones kept,
        they may shadow base keys — and fold into the base only once
        they reach a quarter of its size (then tombstones drop, since
        nothing older remains)."""
        base = self._ssts[0]
        young = self._ssts[1:]
        young_bytes = sum(s.size for _, s in young)
        full = len(self._ssts) == 1 or young_bytes * 4 >= base[1].size
        tables = self._ssts if full else young
        seq = self._next_seq
        self._next_seq += 1
        tmp = self._sst_path(seq) + ".tmp"
        self._write_sst(tmp, self._stream_merge(tables,
                                                drop_tombstones=full))
        os.replace(tmp, self._sst_path(seq))
        # inputs are unlinked below — the merged output's rename must be
        # durable before the only other copies of its keys disappear
        fsutil.fsync_dir(self._sst_path(seq))
        new_sst = (seq, _Sst(self._sst_path(seq)))
        self._ssts = [new_sst] if full else [base, new_sst]
        for oseq, osst in tables:
            osst.close()
            try:
                os.unlink(self._sst_path(oseq))
            except FileNotFoundError:
                pass

    # -- reads --------------------------------------------------------------
    def _get(self, key: bytes) -> "bytes | None":
        with self._lock:
            if key in self._mem:
                return self._mem[key]
            for seq, sst in reversed(self._ssts):  # newest first
                found, value = sst.lookup(key)
                if found:
                    return value
        return None

    def _scan(self, lo: bytes, hi: bytes) -> "Iterator[tuple[bytes, bytes]]":
        """Sorted live (key, value) pairs in [lo, hi); newest wins.
        Materialized under the lock, yielded outside it — a slow
        consumer must not block writers, and a concurrent compaction
        may unlink the SST a lazy reference would point at."""
        with self._lock:
            view: dict[bytes, "bytes | None"] = {}
            for seq, sst in self._ssts:  # oldest -> newest overwrites
                for key, op, value in sst.records_from(lo):
                    if key >= hi:
                        break
                    if key >= lo:
                        view[key] = None if op == 1 else value
            for key, value in self._mem.items():
                if lo <= key < hi:
                    view[key] = value
            pairs = [(k, view[k]) for k in sorted(view)
                     if view[k] is not None]
        yield from pairs

    # -- FilerStore contract ------------------------------------------------
    @staticmethod
    def _ekey(directory: str, name: str = "") -> bytes:
        return b"E" + directory.encode() + b"\x00" + name.encode()

    def insert_entry(self, directory, entry):
        self._put(self._ekey(directory, entry.name),
                  entry.SerializeToString())

    update_entry = insert_entry

    def find_entry(self, directory, name):
        raw = self._get(self._ekey(directory, name))
        if raw is None:
            return None
        e = fpb.Entry()
        e.ParseFromString(raw)
        return e

    def delete_entry(self, directory, name):
        self._put(self._ekey(directory, name), None)

    def delete_folder_children(self, directory):
        lo = self._ekey(directory)
        hi = lo[:-1] + b"\x01"
        for key, _ in list(self._scan(lo, hi)):
            self._put(key, None)

    def list_entries(self, directory, start_from="", inclusive=False,
                     limit=2**31, prefix=""):
        base = self._ekey(directory)
        lo, hi = base, base[:-1] + b"\x01"
        n = 0
        for key, raw in self._scan(lo, hi):
            name = key[len(base):].decode()
            if prefix and not name.startswith(prefix):
                continue
            if start_from:
                if name < start_from or (name == start_from
                                         and not inclusive):
                    continue
            if n >= limit:
                return
            e = fpb.Entry()
            e.ParseFromString(raw)
            n += 1
            yield e

    def kv_get(self, key):
        return self._get(b"K" + key)

    def kv_put(self, key, value):
        self._put(b"K" + key, value)

    def close(self):
        with self._lock:
            self._flush_memtable()
            self._wal.close()
            for _, sst in self._ssts:
                sst.close()
