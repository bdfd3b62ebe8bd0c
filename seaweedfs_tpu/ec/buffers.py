"""Host batch buffers that outlive the operation that filled them.

A rebuild stages every batch of survivor rows in one of `depth + 2` host
buffers (ec/stream.py:AsyncPipe). Made new for every rebuild, each buffer
is used about once, so every survivor byte lands in memory the process
has never touched, and first-touch memory fills far slower than touched
memory (PERF.md section 5, bottleneck 4). A `KeptBuffers` keeps ONE set
between operations:

* one operation owns the set at a time (`lease`). An operation that finds
  the set taken, or too small for what it asks, makes its own set as if
  nothing were kept; the set handed back last is the one kept;
* a set fits any shape its buffers have the bytes for: a `[32, 14, 1 MiB]`
  set serves a `[32, 13, 1 MiB]` or `[32, 10, 1 MiB]` rebuild through a
  view of each buffer's first bytes, so a server that alternates shapes
  settles on the largest;
* buffers are handed out DIRTY: whatever an earlier operation left in
  them. The owner overwrites what it reads;
* a set nobody has used for `IDLE_DROP_S` is dropped, so an idle server's
  resident memory returns to what it was;
* an operation that ends in an error never hands its set back: a device
  transfer may still be reading from it.
"""

from __future__ import annotations

import threading
from contextlib import contextmanager

import numpy as np

# seconds a kept set may lie unused before it is dropped: longer than the
# gap between the rebuilds of one repair round (a verb a few seconds
# apart), far shorter than the time between rounds
IDLE_DROP_S = 45.0


class BufferSet:
    """`count` flat host buffers of one size. `touched[i]` is how many of
    buffer i's first bytes earlier owners have written: what of it is
    warm."""

    def __init__(self, nbytes: int, count: int):
        self.nbytes = nbytes
        self.flat = [np.empty(nbytes, dtype=np.uint8) for _ in range(count)]
        self.touched = [0] * count

    def views(self, shape: tuple) -> "list[np.ndarray]":
        need = int(np.prod(shape))
        return [f[:need].reshape(shape) for f in self.flat]

    def fits(self, nbytes: int, count: int) -> bool:
        return self.nbytes >= nbytes and len(self.flat) == count


class KeptBuffers:
    """The holder of one `BufferSet` between operations (module doc)."""

    def __init__(self):
        self._lock = threading.Lock()
        self._kept: "BufferSet | None" = None
        self._timer: "threading.Timer | None" = None

    @contextmanager
    def lease(self, shape: tuple, count: int):
        """A `BufferSet` of `count` buffers that hold `shape`, owned by
        the caller for the `with` block: the kept one if it is free and
        fits, else a new one. Left without an error, the set becomes the
        kept one."""
        nbytes = int(np.prod(shape))
        with self._lock:
            held = self._kept
            # a free set goes either to this caller or away: one too
            # small would be replaced by the caller's own at the end
            # anyway, and need not stay resident beside it until then
            self._kept = None
            self._cancel_timer()
        if held is None or not held.fits(nbytes, count):
            held = BufferSet(nbytes, count)
        yield held
        with self._lock:
            self._kept = held
            self._cancel_timer()
            self._timer = threading.Timer(IDLE_DROP_S, self._drop_idle,
                                          args=(held,))
            self._timer.daemon = True
            self._timer.start()

    def _cancel_timer(self) -> None:
        if self._timer is not None:
            self._timer.cancel()
            self._timer = None

    def _drop_idle(self, held: BufferSet) -> None:
        with self._lock:
            # a set taken since, or handed back again, has its own timer
            if self._kept is held:
                self._kept = None
                self._timer = None

    def kept_bytes(self) -> int:
        """Bytes of the set kept right now (0: none, or it is in use)."""
        with self._lock:
            kept = self._kept
            return kept.nbytes * len(kept.flat) if kept is not None else 0


# the rebuild's set (ec/encoder.py:_rebuild_batched). The seal's feed
# makes its pipe's buffers new for every call still (ec/stream.py).
REBUILD = KeptBuffers()
