"""Stage account: where ONE long operation (a seal, a rebuild, a scrub of
a volume, a shell verb) spent its wall time, by named stage.

    acct = StageAccount("ec")
    with acct.stage("fill"):            # two perf_counter reads
        ...
    with acct.stage("dispatch", batch=3):
        fut = coder.encode(buf)
    acct.add("write_block", waited)     # seconds measured elsewhere
    acct.fields()   # {"fill_s": .., "dispatch_s": .., "write_block_s": ..}

Every stage adds its seconds into a sum and one into a count
(`seconds(name)`, `count(name)`). Stages are EXCLUSIVE: time spent in a nested stage, or
`add`ed while a stage is open, is taken out of the enclosing stage, so
the sums of one account partition the operation and never exceed its
wall.

In a process that already loaded jax (the chip-owning volume server) a
stage is also a `jax.profiler.TraceAnnotation` named
`swtpu/<op>.<stage>` carrying the stage's keyword arguments: with a
profiler session live (`/debug/jax-profiler`, the benchmark's traced
runs) the interval lands in the profiler's trace, on the same clock as
the device's operations; with none it is a flag test. jax is NEVER
imported for it — master, `-coder native|numpy` volume servers, filer
and shell stay jax-free.

Stage intervals do not go into the /debug/traces ring (a scrub would
evict every request trace within a minute): `publish` sets the sums as
attrs on the operation's own span, and `fields` feeds its finish event.
The granularity is the batch or block — on the order of 100 stages a
second at most — never the needle or a served request.
"""

from __future__ import annotations

import contextvars
import sys
import threading
import time

ANNOTATION_PREFIX = "swtpu/"

# the account that client RPCs are booked onto as they end (utils/rpc.py:
# method -> seconds/calls): the shell sets one for the length of a command
RPC_ACCOUNT: "contextvars.ContextVar[StageAccount | None]" = \
    contextvars.ContextVar("swtpu_rpc_account", default=None)


def _annotation(name: str, args: dict):
    """A TraceAnnotation if this process has jax loaded, else None."""
    jax = sys.modules.get("jax")
    if jax is None:
        return None
    profiler = getattr(jax, "profiler", None)
    if profiler is None:  # jax is still half-way through its own import
        return None
    return profiler.TraceAnnotation(name, **args)


class _Stage:
    __slots__ = ("acct", "name", "t0", "inner", "ann")

    def __init__(self, acct: "StageAccount", name: str, args: dict):
        self.acct = acct
        self.name = name
        self.inner = 0.0   # seconds claimed by nested stages and adds
        self.ann = _annotation(f"{ANNOTATION_PREFIX}{acct.op}.{name}", args)

    def __enter__(self) -> "_Stage":
        if self.ann is not None:
            self.ann.__enter__()
        self.acct._open().append(self)
        self.t0 = time.perf_counter()
        return self

    def __exit__(self, exc_type, exc, tb) -> bool:
        total = time.perf_counter() - self.t0
        stack = self.acct._open()
        stack.pop()
        self.acct._book(self.name, max(0.0, total - self.inner), 1)
        if stack:
            stack[-1].inner += total
        if self.ann is not None:
            self.ann.__exit__(exc_type, exc, tb)
        return False


class StageAccount:
    """Per-operation sums of exclusive stage seconds and stage counts.

    One thread drives an operation's stages; `add` may come from any
    thread (the shell's RPC tally), so the books are locked and the
    open-stage stack is per thread."""

    def __init__(self, op: str, stages: "tuple[str, ...]" = ()):
        self.op = op
        self._sums: "dict[str, float]" = {s: 0.0 for s in stages}
        self._counts: "dict[str, int]" = {s: 0 for s in stages}
        self._lock = threading.Lock()
        self._local = threading.local()

    def _open(self) -> "list[_Stage]":
        try:
            return self._local.stack
        except AttributeError:
            self._local.stack = []
            return self._local.stack

    def _book(self, name: str, seconds: float, n: int) -> None:
        with self._lock:
            self._sums[name] = self._sums.get(name, 0.0) + seconds
            self._counts[name] = self._counts.get(name, 0) + n

    def stage(self, name: str, **args) -> _Stage:
        """Context manager timing one interval of stage `name`; `args`
        (numbers, short strings) ride the profiler annotation."""
        return _Stage(self, name, args)

    def add(self, name: str, seconds: float, n: int = 1) -> None:
        """Book `seconds` measured elsewhere; inside an open stage of the
        calling thread they are taken out of that stage."""
        self._book(name, seconds, n)
        stack = self._open()
        if stack:
            stack[-1].inner += seconds

    def timed(self, name: str, fn):
        """`fn` wrapped so that each call runs as one stage `name`."""
        def call(*a, **kw):
            with self.stage(name):
                return fn(*a, **kw)
        return call

    def seconds(self, name: str) -> float:
        with self._lock:
            return self._sums.get(name, 0.0)

    def count(self, name: str) -> int:
        with self._lock:
            return self._counts.get(name, 0)

    def names(self) -> "list[str]":
        """Stage names, most seconds first."""
        with self._lock:
            return sorted(self._sums, key=lambda k: -self._sums[k])

    def fields(self, ndigits: int = 4) -> dict:
        """`{<stage>_s: seconds}` for a finish event or a stats dict."""
        with self._lock:
            return {f"{name}_s": round(secs, ndigits)
                    for name, secs in self._sums.items()}

    def publish(self, span) -> None:
        """The sums as attrs of the operation's own span."""
        for key, val in self.fields().items():
            span.set_attr(key, val)
