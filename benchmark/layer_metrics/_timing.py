"""The shell's `timing` lines, one per command of a verb's script, as the
program writes them to stderr (`shell/commands.py:timing_line`) and the
kinds keep them in a timed operation's `out`:

    timing <verb> total=<s> rpc=<s> <Method>=<s>/<calls> ...

A program that writes none (an earlier commit, `SWTPU_TRACE_SAMPLE=0`)
gives no lines, and the readers then return None."""

from __future__ import annotations

import re

_LINE = re.compile(r"^timing (\S+) total=([0-9.]+) rpc=([0-9.]+)(.*)$")
_METHOD = re.compile(r" (\w+)=([0-9.]+)/(\d+)")


def lines(out: str) -> "list[dict]":
    """[{verb, total, rpc, methods: {name: (seconds, calls)}}]."""
    found = []
    for text in out.splitlines():
        m = _LINE.match(text)
        if m:
            found.append({"verb": m.group(1), "total": float(m.group(2)),
                          "rpc": float(m.group(3)),
                          "methods": {k: (float(s), int(n)) for k, s, n
                                      in _METHOD.findall(m.group(4))}})
    return found


def verbs(run) -> "list[dict]":
    """The window's timed verbs: all operations of the first one's label."""
    return [op for op in run.ops if op["label"] == run.ops[0]["label"]
            and "out" in op] if run.ops else []


def method_share(run, method: str) -> "float | None":
    """Percent of the verbs' wall inside client RPCs of `method`."""
    ops = verbs(run)
    found = [ln for op in ops for ln in lines(op["out"])]
    if not found:
        return None
    inside = sum(ln["methods"].get(method, (0.0, 0))[0] for ln in found)
    return 100.0 * inside / sum(op["wall_s"] for op in ops)
