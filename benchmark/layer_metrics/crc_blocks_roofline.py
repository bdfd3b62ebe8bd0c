"""The CRC32C scan (`jit__lambda`) against its roofline, block by block:
the needed bytes are the `needed` of the `swtpu/scrub.device` stages
recorded whole inside the trace, and the device time is that of the
program runs that began inside those stages — no share of a sweep is
taken by time. Padding of the `[8 MiB / L, L]` blocks is the kernel's
cost, not its work."""
from benchmark import host_spans, roofline


def read(run):
    reduced = host_spans.of(run)
    if not reduced:
        return None
    stages = [(s, s + d, stats.get("needed", 0))
              for name, s, d, stats in reduced["spans"]
              if name == "swtpu/scrub.device"]
    needed = seconds = 0.0
    for s0, s1, nbytes in stages:
        runs = [d for name, s, d in reduced["programs"]
                if name.startswith("jit__lambda") and s0 <= s and s + d <= s1]
        if runs:
            needed += nbytes
            seconds += sum(runs) / 1e9
    if not needed or not seconds:
        return None
    return roofline.share(*roofline.crc_ops_bytes(1, needed), seconds,
                          run.device["kind"])[0]
