"""Share of the scrubs' own elapsed time in which A's sweep thread waited
on its walker process: the walker's start, its next block, and the
hand-overs and joins booked with them (`recv_s` of `volume.scrub.finish`).
None on a program whose events lack the field."""


def read(run):
    events = [e for e in run.events("volume.scrub.finish")
              if e.get("elapsed_s") and "recv_s" in e]
    if not events:
        return None
    return 100.0 * sum(e["recv_s"] for e in events) / sum(
        e["elapsed_s"] for e in events)
