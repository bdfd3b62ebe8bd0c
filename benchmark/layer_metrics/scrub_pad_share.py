"""Share of the bytes dispatched to the device that are padding: 1 -
needle bytes verified over the `[B, L]` blocks' bytes, over the window's
`volume.scrub.finish` events. A count, not a time."""


def read(run):
    events = run.events("volume.scrub.finish")
    dispatched = sum(e["bytes_dispatched"] for e in events)
    if not dispatched:
        return None
    return 100.0 * (1.0 - sum(e["bytes_checked"] for e in events)
                    / dispatched)
