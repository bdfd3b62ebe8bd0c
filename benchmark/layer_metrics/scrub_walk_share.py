"""Share of the scrubs' own elapsed time walking records and reading
bodies between two dispatches (`walk_s` of the window's
`volume.scrub.finish` events: whole sweeps, not the traced slice)."""


def read(run, field="walk_s"):
    events = [e for e in run.events("volume.scrub.finish")
              if e.get("elapsed_s")]
    if not events:
        return None
    return 100.0 * sum(e[field] for e in events) / sum(
        e["elapsed_s"] for e in events)
