"""Share of the rebuild RPCs' duration loading survivors into the batch
(local preads or remote ranged fetches: `read_s` of
`ec.rebuild.finish`, the sum of the `swtpu/rebuild.read` stages). None
where the program has no such field."""


def read(run, field="read_s"):
    events = [e for e in run.events("ec.rebuild.finish")
              if e.get("duration_ms") and field in e]
    if not events:
        return None
    return 100.0 * sum(e[field] for e in events) / sum(
        e["duration_ms"] / 1e3 for e in events)
