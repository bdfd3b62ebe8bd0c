"""Share of the encode pipeline's wall spent filling host batches."""


def read(run, field="fill_s"):
    events = [e for e in run.events("ec.encode.finish") if e.get("wall_s")]
    if not events:
        return None
    return 100.0 * sum(e[field] for e in events) / sum(e["wall_s"]
                                                       for e in events)
