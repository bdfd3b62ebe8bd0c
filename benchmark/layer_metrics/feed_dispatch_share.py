"""Share of the encode pipeline's wall inside `coder.encode(buf)`: the
host-to-device copy of a `[32, d, 1 MiB]` batch and the program's launch
(`dispatch_s` of `ec.encode.finish`, the sum of the `swtpu/ec.dispatch`
stages). None where the events lack the field (a host coder's pipeline
has no dispatch or drain, an earlier commit no `finish_s`)."""


def read(run, field="dispatch_s"):
    events = [e for e in run.events("ec.encode.finish")
              if e.get("wall_s") and field in e]
    if not events:
        return None
    return 100.0 * sum(e[field] for e in events) / sum(e["wall_s"]
                                                       for e in events)
