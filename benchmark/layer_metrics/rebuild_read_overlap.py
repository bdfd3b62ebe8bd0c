"""How many survivor loads a rebuild's `read` stage had going at once, on
average: the loads' own seconds (`read_busy_s` of `ec.rebuild.finish`,
summed over the tasks) over the stage's wall on the RPC's thread
(`read_s`). 1 for loads run one after another, at most d. None where the
program books no `read_busy_s`."""


def read(run):
    events = [e for e in run.events("ec.rebuild.finish")
              if e.get("read_s") and "read_busy_s" in e]
    if not events:
        return None
    return sum(e["read_busy_s"] for e in events) / sum(
        e["read_s"] for e in events)
