"""Percent of the verbs' wall that is not the server-side work the
program's own spans account for: shell start, lock, topology reads, RPC
hops, settle polls and, for a seal, the spread to B. The span is the
finish event of the verb's label, or the scrub's own elapsed time as the
verb prints it. One entry per cell, `<cell>_verb_overhead_share`."""

# label of the timed operation -> (A's event, field, seconds per unit)
SPANS = {"seal": ("ec.encode.finish", "wall_s", 1.0),
         "repair": ("ec.rebuild.finish", "duration_ms", 1e-3)}


def read(run):
    if not run.ops:
        return None
    label = run.ops[0]["label"]
    done = [op for op in run.ops if op["label"] == label]
    if label in SPANS:
        event, field, unit = SPANS[label]
        inside = sum(e[field] for e in run.events(event)) * unit
    elif "volumes" in done[0]:
        inside = sum(v["elapsed_s"] for op in done
                     for v in op["volumes"].values())
    else:
        return None
    return 100.0 * (1.0 - inside / sum(op["wall_s"] for op in done))
