"""Percent of the window's timed verbs that ran in the master's resident
shell executor: those whose stderr says `shell via=executor`, of those
with a `process` line (`_process.verbs`). A verb that ran in its own
process says `shell via=process`. None where no verb writes a `shell
via=` line (a program without the executor). One entry per cell,
`<cell>_verb_executor_share`."""
import re

from benchmark.layer_metrics import _process

_VIA = re.compile(r"^shell via=(\w+)$", re.M)


def read(run):
    vias = [_VIA.search(op["out"]) for op, _ in _process.verbs(run)]
    if not any(vias):
        return None
    return 100.0 * sum(1 for m in vias
                       if m and m.group(1) == "executor") / len(vias)
