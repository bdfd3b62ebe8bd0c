"""Share of the scrubs' own elapsed time zero-filling and copying needles
into `[B, L]` blocks (`pack_s` of `volume.scrub.finish`)."""
from benchmark.layer_metrics import scrub_walk_share


def read(run):
    return scrub_walk_share.read(run, "pack_s")
