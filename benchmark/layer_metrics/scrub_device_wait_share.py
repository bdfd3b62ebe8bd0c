"""Share of the scrubs' own elapsed time inside the jitted CRC call
through `np.asarray`: host-to-device, the scan, device-to-host
(`device_s` of `volume.scrub.finish`)."""
from benchmark.layer_metrics import scrub_walk_share


def read(run):
    return scrub_walk_share.read(run, "device_s")
