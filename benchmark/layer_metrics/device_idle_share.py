"""Share of the traced window in which no operation ran on the device
(xplane.py: 1 - union of the device operations' intervals over the
window). One entry per cell, `<cell>_device_idle_share`."""


def read(run):
    if not run.traced or not run.trace_window:
        return None
    window = run.trace_window[1] - run.trace_window[0]
    return 100.0 * (1.0 - run.traced["busy_s"] / window)
