"""Share of the ingest window in which no operation ran on the device,
in percent, from the profiler trace."""


def read(window):
    d = window.device
    return 100.0 * (1.0 - d.busy_s / d.window_s) if d else None
