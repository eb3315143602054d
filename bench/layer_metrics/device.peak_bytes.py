"""Device: peak bytes in use on the fullest chip after the window, as
the runtime reports it (memory_stats)."""


def read(run):
    return run.peak_bytes
