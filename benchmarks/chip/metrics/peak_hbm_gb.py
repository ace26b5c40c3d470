"""The device's peak bytes in use (``memory_stats()``) after the window,
on the fullest chip, in GB."""


def read(run):
    if run.memory_peak_bytes is None:
        return None
    return run.memory_peak_bytes / 1e9
