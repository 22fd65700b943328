"""peak_hbm_gb: ``peak_bytes_in_use`` after the window, on the fullest device.

The memory that caps how many slots a chip holds.  Moves ``tokens_per_s``.
"""


def read(r):
    return r.memory_peak_bytes / 1e9 if r.memory_peak_bytes else None
