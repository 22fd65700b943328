"""device_idle_pct: share of the window in which no operation runs on the device.

One minus the union of the device's operation intervals over the traced
window; on several chips, the device idle the longest.  Moves
``tokens_per_s``.
"""


def read(r):
    if not r.trace.ops:
        return None
    return 100.0 * (1.0 - min(r.trace.busy_share(d) for d in r.trace.ops))
