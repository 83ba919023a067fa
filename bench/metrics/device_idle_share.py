"""device_idle_share: the share of the traced window a device ran nothing.

1 - (union of the device's op intervals) / (traced window), averaged over
the cell's devices, from the profiler trace.
"""


def read(obs):
    if obs.trace is None:
        return None
    return obs.trace["idle_share"]
