"""Algorithmic rate of the fused sweep kernel, Gbit/s.

Sum over the window's launches of frames x n_bits x nodes, over the summed
device time of the trace's operations matching ``PATTERN`` (the
``pallas_call`` of ``net_sweep``, named after its jit).  The same work
whatever implements it.
"""

PATTERN = r"net_sweep"


def read(rec):
    tr = rec.device_trace
    if tr is None or not rec.launch_bits:
        return None
    seconds, n = tr.kernel(PATTERN)
    if not n or seconds <= 0:
        return None
    return rec.launch_bits / seconds / 1e9
