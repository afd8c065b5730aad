"""Mean time, over the ``query/execute`` spans of the window, in which the
first chip ran no operation while the span was open, in milliseconds:
each span's length less its intersection with the union of the device's
operation intervals.  It is the host's share of each query."""
import numpy as np

from chipbench import tracing


def read(window):
    d = window.device
    spans = window.spans_named("query/execute")
    if not d or not d.chips or not spans:
        return None
    busy = tracing._union(tracing._clip(d.chips[0]["ops"], d.lo, d.hi))
    idle = []
    for s in spans:
        lo = window.epoch + s["ts"] / 1e6 + d.offset
        hi = min(lo + s["dur"] / 1e6, d.hi)
        # busy intervals are sorted and disjoint: only those that start
        # before the span ends can overlap it
        iv = busy[:np.searchsorted(busy[:, 0], hi)]
        covered = np.clip(np.minimum(iv[:, 1], hi) - np.maximum(iv[:, 0], lo),
                          0.0, None).sum()
        idle.append(max(hi - lo, 0.0) - covered)
    return float(np.mean(idle)) * 1e3
