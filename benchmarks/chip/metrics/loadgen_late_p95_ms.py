"""95th percentile of how late the load generator sent its requests
(send time minus due time), in milliseconds: a starved generator shows
here, not as a fast server."""
import numpy as np


def read(window):
    late = [(sent - due) * 1e3 for due, sent, *_ in window.loadgen or ()
            if sent is not None]
    return float(np.percentile(late, 95)) if late else None
