"""request_p95_ms: 95th percentile, over every viewport request due in
the window, of the milliseconds from when it was due (open loop) to
when its last tile was delivered (host clock)."""

import numpy as np


def read(run):
    if run.system != "tile_server" or not run.latencies_ms:
        return None
    return float(np.percentile(np.asarray(run.latencies_ms), 95))
