"""serve_p50_ms: median latency of every request due in the window, each
timed from when it was due to when its result was set; a failed request
counts as infinite."""
import numpy as np


def read(record):
    if record["kind"] != "serve":
        return None
    return float(np.percentile(record["latency_ms"], 50))
