"""serve_p95_ms: 95th percentile of the same latencies as serve_p50_ms."""
import numpy as np


def read(record):
    if record["kind"] != "serve":
        return None
    return float(np.percentile(record["latency_ms"], 95))
