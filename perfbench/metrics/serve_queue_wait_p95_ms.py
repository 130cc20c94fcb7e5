"""serve_queue_wait_p95_ms: 95th percentile of the engine's queue-wait
reservoir (``ServeMetrics`` stage ``queue_wait``, submit to dequeue), over
the window's requests."""


def read(record):
    if record["kind"] != "serve" or record["queue_wait_p95_ms"] is None:
        return None
    return record["queue_wait_p95_ms"]
