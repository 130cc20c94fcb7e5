"""serve_batch_fill: real rows over padded rows of the batches the engine
ran in the window (``ServeMetrics`` counters batch_real / batch_slots), in
percent."""


def read(record):
    if record["kind"] != "serve" or not record["batch_slots"]:
        return None
    return 100.0 * record["batch_real"] / record["batch_slots"]
