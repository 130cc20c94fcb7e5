"""serve_structures_per_s: requests answered (not failed) before the window
closed, over the window's length."""


def read(record):
    if record["kind"] != "serve":
        return None
    return record["answered_in_window"] / record["window_s"]
