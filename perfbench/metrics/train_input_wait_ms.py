"""train_input_wait_ms: mean milliseconds per step that the window's loop
blocked in the session's batch source (host clock around the call)."""


def read(record):
    if record["kind"] != "train" or not record["steps"]:
        return None
    return 1e3 * record["input_wait_s"] / record["steps"]
