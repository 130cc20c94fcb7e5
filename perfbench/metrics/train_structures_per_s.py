"""train_structures_per_s: real structures whose step completed in the
window, over the window's wall time (it ends on the last state's
``block_until_ready``)."""


def read(record):
    if record["kind"] != "train":
        return None
    return record["structures"] / record["window_s"]
