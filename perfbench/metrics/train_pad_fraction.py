"""train_pad_fraction: percent of the padded edge slots in the window's
batches that carry no real edge (one minus the mean of the edge mask, the
arithmetic of the program's ``data/bucketing.py::pad_fraction``, weighted
by each batch's slots)."""


def read(record):
    if record["kind"] != "train" or not record["edge_slots"]:
        return None
    return 100.0 * (1.0 - record["edges"] / record["edge_slots"])
