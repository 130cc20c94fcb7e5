"""device_idle_share.train: percent of the traced training window in which
no operation ran on the device (mean over the chips in use)."""
from perfbench.devtrace import idle_share


def read(record):
    return idle_share(record, "train")
