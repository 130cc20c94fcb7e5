"""device_idle_share.serve: percent of the traced serving window in which
no operation ran on the device."""
from perfbench.devtrace import idle_share


def read(record):
    return idle_share(record, "serve")
