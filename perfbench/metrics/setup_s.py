"""setup_s: seconds from process start to the window's start (data, weights,
session, compile-cache load, the checked first steps, warm-up)."""


def read(record):
    return record["setup_s"]
