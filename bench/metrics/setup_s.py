"""Seconds from process start to the first timed request: loading, making
the data, filling the window, warming the cell's shapes, compiling."""


def read(rec):
    return rec["setup_s"]
