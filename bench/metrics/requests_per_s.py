"""Requests completed without error over the window."""


def read(rec):
    return rec["completed"] / rec["window_s"]
