"""The whole window over the closed-loop steps it completed."""


def read(rec):
    return rec["window_s"] / rec["steps"]
