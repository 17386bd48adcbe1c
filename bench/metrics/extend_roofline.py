"""Least time of the window's extends (bench/counts/extend.py, per chip) over
the device busy time inside the benchmark's "extend" annotations, every
device op included, mean over the chips, %."""
import tracing


def read(rec):
    tr, least = rec["trace"], rec["least_s"].get("extend")
    if not tr or not least:
        return None
    busy = sum(b for _, b in tracing.busy_in(tr, "extend"))
    return 100.0 * least / busy if busy > 0 else None
