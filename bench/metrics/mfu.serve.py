"""The whole serving loop's share of the chip's roofline: least time of
every request the window completed (bench/counts, per chip) over the
window's wall time, %."""


def read(rec):
    least = sum(rec["least_s"].values())
    return 100.0 * least / rec["window_s"] if least else None
