"""Least work of one extend into a full window of N rows in D dimensions.

Any implementation reads the window's inputs X and gradients G once (the
new row included), writes the new representer weights Z (every row moves
when the window changes) and stores the new row (x, g):

    bytes = itemsize * D * (3 N + 2)

Z = A^-1 (G + ...) is at least one (N, N) x (N, D) product:

    flops = 2 N^2 D

Neither depends on how many passes or kernels the program takes.
"""


def count(cfg: dict, *, n: int, d: int, itemsize: int, **_) -> dict:
    return {"bytes": itemsize * d * (3 * n + 2), "flops": 2 * n * n * d}
