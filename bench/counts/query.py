"""Least work of one query of Q points against a window of N rows.

Any implementation reads the query points Xq, the window's inputs X and
its representer weights Z once, and writes the Q values and the (Q, D)
gradients:

    bytes = itemsize * (D * (2 Q + 2 N) + Q)

The means need q . x_j and q . z_j for every pair, and the gradients one
(Q, N) x (N, D) product with each of Z and X:

    flops = 8 Q N D
"""


def count(cfg: dict, *, n: int, d: int, q: int, itemsize: int, **_) -> dict:
    return {"bytes": itemsize * (d * (2 * q + 2 * n) + q),
            "flops": 8 * q * n * d}
