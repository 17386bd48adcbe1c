"""CG iterations of the state's re-solves (``GPGState.data.cg_iters``
after each extend), summed over the window, per extend."""


def read(rec):
    n = rec["ops"].get("extend", 0)
    return rec["cg_iters"] / n if n and rec["cg_iters"] is not None else None
