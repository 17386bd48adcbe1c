"""Host time of a server step: each ``server.step`` annotation less the
device busy time inside it (mean over the chips), as the mean over the
window's steps, in milliseconds."""
import tracing


def read(rec):
    tr = rec["trace"]
    if not tr or not tr["devices"]:
        return None
    steps = tracing.busy_in(tr, "server.step")
    if not steps:
        return None
    return 1e3 * sum(span - busy for span, busy in steps) / len(steps)
