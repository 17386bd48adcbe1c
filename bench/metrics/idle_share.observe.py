"""Share of the traced window in which no operation ran on the device
(1 - union of the device-op intervals / window), mean over the chips, %."""
import tracing


def read(rec):
    tr = rec["trace"]
    if not tr or not tr["devices"]:
        return None
    return 100.0 * (1.0 - tracing.busy_s(tr) / tracing.window_s(tr))
