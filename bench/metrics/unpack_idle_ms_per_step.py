"""Device idle time inside the program's ``fleet.unpack`` spans (the
per-tenant slicing and assignment of a launch's results, their union),
mean over the chips, in milliseconds per server step."""
import program_spans


def read(rec):
    return program_spans.idle_ms_per_step(rec, ("fleet.unpack",))
