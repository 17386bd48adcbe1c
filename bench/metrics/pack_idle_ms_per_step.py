"""Device idle time inside the program's ``fleet.pack`` spans (the host
packing before each launch of ``GPFleetServer.step``, their union), mean
over the chips, in milliseconds per server step."""
import program_spans


def read(rec):
    return program_spans.idle_ms_per_step(rec, ("fleet.pack",))
