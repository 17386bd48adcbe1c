"""Seconds the program spent building executables in the window (XLA
compiles and loads from the persistent cache, by its compile counter
``repro.obs.trace``, all spans together), in milliseconds per
closed-loop step."""
import program_spans


def read(rec):
    sec = program_spans.compile_seconds(rec)
    if sec is None or not rec["steps"]:
        return None
    return 1e3 * sec / rec["steps"]
