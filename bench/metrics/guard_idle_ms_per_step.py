"""Device idle time inside the program's guardrail spans
(``guard.check_finite`` and ``guard.after_mutation`` of
``GPGState.extend``, their union), mean over the chips, in milliseconds
per closed-loop step."""
import program_spans


def read(rec):
    return program_spans.idle_ms_per_step(
        rec, ("guard.check_finite", "guard.after_mutation"))
