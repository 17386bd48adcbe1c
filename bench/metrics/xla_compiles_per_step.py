"""Executables the process built inside the window (XLA compiles and loads
from the persistent cache, ``jax.monitoring``), per closed-loop step.
Counts the state ops' eager retraces."""


def read(rec):
    return rec["compiles"] / rec["steps"]
