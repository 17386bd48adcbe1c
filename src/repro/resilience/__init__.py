"""repro.resilience — fault tolerance for the serving stack (DESIGN.md
sec. 17).

  errors.py      typed failure taxonomy (admission rejects, degraded
                 queries, shed/deadline/retry/quarantine, corruption)
  guardrails.py  numerical guardrails outside the guarded programs:
                 non-finite admission (on the device for device
                 arrays), the jitter-escalation ladder, the
                 CG-divergence watchdog predicate, the bf16-drift
                 trip-wire — zero jaxpr cost by construction
  snapshot.py    snapshot/restore of all three state flavors through the
                 two-phase CheckpointManager (elastic fleet repack,
                 cross-mesh sharded restore, corruption fallback)
  journal.py     append-only op journal + bit-exact replay since the
                 last snapshot
  chaos.py       deterministic seed-replayable fault injector extending
                 runtime.recovery.FailureInjector to the serve path

The recovery invariant the tests enforce: snapshot + journal replay
reproduces an uninterrupted run BIT-IDENTICALLY (single and fleet
flavors; sharded on the same mesh), and every chaos-injected fault is
detected and recovered with matching ``resilience.*`` telemetry and
zero recompiles.
"""
from repro.resilience import chaos, errors, guardrails, journal, snapshot
from repro.resilience.chaos import FAULT_KINDS, ChaosInjector
from repro.resilience.errors import (CheckpointCorruptionError,
                                     DeadlineExceededError,
                                     JournalCorruptionError,
                                     NonFiniteObservationError,
                                     QueueOverloadError, ResilienceError,
                                     RetryExhaustedError, ShedResponse,
                                     TenantQuarantinedError,
                                     UnsupportedQueryError)
from repro.resilience.guardrails import (bf16_tripwire, check_finite,
                                         enabled, factor_ok,
                                         heal_factorization,
                                         record_recovery, set_enabled,
                                         use_guardrails)
from repro.resilience.journal import Journal, replay_fleet, replay_single
from repro.resilience.snapshot import restore, snapshot as take_snapshot

__all__ = [
    "chaos", "errors", "guardrails", "journal", "snapshot",
    "ChaosInjector", "FAULT_KINDS",
    "ResilienceError", "NonFiniteObservationError", "UnsupportedQueryError",
    "DeadlineExceededError", "QueueOverloadError", "RetryExhaustedError",
    "TenantQuarantinedError", "JournalCorruptionError",
    "CheckpointCorruptionError", "ShedResponse",
    "enabled", "set_enabled", "use_guardrails", "check_finite",
    "factor_ok", "heal_factorization", "bf16_tripwire", "record_recovery",
    "Journal", "replay_single", "replay_fleet",
    "take_snapshot", "restore",
]
