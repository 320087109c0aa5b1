"""Resilience subsystem: retry/backoff/deadline policy, graceful tier
degradation, and deterministic fault injection.

The reference treats every failure as terminal (its V4 ships with known
bugs, V5 is a 0-byte stub). This package is the production-stack answer (in the spirit of Varuna's preemption-tolerant
scheduling and CheckFreq-style recovery):

- ``policy``  — ``RetryPolicy`` (exponential backoff + deterministic
  jitter), ``Deadline`` propagation, per-attempt ``FaultLog`` records, the
  ``retry_call`` combinator, and the ``Degrader`` that walks an ordered
  fallback chain emitting structured ``DEGRADED(from, to, cause)`` events
  instead of crashing.
- ``chaos``   — seed-driven fault injectors (collective failure, device
  loss, kernel-compile failure, ssh/rsync transients,
  sdc bit-flips, nan_loss) enabled via the ``CHAOS_SPEC`` environment
  variable so every recovery path is exercisable on CPU in tier-1 tests.
- ``sentinel`` — step-level silent-data-corruption detection: NaN/Inf and
  norm-spike screening, cross-replica divergence checksums for the
  dp/sp/tp shard_map paths, periodic golden-oracle spot checks, and the
  structured ``SDC`` fault class the quarantine/rollback policy consumes.
- ``journal`` — append-only crash-consistent run journal (fsync'd jsonl
  appends + atomic tmp-write/rename artifact writes) giving idempotent
  resume to harness sweeps (``--resume``) and the train CLI
  (checkpoint-every-N + last-good rollback).
- ``supervisor`` — the elastic layer over the in-graph sentinel: forwards
  compiled with per-stage digest taps inside their shard_map bodies, a
  trip (``stage_digest``/``shard_divergence``/``device_loss``) re-plans
  down a degradation ladder (fewer shards → replicated → reference) and
  replays the batch, journaling every transition (run ``--supervise``,
  harness ``SupervisorMsg`` column).

Wired through ``harness`` (DEGRADED triage + bounded timeout re-capture +
journaled ``--resume``), ``parallel.deploy`` (retrying transports + quorum
degradation + journaled host states), ``run``
(``--max-retries/--fallback-chain/--deadline-s``), ``train``
(``--checkpoint-every`` + sentinel rollback). See docs/RESILIENCE.md.

``sentinel`` and ``supervisor`` import jax and are therefore NOT
re-exported here — the stdlib-only consumers (harness, deploy) import
this package without paying a jax import; training/serving
callers import ``resilience.sentinel`` / ``resilience.supervisor``
directly.
"""

from .chaos import (
    CHAOS_ENV,
    KNOWN_SITES,
    ChaosInjector,
    ChaosSpec,
    InjectedFault,
    active,
)
from .journal import (
    JOURNAL_NAME,
    Journal,
    atomic_open,
    atomic_write_bytes,
    atomic_write_text,
    atomic_writer,
)
from .policy import (
    DEGRADED,
    Attempt,
    Deadline,
    DegradationExhausted,
    DegradedEvent,
    Degrader,
    FaultLog,
    RetryPolicy,
    retry_call,
    tier_fallback_chain,
)

__all__ = [
    "CHAOS_ENV",
    "KNOWN_SITES",
    "JOURNAL_NAME",
    "Journal",
    "atomic_open",
    "atomic_write_bytes",
    "atomic_write_text",
    "atomic_writer",
    "ChaosInjector",
    "ChaosSpec",
    "InjectedFault",
    "active",
    "DEGRADED",
    "Attempt",
    "Deadline",
    "DegradationExhausted",
    "DegradedEvent",
    "Degrader",
    "FaultLog",
    "RetryPolicy",
    "retry_call",
    "tier_fallback_chain",
]
