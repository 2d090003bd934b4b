"""paddle_tpu.resilience — fault-tolerant training runtime.

Four pillars (ISSUE 4 tentpole):

1. **Anomaly guard** (`guard.py`) — a cheap on-device all-finite
   reduction fused into the compiled train step; policy ``raise`` /
   ``skip_step`` / ``rollback`` (restore newest complete checkpoint +
   replay the data cursor).  Wired through Executor.run and the AMP
   loss-scale path.
2. **Retry with jittered exponential backoff** (`retry.py`) around
   transient runtime failures, classified by the error-taxonomy table
   (`taxonomy.py`) so programming errors still fail fast.
3. **Preemption-safe training** (`preempt.py`) — SIGTERM/SIGINT raise
   a flag; the training loop force-checkpoints at the next step
   boundary and exits cleanly; `train_from_dataset(auto_resume=True)`
   restores the latest checkpoint and skips consumed batches.
4. **Deterministic fault injection** (`faultinject.py`) — NaN feeds at
   step N, synthetic transient errors, kill-between-array-write-and-
   marker during checkpoint saves; drives `tests/test_resilience.py`.

Plus the fleet-level pillar (ISSUE 11): the **elastic runtime**
(`elastic.py`) — topology-change resharding
(`CheckpointManager.restore_resharded`), rank join/leave through an
`ElasticCoordinator` (heartbeat liveness, bounded-timeout boundary
sync, leave/join intents, shrink/grow transitions gated into
/healthz), and skew-driven policies (`ElasticPolicy`:
warn | rebalance | evict off `monitor.fleet_skew()`), exercised by the
kill/reshard/rejoin arc of `tests/test_elastic.py` (`kill_and_rejoin`,
two real processes).

All recovery events land as `resilience.*` monitor counters/gauges
(visible in `monitor.snapshot()` and the merged Chrome trace), and
checkpoint save/restore wall time is recorded by checkpoint.py.

Usage::

    from paddle_tpu import resilience
    from paddle_tpu.checkpoint import CheckpointManager

    mgr = CheckpointManager("/ckpt", save_interval_steps=50)
    resilience.enable_anomaly_guard(policy="rollback", manager=mgr)
    resilience.enable_retry(resilience.RetryPolicy(max_retries=5))
    with resilience.PreemptionHandler():
        exe.train_from_dataset(prog, dataset, checkpoint=mgr,
                               auto_resume=True)
"""

from .breaker import (CircuitBreaker, CircuitOpenError)      # noqa: F401
from . import elastic                                        # noqa: F401
from .elastic import (ElasticCoordinator, ElasticPolicy,     # noqa: F401
                      TopologyChanged, active_coordinator)
from .faultinject import (FaultPlan, InjectedCrash,          # noqa: F401
                          InjectedTransientError, plan_scope)
from . import faultinject                                    # noqa: F401
from .guard import (AnomalyError, AnomalyGuard,              # noqa: F401
                    RollbackPerformed, active_guard, all_finite,
                    anomaly_guard, disable_anomaly_guard,
                    enable_anomaly_guard)
from .preempt import (PreemptionHandler, clear_drain,        # noqa: F401
                      clear_preemption, drain_requested,
                      preemption_requested, request_drain,
                      request_preemption)
from .retry import RetriesExhausted, RetryPolicy, call_with_retry
from .taxonomy import (DEADLINE, FATAL, PREEMPTION, TRANSIENT, TAXONOMY,
                       DeadlineExceeded, classify, is_deadline, is_oom,
                       is_preemption, is_transient)

__all__ = [
    # guard
    "AnomalyGuard", "AnomalyError", "RollbackPerformed",
    "enable_anomaly_guard", "disable_anomaly_guard", "anomaly_guard",
    "active_guard", "all_finite", "guarded_step",
    # retry
    "RetryPolicy", "RetriesExhausted", "call_with_retry",
    "enable_retry", "disable_retry", "active_retry",
    # breaker
    "CircuitBreaker", "CircuitOpenError",
    # elastic fleet (ISSUE 11)
    "elastic", "ElasticCoordinator", "ElasticPolicy", "TopologyChanged",
    "active_coordinator",
    # taxonomy
    "classify", "is_transient", "is_oom", "is_deadline", "is_preemption",
    "DeadlineExceeded", "TRANSIENT", "FATAL", "DEADLINE", "PREEMPTION",
    "TAXONOMY",
    # preemption / drain
    "PreemptionHandler", "preemption_requested", "request_preemption",
    "clear_preemption", "drain_requested", "request_drain", "clear_drain",
    # fault injection
    "faultinject", "FaultPlan", "plan_scope", "InjectedTransientError",
    "InjectedCrash",
]

_retry_policy = None


def enable_retry(policy=None):
    """Install a process-wide retry policy: Executor.run wraps each
    compiled dispatch in call_with_retry while one is active.

    Caveat: a failure that strikes MID-execution may have consumed
    donated input buffers, in which case the retry itself fails fast
    on deleted arrays — the net effect is still a clean error, never
    silent corruption.  Failures before execution starts (allocation
    RESOURCE_EXHAUSTED, rendezvous errors, injected faults) retry
    cleanly."""
    global _retry_policy
    _retry_policy = policy or RetryPolicy()
    return _retry_policy


def disable_retry():
    global _retry_policy
    _retry_policy = None


def active_retry():
    return _retry_policy


def _mon():
    from .. import monitor

    return monitor


def guarded_step(step, guard=None, template_state=None):
    """Wrap a functional train step (the `make_amp_train_step` /
    `make_train_step` family: ``step(state, *batch) -> (state, loss,
    finite)`` or ``(state, loss)``) with host-side guard-policy
    handling — the eager-mode twin of the executor's fused check.

    AMP steps already compute the `finite` flag from the loss-scale
    path; steps without one get the finiteness of their loss checked.
    Policy ``rollback`` restores through guard.manager and raises
    RollbackPerformed with `.state` set to the restored pytree (the
    caller rewinds its batch cursor to `.step` and continues from
    `.state`)."""
    import numpy as np

    g = guard or active_guard()
    if g is None:
        raise ValueError("no anomaly guard active (pass guard= or "
                         "enable_anomaly_guard first)")

    def wrapped(state, *batch):
        out = step(state, *batch)
        if len(out) == 3:
            new_state, loss, finite = out
        else:
            new_state, loss = out
            finite = np.isfinite(np.asarray(loss)).all()
        ok = bool(np.asarray(finite))
        mon = _mon()
        if ok:
            g.note_ok()
            return new_state, loss, True
        if mon.is_enabled():
            mon.counter("resilience.anomaly_steps").add(1)
        g.note_anomaly()
        if g.policy == "raise":
            raise AnomalyError("guarded step produced non-finite "
                               "loss/gradients (policy=raise)")
        if g.policy == "skip_step":
            if mon.is_enabled():
                mon.counter("resilience.skipped_steps").add(1)
            # AMP steps already selected the old state on overflow;
            # plain steps committed a poisoned update — hand back the
            # INPUT state so the skip really skips
            return (new_state if len(out) == 3 else state), loss, False
        # rollback
        g.note_rollback()
        if mon.is_enabled():
            mon.counter("resilience.rollbacks").add(1)
        template = template_state if template_state is not None \
            else (new_state if len(out) == 3 else state)
        restored, ck_step = g.manager.restore_latest(template)
        exc = RollbackPerformed(ck_step)
        exc.state = restored
        raise exc

    return wrapped
