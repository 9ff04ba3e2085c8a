"""Deterministic fault injection + preemption notices.

The fault plane that makes every recovery path in this repo testable:
a seeded, env-driven plan (``RAYDP_TPU_FAULT_PLAN``) describes exactly
which process dies, stalls, or loses an RPC, and when — so tier-1 tests
exercise rank death, host preemption, dropped control-plane traffic,
and heartbeat stalls deterministically instead of by hope. See
``doc/fault_tolerance.md`` for the grammar and the supervisor semantics
built on top.

Hook surface (all no-ops when no plan is configured):

* :func:`on_train_step` — estimator step boundary (kill / preempt).
* :func:`on_task` — ETL worker task boundary (kill).
* :func:`on_rpc` — RPC client send (delay / drop one call).
* :func:`on_heartbeat` — heartbeat loops (skip beats).
* :func:`on_serve_request` — serving replica request boundary
  (serve_kill / latency).

Preemption notices are first-class and independent of the plan: a real
SIGTERM lands in the same :func:`preemption_requested` flag the
injected ``preempt`` clause sets, so the estimator's drain-and-
emergency-checkpoint path is identical for simulated and real
preemptions.
"""
from raydp_tpu.fault.plan import (
    FAULT_PLAN_ENV,
    FAULT_SEED_ENV,
    FaultClause,
    FaultPlanError,
    parse_plan,
)
from raydp_tpu.fault.inject import (
    PREEMPT_GRACE_ENV,
    PreemptionError,
    active,
    ambient_rank,
    ambient_replica,
    install_sigterm_drain,
    mark_drained,
    on_heartbeat,
    on_rpc,
    on_serve_request,
    on_task,
    on_train_step,
    preemption_requested,
    request_preemption,
    reset_for_tests,
)

__all__ = [
    "FAULT_PLAN_ENV",
    "FAULT_SEED_ENV",
    "PREEMPT_GRACE_ENV",
    "FaultClause",
    "FaultPlanError",
    "PreemptionError",
    "active",
    "ambient_rank",
    "ambient_replica",
    "install_sigterm_drain",
    "mark_drained",
    "on_heartbeat",
    "on_rpc",
    "on_serve_request",
    "on_task",
    "on_train_step",
    "parse_plan",
    "preemption_requested",
    "request_preemption",
    "reset_for_tests",
]
