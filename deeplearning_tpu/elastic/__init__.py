"""Elastic runs: survive preemption, resume anywhere, restart yourself.

Production TPU time is preemptible, and a device can stop answering
without raising — a long run that cannot be killed and resumed is a run
that eventually loses everything. This package is the machinery that
makes any Trainer run survivable:

- ``signals``    — chained signal subscriptions (flight recorder AND
  preemption guard share SIGTERM; neither clobbers the other).
- ``preempt``    — SIGTERM/SIGINT → flush in-flight checkpoint + flight
  ring → :class:`Preempted` at the next step boundary → exit
  :data:`EXIT_PREEMPTED` (75), the supervisor's requeue signal.
- ``heartbeat``  — step/activity watermark file the Trainer feeds and
  the supervisor reads.
- ``faults``     — ``DLTPU_FAULTS`` injection (sigterm / crash / wedge)
  so the whole loop is CPU-testable in tier-1.
- ``supervisor`` — launch, watch, classify slow-vs-wedged, kill,
  requeue with bounded exponential backoff.
- ``topology`` / ``resume`` — checkpoint topology sidecars and
  restore-onto-a-different-mesh (import these two explicitly:
  ``from deeplearning_tpu.elastic import resume`` — they import jax,
  the rest of the package stays importable without touching a backend).

README "Elastic run policy" documents the exit-code and backoff
contract; ``tools/supervise.py`` is the CLI.
"""

from . import faults, heartbeat, preempt, signals, supervisor
from .preempt import EXIT_PREEMPTED, Preempted, PreemptionGuard
from .supervisor import Supervisor, SupervisorConfig, WedgeDetector

__all__ = ["signals", "preempt", "heartbeat", "faults", "supervisor",
           "EXIT_PREEMPTED", "Preempted", "PreemptionGuard",
           "Supervisor", "SupervisorConfig", "WedgeDetector"]
