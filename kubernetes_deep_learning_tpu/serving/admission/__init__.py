"""End-to-end admission control & overload management for the serving path.

The reference leans entirely on Kubernetes for overload behavior (replica
scaling + a cloud LoadBalancer); in-process it has a single fixed 20 s
deadline and no shedding, so under 2x load every request degrades together.
This package makes the tiers themselves predictable under overload, in the
spirit of Clockwork (OSDI '20) and DAGOR (SoCC '18):

- ``deadline``: a per-request deadline budget propagated in the
  ``X-Request-Deadline-Ms`` header, so every queue wait and upstream
  timeout is computed from the REMAINING budget and exhausted requests are
  rejected before touching the TPU;
- ``limiter``: an AIMD adaptive concurrency limiter with a bounded
  admission queue (503 + Retry-After with a distinct shed reason);
- ``breaker``: a gateway-side circuit breaker on the model tier with
  half-open probing;
- ``controller``: the per-tier front door combining the above, the
  ``kdlt_admission_*`` metrics, and graceful drain (SIGTERM flips /readyz,
  stops admission, lets in-flight work finish).
"""

from kubernetes_deep_learning_tpu.serving.admission.breaker import CircuitBreaker
from kubernetes_deep_learning_tpu.serving.admission.brownout import (
    BrownoutController,
    brownout_enabled,
)
from kubernetes_deep_learning_tpu.serving.admission.controller import (
    AdmissionController,
    Ticket,
    admission_enabled,
    drain_timeout_s,
    install_sigterm_drain,
)
from kubernetes_deep_learning_tpu.serving.admission.deadline import (
    DEADLINE_HEADER,
    WSGI_DEADLINE_KEY,
    Deadline,
)
from kubernetes_deep_learning_tpu.serving.admission.limiter import (
    AdaptiveLimiter,
    env_budgets,
    parse_budgets,
)
from kubernetes_deep_learning_tpu.serving.admission.shed import (
    RETRY_AFTER_HEADER,
    Shed,
    retry_after_headers,
)

__all__ = [
    "AdaptiveLimiter",
    "AdmissionController",
    "BrownoutController",
    "CircuitBreaker",
    "DEADLINE_HEADER",
    "Deadline",
    "RETRY_AFTER_HEADER",
    "Shed",
    "Ticket",
    "WSGI_DEADLINE_KEY",
    "admission_enabled",
    "brownout_enabled",
    "drain_timeout_s",
    "env_budgets",
    "install_sigterm_drain",
    "parse_budgets",
    "retry_after_headers",
]
