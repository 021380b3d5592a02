"""Zero-downtime serving: versioned deploys, canary rollout, drain.

Production model serving is not ``ParallelInference`` alone — it is the
lifecycle around it: a new version must be **warmed before it sees
traffic** (whole-program XLA compiles on the first request are exactly
the cold-start the AOT-everything posture of Fishman et al.
arXiv:1810.09868 exists to kill), promoted **gradually** under measured
SLOs, and **rolled back automatically** when it grades worse than the
incumbent — with in-flight requests drained, never dropped. The DL4J
heritage here is the model-zoo/serving layer (PAPER.md); the SLO gating
reuses the PR-3 rule engine and the PR-5 typed-failure machinery.

Three modules:

- :mod:`~deeplearning4j_tpu.serving.registry` — :class:`ModelRegistry`:
  ``deploy(version, net)`` builds a ``ParallelInference`` per version and
  AOT-warms every shape-bucket executable before the version is eligible
  for traffic (the persistent compile cache placed by
  ``async_runtime.configure_compile_cache`` makes re-deploys and restarts
  skip compilation entirely);
  ``deploy_generative(version, engine)`` does the same for a generative
  decode version — a ``GenerationPipeline`` whose prefill, slot-insert,
  and decode-step executables all warm before traffic;
  ``retire(version)`` goes through graceful drain.
- :mod:`~deeplearning4j_tpu.serving.rollout` — :class:`CanaryRollout`:
  the shadow → canary → ramp → full / rolled-back state machine, graded
  by per-version SLO rules (latency-quantile ratio, error rate, shadow
  divergence) evaluated through a PR-3 :class:`SLOEngine`.
- :mod:`~deeplearning4j_tpu.serving.router` — :class:`ServingRouter`:
  the ``output()`` front-end that splits traffic deterministically by
  request hash, records ``dl4j_serving_version_*`` metrics, fires the
  ``serving.canary`` chaos point on the canary path, and under
  ``DL4J_TPU_ROLLOUT=0`` degrades to a byte-identical single-version
  passthrough.

Two further modules grow this into a *network* serving tier (the HTTP
front door PR):

- :mod:`~deeplearning4j_tpu.serving.frontdoor` — :class:`FrontDoor`:
  the HTTP/SSE wire surface (``POST /v1/classify``, ``POST /v1/generate``
  with per-token streaming, typed-error → status mapping, admission
  control, the ``http.request`` chaos point, ``dl4j_http_*`` metrics).
- :mod:`~deeplearning4j_tpu.serving.shared_state` — :class:`SharedStore`
  + :class:`SharedServingState`: the file-backed CAS store N worker
  processes coordinate through (one version set, consistent canary
  splits, fleet-aggregated SLO windows, shared drains) — with
  **lease-fenced leadership** (monotonic leader terms; a stale leader's
  write loses at write time, ``DL4J_TPU_FLEET_FENCE``), digest-validated
  reads with corruption quarantine + mirror-replay rebuild, and
  negative-clock-delta clamping throughout.
- :mod:`~deeplearning4j_tpu.serving.idempotency` — :class:`ResultJournal`:
  the front door's bounded, TTL'd ``X-Dl4j-Idempotency-Key`` → outcome
  journal (``DL4J_TPU_IDEMPOTENCY``): a retried key replays the original
  outcome without re-executing, so QoS token debt is charged exactly
  once per key — the safety the fleet proxy's connect-failover rides.
- :mod:`~deeplearning4j_tpu.serving.session` — :class:`Session` +
  :class:`SessionJournal`: the durable generation-session layer
  (``DL4J_TPU_SESSIONS``): every admitted generation journals its
  prompt hash, sampler seed and emitted-token log into the shared
  store at step boundaries, so a survivor worker can **adopt** an
  orphaned stream (lease-fenced), re-prefill ``prompt + emitted`` and
  continue the identical token sequence — mid-stream crash failover
  with exactly-once delivery, byte-identical under greedy.

Surfaces: ``UIServer GET /debug/deploy`` and ``deploy.json`` in
flight-recorder bundles both serve :func:`snapshot`;
``GET /debug/fleet`` and ``fleet.json`` serve
:func:`~deeplearning4j_tpu.serving.frontdoor.fleet_snapshot` (fence
state, corruption/rebuild evidence, the idempotency journal).
"""
from deeplearning4j_tpu.serving.errors import (RolloutConflictError,
                                               StoreLockTimeout)
from deeplearning4j_tpu.serving.frontdoor import (FrontDoor, fleet_snapshot,
                                                  frontdoor_enabled)
from deeplearning4j_tpu.serving.idempotency import (IDEMPOTENCY_HEADER,
                                                    ResultJournal,
                                                    idempotency_enabled)
from deeplearning4j_tpu.serving.registry import DeployedVersion, ModelRegistry
from deeplearning4j_tpu.serving.rollout import (CanaryRollout, RolloutPolicy,
                                                RolloutState)
from deeplearning4j_tpu.serving.router import ServingRouter, rollout_enabled
from deeplearning4j_tpu.serving.session import (Session, SessionJournal,
                                                SessionLost,
                                                sessions_enabled)
from deeplearning4j_tpu.serving.shared_state import (SharedServingState,
                                                     SharedStore,
                                                     fleet_fence_enabled)

__all__ = [
    "ModelRegistry", "DeployedVersion", "CanaryRollout", "RolloutPolicy",
    "RolloutState", "ServingRouter", "rollout_enabled", "snapshot",
    "FrontDoor", "frontdoor_enabled", "SharedStore", "SharedServingState",
    "RolloutConflictError", "StoreLockTimeout", "fleet_fence_enabled",
    "fleet_snapshot", "ResultJournal", "IDEMPOTENCY_HEADER",
    "idempotency_enabled", "Session", "SessionJournal", "SessionLost",
    "sessions_enabled",
]


def snapshot() -> dict:
    """The ``/debug/deploy`` + bundle ``deploy.json`` payload: every live
    registry's versions (state, warmup, traffic) and every live router's
    rollout state machine."""
    return {
        "rollout_enabled": rollout_enabled(),
        "registries": [r.snapshot() for r in list(ModelRegistry._live)],
        "routers": [r.snapshot() for r in list(ServingRouter._live)],
    }
