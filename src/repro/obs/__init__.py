"""Flight recorder + watchtower for the federated split engine: tracing,
metrics, recording + replay, profiling (ISSUE 6 / ROADMAP item 4), and the
detection layer over it — health monitors, content digests, run diffing,
bench regression gating (ISSUE 7).

  * :mod:`repro.obs.trace`    — virtual-clock nested spans + Chrome-trace
    export; the round's span names on the profiler's clock
  * :mod:`repro.obs.metrics`  — typed counter/gauge/histogram registry + JSONL
  * :mod:`repro.obs.recorder` — per-run persistence of feedback/knobs/metrics
    /alerts/digests
  * :mod:`repro.obs.replay`   — offline controller replay over recorded logs
  * :mod:`repro.obs.profile`  — jit + kernel timing feeding the roofline model
  * :mod:`repro.obs.health`   — per-round numeric-health monitors + policies
  * :mod:`repro.obs.digest`   — content digests of the committed global state
  * :mod:`repro.obs.diff`     — cross-run divergence localization
  * :mod:`repro.obs.regress`  — bench-baseline regression gate (CLI)
"""
from repro.obs.diff import DiffEntry, RunDiff, diff_runs
from repro.obs.digest import (RoundDigest, digest_from_dict, digest_to_dict,
                              state_digest, tree_digest, tree_sketch)
from repro.obs.health import (HEALTH_CHECKS, HealthAbort, HealthAlert,
                              HealthMonitor, alert_from_dict, alert_to_dict)
from repro.obs.metrics import (Counter, Gauge, Histogram, JsonlSink,
                               MetricsRegistry, load_jsonl, observe_round)
from repro.obs.profile import (KernelProfile, profile_agg_fuse,
                               profile_dp_clip, profile_engine_kernels,
                               profile_fedavg, profile_jit)
from repro.obs.recorder import (FlightRecorder, RunRecord, feedback_from_dict,
                                feedback_to_dict, knobs_from_dict,
                                knobs_to_dict, load_run)
from repro.obs.replay import (ReplayResult, replay_decisions, replay_run,
                              suite_from_manifest)
from repro.obs.trace import (Span, Tracer, validate_chrome_trace)

__all__ = [
    "DiffEntry", "RunDiff", "diff_runs",
    "RoundDigest", "digest_from_dict", "digest_to_dict", "state_digest",
    "tree_digest", "tree_sketch",
    "HEALTH_CHECKS", "HealthAbort", "HealthAlert", "HealthMonitor",
    "alert_from_dict", "alert_to_dict",
    "Counter", "Gauge", "Histogram", "JsonlSink", "MetricsRegistry",
    "load_jsonl", "observe_round",
    "KernelProfile", "profile_agg_fuse", "profile_dp_clip",
    "profile_engine_kernels", "profile_fedavg", "profile_jit",
    "FlightRecorder", "RunRecord", "feedback_from_dict", "feedback_to_dict",
    "knobs_from_dict", "knobs_to_dict", "load_run",
    "ReplayResult", "replay_decisions", "replay_run", "suite_from_manifest",
    "Span", "Tracer", "validate_chrome_trace",
]
