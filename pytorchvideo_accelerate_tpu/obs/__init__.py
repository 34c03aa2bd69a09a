"""Unified telemetry spine: spans, flight recorder, watchdog, metrics.

The shared observability layer for every subsystem (data decode pool,
device prefetcher, pjit train loop, checkpointing, serving micro-batcher).
Stdlib-only — importable from worker threads and the serving process
without touching jax. See docs/OBSERVABILITY.md for the span taxonomy and
the runbook.

Process-default singletons (`get_collector`/`get_recorder`/`get_registry`)
are the convenient shared path — like the logging module, telemetry wants
ambient availability; tests construct private instances. `configure()` is
the one switch: `obs.enabled=false` turns every span into a shared no-op
context manager and detaches the recorder.
"""

from __future__ import annotations

from pytorchvideo_accelerate_tpu.obs.flight_recorder import (  # noqa: F401
    FlightRecorder,
    get_recorder,
)
from pytorchvideo_accelerate_tpu.obs.registry import (  # noqa: F401
    Counter,
    Gauge,
    Histogram,
    Registry,
    get_registry,
)
from pytorchvideo_accelerate_tpu.obs.spans import (  # noqa: F401
    BACKGROUND as BACKGROUND_SPANS,
    SpanCollector,
    current_stacks,
    get_collector,
    span,
    timed_span,
)
from pytorchvideo_accelerate_tpu.obs.watchdog import Watchdog  # noqa: F401
# distributed tracing (obs/trace.py): `obs.trace.configure_tracing(...)`,
# capture/attach handoff helpers, the per-process trace ring
from pytorchvideo_accelerate_tpu.obs import trace  # noqa: F401
# pva-tpu-hbm (PR 18): the device-memory ledger, the scrape-tick history
# ring + burn-rate alert engine, and on-demand profiler capture — all
# follow the sync.py arming discipline (disarmed = one global read)
from pytorchvideo_accelerate_tpu.obs import alerts  # noqa: F401
from pytorchvideo_accelerate_tpu.obs import history  # noqa: F401
from pytorchvideo_accelerate_tpu.obs import memory  # noqa: F401
from pytorchvideo_accelerate_tpu.obs import profiler  # noqa: F401
from pytorchvideo_accelerate_tpu.obs.alerts import (  # noqa: F401
    AlertEngine,
    AlertRule,
)
from pytorchvideo_accelerate_tpu.obs.history import MetricsHistory  # noqa: F401,E501
from pytorchvideo_accelerate_tpu.obs.memory import MemoryLedger  # noqa: F401

# default wiring: completed spans feed the flight-recorder ring
get_collector().recorder = get_recorder()


def configure(enabled: bool = None, capacity: int = None) -> SpanCollector:
    """Flip the process-default telemetry on/off and/or resize the flight
    ring (Trainer/serving call this from TrainConfig.obs)."""
    collector = get_collector()
    recorder = get_recorder()
    if capacity is not None:
        recorder.set_capacity(capacity)
    if enabled is not None:
        collector.enabled = bool(enabled)
        collector.recorder = recorder if enabled else None
    return collector
