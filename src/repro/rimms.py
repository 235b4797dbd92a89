"""Stable public namespace for the RIMMS runtime (ISSUE 10 satellite).

``import repro.rimms as rimms`` is the supported surface for user code:
the streaming session API, the op/variant registry, calibration and
autotuning, platform registration, and the public exception types.
Internal module layout (``repro.core.*``) may shift between issues;
names re-exported here — everything in ``__all__`` — stay put.

    import repro.rimms as rimms

    @rimms.op("fft", kinds=("cpu",))
    def my_fft(ins): ...

    with rimms.Session.emulated(n_cpu=2) as session:
        table = rimms.autotune(session)       # measured variant winners
        session.save_calibration("calib.json")
    session = rimms.Session.emulated(calibration="calib.json")
"""

from __future__ import annotations

from repro.core.allocator import AllocError
from repro.core.api import (
    BufferFuture, OpRegistry, OpVariant, Session, SessionClient,
    SessionClosedError, default_registry, op,
)
from repro.core.autotune import Tunable, autotune, register_tunables, tunables
from repro.core.calibrate import (
    DEFAULT_VARIANT, CalibrationTable, calibrate, resolve_calibration,
)
from repro.core.graph import CostModel
from repro.core.locations import HOST, Location
from repro.core.pworker import WorkerDied
from repro.core.qos import BackpressureFull, QuotaExceeded
from repro.core.runtime import (
    BACKENDS, platform_names, register_platform, resolve_backend,
)

__all__ = [
    # streaming session API
    "Session", "SessionClient", "SessionClosedError", "BufferFuture",
    # op/variant registry
    "op", "OpRegistry", "OpVariant", "default_registry", "DEFAULT_VARIANT",
    # calibration + autotuning (ISSUE 10)
    "CalibrationTable", "calibrate", "resolve_calibration", "autotune",
    "register_tunables", "tunables", "Tunable", "CostModel",
    # platforms / backends
    "register_platform", "platform_names", "BACKENDS", "resolve_backend",
    "HOST", "Location",
    # public exception types
    "AllocError", "QuotaExceeded", "BackpressureFull", "WorkerDied",
]
