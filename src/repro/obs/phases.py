"""Engine phase spans in the JAX profiler's own trace.

``BulletServer.step`` opens one span per phase it runs, named from
:data:`ENGINE_PHASES`, as ``jax.profiler.TraceAnnotation`` host events.
They land on the host plane of a ``jax.profiler.trace`` capture, on the
same clock as the device's operations, so a device idle gap can be
charged to the engine phase the host was in (docs/OBSERVABILITY.md,
"Engine phase spans").

The spans are not gated on ``Observability.enabled``: the profiler's own
check is the gate, and with no capture running an annotation costs one
enter/exit pair of a native object.
"""

from __future__ import annotations

import jax

#: every span the engine emits; all nest inside ``engine.step``
ENGINE_PHASES = (
    "engine.step",       # the whole of BulletServer.step
    "engine.admit",      # forming the next prompt batch
    "engine.schedule",   # scheduler.schedule + reorder + partition switch
    "engine.prefill",    # a prefill layer group's launch (serial, fused, chip)
    "engine.migrate",    # a finished prompt batch's handoff to decode
    "engine.tables",     # block-table export and upload
    "engine.decode",     # the decode iteration's launch
    "engine.readback",   # a device->host read (EngineStats.host_syncs)
    "engine.emit",       # per-slot token bookkeeping after a decode readback
    "engine.refit",      # an online estimator refit
)


def phase(name: str) -> jax.profiler.TraceAnnotation:
    """The span of one engine phase; use as a context manager. No keyword
    arguments: they would format a string on every call."""
    return jax.profiler.TraceAnnotation(name)
