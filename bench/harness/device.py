"""The device a run is on, its published peaks, and compile counting."""

from __future__ import annotations

import json
import os
from typing import Optional


class NoAccelerator(RuntimeError):
    """JAX finds no TPU of a kind in the peak table, or too few chips."""


def require_accelerator(chips: int, peaks: dict):
    """The first ``chips`` devices, which must be TPUs whose kind has
    published peaks; raises ``NoAccelerator`` otherwise."""
    import jax
    devices = jax.devices()
    d = devices[0]
    if d.platform != "tpu":
        raise NoAccelerator(f"needs a TPU, found {d.platform}")
    if d.device_kind not in peaks["kinds"]:
        raise NoAccelerator(f"no published peaks for {d.device_kind!r} in "
                            "bench/peaks.json")
    if len(devices) < chips:
        raise NoAccelerator(f"cell needs {chips} chips, found {len(devices)}")
    return devices[:chips]


def describe(devices) -> dict:
    d = devices[0]
    import jax
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(jax.devices())}


def memory_peak_bytes(devices) -> Optional[int]:
    """Peak bytes in use on the fullest chip, where the backend reports it."""
    peaks = []
    for d in devices:
        stats = d.memory_stats() or {}
        if "peak_bytes_in_use" in stats:
            peaks.append(int(stats["peak_bytes_in_use"]))
    return max(peaks) if peaks else None


def load_peaks(root: str) -> dict:
    with open(os.path.join(root, "bench", "peaks.json")) as f:
        return json.load(f)


class CompileCounter:
    """Counts, through JAX's monitoring events, the programs lowered
    (every new program this process meets, whether then compiled or read
    from the persistent cache) and the backend compilations."""

    def __init__(self):
        import jax
        from jax._src import dispatch
        self.lowerings = 0
        self.compiles = 0
        self.compile_s = 0.0
        self._lower = dispatch.JAXPR_TO_MLIR_MODULE_EVENT
        self._compile = dispatch.BACKEND_COMPILE_EVENT
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, duration: float, **_) -> None:
        if event == self._lower:
            self.lowerings += 1
        elif event == self._compile:
            self.compiles += 1
            self.compile_s += duration
