"""Grouped server configuration (the BulletServer construction surface).

``BulletServer.__init__`` accreted 17 keyword parameters across the first
seven PRs. This module groups them into cohesive frozen sub-configs so the
surface stops rotting:

    from repro.core.config import CacheConfig, ServerConfig
    server = BulletServer(cfg, params, config=ServerConfig(
        slo=SLO(3.0, 150.0), max_slots=8,
        cache=CacheConfig(share_prefix=True)))

The legacy flat-kwarg form still works for one release via a deprecation
shim in the engine (it forwards through :meth:`ServerConfig.from_legacy`
and warns). ``launch/serve.py`` builds the config from CLI flags in one
place (``build_server_config``).

Defaults here are "resolve later" sentinels (None) wherever the engine
picks a platform-dependent default (paged on CPU-hosted tests vs dense,
fused on single-device, device list, dtype); the engine resolves them
exactly as the legacy kwargs did, so `ServerConfig()` ≡ no kwargs.
"""

from __future__ import annotations

from dataclasses import dataclass, field, fields
from typing import Any, Optional, Sequence, Tuple

from repro.core.scheduler import SchedulerConfig
from repro.serving.request import SLO


@dataclass(frozen=True)
class CacheConfig:
    """KV cache layout and reuse knobs (docs/KV_SHARING.md)."""
    #: paged pool (None = engine default: paged when supported)
    paged: Optional[bool] = None
    #: tokens per KV page
    page_size: int = 16
    #: ref-counted shared-prefix page reuse in the paged pool; requires a
    #: paged cache and tile granularity (docs/KV_SHARING.md)
    share_prefix: bool = False


@dataclass(frozen=True)
class ExecConfig:
    """Where and how cycles execute (docs/PARTITIONS.md)."""
    #: fused spatial-sharing cycles (None = engine default)
    fused: Optional[bool] = None
    #: partition granularity: "tile" | "chip" | "auto"
    partition: str = "tile"
    #: explicit device list (None = all local devices)
    devices: Optional[Tuple[Any, ...]] = None


@dataclass(frozen=True)
class ControlConfig:
    """The control loops around the scheduler (docs/TUNING.md)."""
    #: online estimator refit: None = engine default (on), False = pinned,
    #: or a pre-built OnlineRefitter
    refit: Any = None
    #: cycles between refit solves
    refit_interval: int = 32
    #: Algorithm 1/2 search knobs; None = a fresh per-server
    #: SchedulerConfig() (never a shared module-level instance)
    sched: Optional[SchedulerConfig] = None


@dataclass(frozen=True)
class ServerConfig:
    """Everything BulletServer needs beyond (model cfg, params)."""
    slo: Optional[SLO] = None
    est: Any = None                      # PerfEstimator; None = default
    max_slots: int = 8
    max_len: int = 128
    max_prefill_batch: int = 4
    dtype: Any = None                    # None = engine default (float32)
    cache: CacheConfig = field(default_factory=CacheConfig)
    execution: ExecConfig = field(default_factory=ExecConfig)
    control: ControlConfig = field(default_factory=ControlConfig)
    obs: Any = None                      # Observability seam
    faults: Any = None                   # FaultInjector seam
    guard: Any = None                    # SLOGuard seam
    #: TenancyController seam (docs/MULTITENANCY.md): per-tenant rate
    #: limits, OIT throttling, credit-biased admission + preemption;
    #: None runs single-tenant, byte-identical to the pre-tenancy engine
    tenancy: Any = None

    @classmethod
    def from_legacy(cls, kw: dict) -> "ServerConfig":
        """Build a ServerConfig from the pre-redesign flat kwargs.

        Raises TypeError on names that were never BulletServer kwargs, so
        the shim keeps the old surface's typo detection."""
        unknown = set(kw) - LEGACY_KEYS
        if unknown:
            raise TypeError(
                f"unknown BulletServer argument(s): {sorted(unknown)}")
        kw = dict(kw)
        devices = kw.pop("devices", None)
        if devices is not None and not isinstance(devices, tuple):
            devices = tuple(devices)
        cache = CacheConfig(
            paged=kw.pop("paged", None),
            page_size=kw.pop("page_size", 16),
            share_prefix=kw.pop("share_prefix", False))
        execution = ExecConfig(
            fused=kw.pop("fused", None),
            partition=kw.pop("partition", "tile"),
            devices=devices)
        control = ControlConfig(
            refit=kw.pop("refit", None),
            refit_interval=kw.pop("refit_interval", 32),
            sched=kw.pop("sched", None))
        return cls(cache=cache, execution=execution, control=control, **kw)


#: the flat kwargs the deprecation shim accepts (the historical 17 plus
#: the new share_prefix knob, for symmetry during the transition release)
LEGACY_KEYS = frozenset(
    {f.name for f in fields(ServerConfig)
     if f.name not in ("cache", "execution", "control")}
    | {f.name for f in fields(CacheConfig)}
    | {f.name for f in fields(ExecConfig)}
    | {f.name for f in fields(ControlConfig)})


def build_server_config(args, *, slo=None, est=None, obs=None,
                        faults=None, guard=None, tenancy=None,
                        refit: Any = None, dtype: Any = None) -> ServerConfig:
    """The one place launch/serve.py turns CLI flags into a ServerConfig.

    ``args`` is the serve argparse namespace; objects the launcher
    constructs itself (SLO choice differs per mode, estimator, obs,
    resilience seams, tenancy controller) are passed explicitly."""
    return ServerConfig(
        slo=slo, est=est,
        max_slots=args.slots, max_len=args.max_len, dtype=dtype,
        cache=CacheConfig(page_size=args.page_size,
                          share_prefix=args.share_prefix),
        execution=ExecConfig(partition=args.partition),
        control=ControlConfig(refit=refit),
        obs=obs, faults=faults, guard=guard, tenancy=tenancy)
