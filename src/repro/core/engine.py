"""Concurrent execution engine (paper §3.5) — real-model execution path.

Two engine objects (prefill, decode) share a MetadataBuffer and a unified
KV pool, each running a decentralized scheduling loop:

- The **prefill engine** launches one *pattern-repeat group* of layers per
  cycle (the paper's layer-group launches), consulting the SLO scheduler
  between groups; a finished prompt migrates to decode by page-table /
  slot-index handoff only.
- The **decode engine** runs one continuous-batching iteration per cycle
  through a single pre-compiled step function (the CUDA-Graph analogue:
  one jit executable reused every iteration), reading global state from
  the shared buffer first.

On-device caches default to a **block-paged page pool** ((R, pages+1, ps,
K, D) per pattern position) driven by ``PagedKVPool``'s block tables:
prefill scatters KV straight into pooled pages (no ``max_len``-row
migration copy), decode streams only live pages through the paged Pallas
kernel (grid bucketed over the max live page count to bound recompiles),
and preempt / resume / migrate move block ownership in the table instead
of re-laying-out device rows. Architectures the paged layout cannot cover
(ring windows, recurrent states, cross-attention) fall back to the dense
fixed-slot pool ((R, slots, S, K, D)) written in place via donation —
both are functional analogues of the cudaIpc shared pool. JAX async
dispatch lets the host run scheduling while the device executes,
mirroring the paper's decoupled CPU/GPU control flow.
"""

from __future__ import annotations

import functools
import time
import warnings
from collections import deque
from dataclasses import dataclass, field, replace
from typing import Callable, Deque, Dict, List, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P
import numpy as np

from repro.configs.base import ModelConfig
from repro.core import analytics
from repro.core.config import ServerConfig
from repro.core.estimator import (CycleObservation, OnlineRefitter,
                                  PerfEstimator, hardware_for,
                                  predict_cycle)
from repro.core.metadata import MetadataBuffer
from repro.core.resource import ResourceManager
from repro.core.scheduler import SchedulerConfig, SLOScheduler
from repro.kvcache.paged import PagedKVPool, transfer_pages
from repro.launch.submesh import (HandoffPolicy, SubMeshSplit,
                                  carve_submeshes, chip_mesh, find_split)
from repro.models import transformer as T
from repro.models.attention import prefill_length
from repro.obs import NULL_OBS, CycleEvent, Observability
from repro.obs.phases import phase
from repro.resilience.faults import (NULL_FAULTS, DispatchError, FaultInjector,
                                     HandoffError)
from repro.models.sharding import (submesh_cache_sharding,
                                   submesh_param_sharding)
from repro.serving.request import Phase, Request, SLO


# ---------------------------------------------------------------------------
# jitted step functions (compiled once, reused — §3.4.2 pre-configured states)
# ---------------------------------------------------------------------------

def _mesh_jit(impl: Callable, sharding, *, donate_argnums=()) -> Callable:
    """jit of the raw step ``impl``; keyword arguments (cfg, rep,
    decode_share) are static. ``sharding`` names where the arguments live
    replicated (chip-enabled serving keeps its state so, on a sub-mesh or
    the global mesh; None = one device). XLA cannot partition a Pallas
    kernel by itself, so on more than one device the whole step runs
    under shard_map, every argument and result replicated: each device
    computes the full step, exactly as the replicated program would."""
    multi = sharding is not None and len(sharding.device_set) > 1

    def run(*args, **static):
        body = functools.partial(impl, **static)
        if multi:
            body = jax.shard_map(body, mesh=sharding.mesh, in_specs=P(),
                                 out_specs=P(), check_vma=False)
        return body(*args)
    return jax.jit(run, static_argnames=("cfg", "rep", "decode_share"),
                   donate_argnums=donate_argnums)


@functools.partial(jax.jit, static_argnames=("cfg", "repeat"),
                   donate_argnums=(3,))
def _prefill_group(params_slice, x, positions, cache_slice, lengths, *,
                   cfg: ModelConfig, repeat: int):
    """Run one pattern-repeat group of layers over the prompt batch."""
    del repeat
    new_entries = []
    for j, blk in enumerate(cfg.pattern):
        x, entry, _ = T._apply_block_full(
            x, params_slice[j], blk, cfg, None, positions, None)
        entry = T._prefill_cache_entry(entry, blk, cfg, lengths,
                                       cache_slice[j], False)
        new_entries.append(entry)
    return x, tuple(new_entries)


def _decode_iteration_impl(params, cache, tokens, pos, active,
                           block_tables=None, *, cfg: ModelConfig):
    """One continuous-batching decode iteration over all slots; inactive
    slots are masked out of the sampled tokens. ``block_tables`` (B, n_b)
    switches to the block-paged cache layout — its (bucketed) width is the
    paged kernel's grid depth. Raw body: the module-level jit below serves
    the serial/fused engine; chip-granular entries wrap their own pjit of
    it bound to the decode sub-mesh (ChipExecutable)."""
    logits, cache = T.decode_step(params, cache, tokens, pos, cfg,
                                  block_tables=block_tables)
    next_tokens = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    next_tokens = jnp.where(active, next_tokens, 0)
    return next_tokens[:, None], cache


_decode_iteration = _mesh_jit(_decode_iteration_impl, None,
                              donate_argnums=(1,))


@functools.partial(jax.jit, static_argnames=("cfg",))
def _embed_prompt(params, tokens, *, cfg: ModelConfig):
    return T.embed_tokens(params, tokens, cfg, None)


@functools.partial(jax.jit, static_argnames=("cfg",))
def _final_logits(params, x, lengths, *, cfg: ModelConfig):
    from repro.models import layers as L
    x = L.rms_norm(x, params["final_norm"], cfg.rmsnorm_eps)
    idx = jnp.clip(lengths - 1, 0, x.shape[1] - 1)
    last = jnp.take_along_axis(x, idx[:, None, None], axis=1)[:, 0]
    logits = T.lm_logits(params, last[:, None], cfg, None)[:, 0]
    return jnp.argmax(logits, axis=-1).astype(jnp.int32)


@functools.partial(jax.jit, donate_argnums=(0,))
def _write_slot(cache_leaf, src_leaf, slot):
    """Copy one request's prefill cache row into its decode slot (dense
    fallback path only — the paged path hands off block indices)."""
    return jax.lax.dynamic_update_index_in_dim(
        cache_leaf, src_leaf, slot, axis=1)


def _prefill_group_paged_impl(params_slice, x, positions, *,
                              cfg: ModelConfig):
    """Run one pattern-repeat group over the prompt batch, returning the
    raw full-sequence KV entries; the caller scatters them straight into
    pooled pages — no dense ``max_len`` row is ever materialized. Raw
    body: the module-level jit below serves the serial engine; chip
    entries wrap their own pjit bound to the prefill sub-mesh."""
    entries = []
    for j, blk in enumerate(cfg.pattern):
        x, entry, _ = T._apply_block_full(
            x, params_slice[j], blk, cfg, None, positions, None)
        entries.append((entry["k"], entry["v"]))
    return x, tuple(entries)


_prefill_group_paged = _mesh_jit(_prefill_group_paged_impl, None)


def _fused_step_impl(params, cache, x, positions, page_map, tokens, pos,
                     active, block_tables, *, cfg: ModelConfig, rep: int,
                     decode_share: float):
    """One spatially-fused engine cycle (§3.5 co-execution): pattern-repeat
    group ``rep`` of the in-flight prefill AND one continuous-batching
    decode iteration, in a single dispatch. At repeat ``rep`` each layer's
    prefill and decode attention share one fused launch whose grid slots
    are interleaved by ``decode_share`` (the partition's ``m_i/M``);
    elsewhere the decode pass streams paged KV as usual. Inactive slots'
    sampled tokens are masked exactly like ``_decode_iteration``."""
    x_p, logits, cache = T.fused_group_decode(
        params, cache, x, positions, page_map, tokens, pos, cfg,
        rep=rep, decode_share=decode_share, block_tables=block_tables)
    next_tokens = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    next_tokens = jnp.where(active, next_tokens, 0)
    return x_p, next_tokens[:, None], cache


_fused_step = _mesh_jit(_fused_step_impl, None, donate_argnums=(1,))


class FusedExecutable(NamedTuple):
    """One pre-built execution state of the resource manager's table
    (§3.4.2): the jitted fused step with a PartitionConfig's decode_share
    baked in as a static argument. ``ResourceManager.switch`` selecting a
    different entry is the libsmctrl stream-swap analogue — a dict lookup,
    never a rebuild."""
    config_id: int
    decode_share: float
    fn: Callable


class ChipExecutable(NamedTuple):
    """One chip-granular execution state of the resource manager's table
    (§3.4.2, second granularity): a pre-built pjit pair bound to a
    disjoint (prefill sub-mesh, decode sub-mesh) split of the device
    group. The prefill executable runs layer groups replicated on the
    prefill sub-mesh and scatters prompt KV into the prefill-side staging
    page pool; the decode executable runs continuous-batching iterations
    on the decode sub-mesh's page pool. The two only meet at the
    ``jax.device_put`` KV handoff (kvcache.paged.transfer_pages) when a
    prompt finishes. Switching entries is still a dict lookup; lowering is
    per activation shape, exactly like FusedExecutable."""
    config_id: int
    split: SubMeshSplit
    p_sharding: object        # replicated NamedSharding, prefill sub-mesh
    d_sharding: object        # replicated NamedSharding, decode sub-mesh
    prefill_fn: Callable
    decode_fn: Callable


@functools.partial(jax.jit, donate_argnums=(0,))
def _scatter_group_pages(cache_leaf, kv, page_map, rep):
    """Scatter one layer group's prefill K/V into the pooled pages of
    repeat ``rep``. cache_leaf: (R, P+1, ps, K, D) donated (in-place page
    update); kv: (B, Sp, K, D); page_map: (B, ceil(Sp/ps)) physical pages
    (trash page past each request's length). One jitted delegate of the
    shared :func:`repro.models.transformer.scatter_prefill_pages` (the
    fused step scatters through the same helper)."""
    return T.scatter_prefill_pages(cache_leaf, kv, page_map, rep=rep)


@functools.partial(jax.jit, static_argnames=("cfg",))
def _prefill_group_shared(params_slice, x, positions, cache_blocks,
                          prefix_map, prefix_lens, rep, *, cfg: ModelConfig):
    """Run one pattern-repeat group over a *suffix* batch whose leading
    ``prefix_lens`` tokens are served from shared pages (docs/KV_SHARING.md):
    per layer, gather the prefix KV from repeat ``rep`` of the page pool
    via ``prefix_map`` (B, Lp) and attend prefix+suffix jointly. Returns
    the suffix's own KV entries for page scatter. The pool is read-only
    here (gather, no donation) — the caller scatters separately."""
    b = prefix_map.shape[0]
    entries = []
    for j, blk in enumerate(cfg.pattern):
        leaf = cache_blocks[j]
        k_pre = leaf["k"][rep][prefix_map]
        v_pre = leaf["v"][rep][prefix_map]
        k_pre = k_pre.reshape(b, -1, *k_pre.shape[3:])
        v_pre = v_pre.reshape(b, -1, *v_pre.shape[3:])
        x, entry = T._apply_block_prefix(
            x, params_slice[j], blk, cfg, None, positions,
            k_pre, v_pre, prefix_lens)
        entries.append((entry["k"], entry["v"]))
    return x, tuple(entries)


@functools.partial(jax.jit, donate_argnums=(0,))
def _scatter_suffix_group_pages(cache_leaf, kv, page_map, offsets, rep):
    """Scatter one layer group's *suffix* K/V into pooled pages at a
    per-row page offset (read-modify-write so copy-on-write prefixes below
    the offset survive). Jitted delegate of
    :func:`repro.models.transformer.scatter_suffix_pages`."""
    return T.scatter_suffix_pages(cache_leaf, kv, page_map, offsets, rep=rep)


@functools.partial(jax.jit, donate_argnums=(0,))
def _copy_pages(cache_leaf, src, dst):
    """Copy-on-write materialization: duplicate pages ``src`` into ``dst``
    across every repeat of one layer's pool, before the first divergent
    write lands in ``dst`` (docs/KV_SHARING.md)."""
    return cache_leaf.at[:, dst].set(cache_leaf[:, src])


# ---------------------------------------------------------------------------

@dataclass
class EngineStats:
    prefill_cycles: int = 0
    decode_iterations: int = 0
    reconfigs: int = 0
    paused_cycles: int = 0
    migrated: int = 0
    preempted: int = 0
    fused_cycles: int = 0
    #: estimator refits applied (params actually swapped) vs. attempts the
    #: OnlineRefitter rejected on its hysteresis margin
    refits: int = 0
    refits_rejected: int = 0
    #: chip-granular cycles (disjoint sub-mesh dispatches) and cross-mesh
    #: KV handoffs (requests whose pages re-sharded prefill→decode mesh)
    chip_cycles: int = 0
    handoffs: int = 0
    #: resilience counters (docs/RESILIENCE.md): deadline/explicit cancels,
    #: backpressure sheds, transient-handoff retries, unwound prefill
    #: batches, dispatch failures absorbed, and guard lattice transitions
    cancelled: int = 0
    shed: int = 0
    handoff_retries: int = 0
    prefill_aborts: int = 0
    dispatch_failures: int = 0
    degrades: int = 0
    restores: int = 0
    #: shared-prefix KV reuse (docs/KV_SHARING.md): tokens the prefill
    #: engine actually computed (unshared suffixes), tokens served from
    #: shared pages instead, and admissions that hit the prefix index
    prefill_tokens: int = 0
    reused_prefill_tokens: int = 0
    prefix_hits: int = 0
    #: device->host reads of serving (``BulletServer._to_host``): each
    #: waits for the device to compute the array and copy it back
    host_syncs: int = 0


class DecodeWork(NamedTuple):
    """What the most recent decode iteration actually executed — consumed
    by virtual-clock replay / estimator feedback so the work charged is
    the work that ran (per-slot live contexts, not a collapsed mean).

    ``streamed`` is each running slot's share of the KV tokens the cache
    stream actually fetched. Both kernels iterate over all ``max_slots``
    rows: the paged grid streams the *bucketed max* live page count per
    slot (dead columns and idle slots hit the trash page), the dense
    kernel streams every slot's full ``max_len`` row — so the total is
    ``max_slots × bucket·ps`` (paged) or ``max_slots × max_len`` (dense),
    apportioned over the ``batch`` slots that ran. This is what replay
    charges — live context bounds it from below.
    """
    batch: int
    mean_context: int
    contexts: Tuple[int, ...]             # live context per slot that ran
    streamed: Tuple[int, ...] = ()        # fetched KV tokens per ran slot


@dataclass
class PrefillTask:
    """Resumable prefill state for one prompt batch (paper §3.5).

    The prefill engine persists activations and per-group cache entries
    here between layer-group launches, so the main loop can run decode
    iterations — and admit newly-arrived work — *between* groups instead
    of holding the device for the whole prompt. In paged mode KV is
    scattered into pooled pages as each group finishes (``page_map``
    routes prompt blocks to physical pages) and ``tmp_cache``/``entries``
    stay empty."""
    batch: List[Request]
    x: jax.Array                          # activations after `rep` groups
    positions: jax.Array
    lengths: jax.Array
    tmp_cache: Optional[dict]
    n_tokens: int = 0                     # total prompt tokens in the batch
    entries: List[tuple] = field(default_factory=list)
    rep: int = 0                          # next pattern-repeat group to run
    #: (B, blocks) physical pages, uploaded to device once at admission
    #: (immutable for the task's lifetime — every group reuses it)
    page_map: Optional[jax.Array] = None
    #: partition granularity pinned at admission: "tile" runs the fused
    #: (or serial) co-located path, "chip" runs every layer group on the
    #: current chip entry's prefill sub-mesh with a cross-mesh KV handoff
    #: at migration. Pinned for the task's lifetime — pages scatter into
    #: one pool consistently.
    granularity: str = "tile"
    #: sharding the task's device state currently lives on (chip-enabled
    #: serving only; None = default placement)
    sharding: Optional[object] = None
    #: shared-prefix reuse (docs/KV_SHARING.md): when set, ``x``/``positions``
    #: /``lengths`` cover only each request's unshared suffix. prefix_map
    #: (B, Lp) gathers the reused pages (incl. the copy-on-write tail),
    #: prefix_lens (B,) the reused token counts, scatter_offsets (B,) the
    #: in-page slot of each row's first suffix token.
    prefix_map: Optional[jax.Array] = None
    prefix_lens: Optional[jax.Array] = None
    scatter_offsets: Optional[jax.Array] = None
    reused_tokens: int = 0                # sum of prefix_lens


class BulletServer:
    """Single-host Bullet serving runtime over a real JAX model."""

    def __init__(self, cfg: ModelConfig, params, *,
                 config: Optional[ServerConfig] = None, **legacy):
        """Construct from a grouped :class:`ServerConfig` (the documented
        surface — see docs/KV_SHARING.md and docs/TUNING.md):

            BulletServer(cfg, params, config=ServerConfig(slo=SLO(...)))

        The historical flat kwargs (slo=, paged=, fused=, …) still work
        for one release through a deprecation shim that forwards them via
        ``ServerConfig.from_legacy`` and warns."""
        if legacy:
            if config is not None:
                raise TypeError("pass either config=ServerConfig(...) or "
                                "the legacy flat kwargs, not both")
            config = ServerConfig.from_legacy(legacy)
            warnings.warn(
                "BulletServer(**kwargs) is deprecated; group the options "
                "in a repro.core.config.ServerConfig and pass config=...",
                DeprecationWarning, stacklevel=2)
        elif config is None:
            config = ServerConfig()
        if config.slo is None:
            raise TypeError("an SLO is required: pass "
                            "config=ServerConfig(slo=SLO(...))")
        self.config = config
        slo: SLO = config.slo
        est = config.est
        max_slots = config.max_slots
        max_len = config.max_len
        max_prefill_batch = config.max_prefill_batch
        # None -> a per-server SchedulerConfig(): a shared module-level
        # default instance would leak `replace(sched, fused=...)`-adjacent
        # mutations across servers
        sched = config.control.sched or SchedulerConfig()
        dtype = config.dtype if config.dtype is not None else jnp.float32
        paged = config.cache.paged
        page_size = config.cache.page_size
        share_prefix = config.cache.share_prefix
        fused = config.execution.fused
        partition = config.execution.partition
        devices = config.execution.devices
        refit = config.control.refit
        refit_interval = config.control.refit_interval
        obs = config.obs
        faults = config.faults
        guard = config.guard
        if cfg.pattern_tail:
            raise NotImplementedError(
                "BulletServer's layer-group loop does not handle "
                "pattern_tail configs; use a homogeneous-pattern model")
        self.cfg = cfg
        self.params = params
        self.slo = slo
        devs = list(devices) if devices is not None else jax.devices()
        if est is None and jax.default_backend() == "tpu":
            # priced on the chips this server runs on: every device for
            # the chip-granular partitions, the default one for tile
            est = PerfEstimator(hardware_for(
                devs if partition != "tile" else devs[:1]))
        self.est = est or PerfEstimator()
        self.buffer = MetadataBuffer()
        self.max_slots = max_slots
        self.max_len = max_len
        self.max_prefill_batch = max_prefill_batch
        self.stats = EngineStats()
        #: observability sink (docs/OBSERVABILITY.md): metrics registry +
        #: request spans + cycle trace. NULL_OBS (disabled) by default —
        #: every hook below is gated on ``self.obs.enabled``, so the
        #: uninstrumented hot path pays one attribute check per cycle.
        self.obs = obs if obs is not None else NULL_OBS
        #: fault-injection seam (docs/RESILIENCE.md): NULL_FAULTS (disabled)
        #: by default, mirroring NULL_OBS — every seam below is gated on
        #: ``self.faults.enabled`` so production pays one attribute check
        self.faults = faults if faults is not None else NULL_FAULTS
        #: retry-with-backoff policy for transient cross-mesh handoff
        #: failures; an attached SLOGuard installs its own
        self.handoff_policy = HandoffPolicy()
        #: the cycle event awaiting its measured duration (the driver's
        #: record_cycle_actual or record_cycle_duration completes it)
        self._open_cycle: Optional[CycleEvent] = None
        #: the device arrays _to_host used last, with their host copies:
        #: reading one of them again waits for nothing. Four cover one
        #: step's reads (first tokens, active, pos, sampled tokens), so an
        #: ``active`` unchanged since the last step is not read again.
        self._host_copies: Deque[Tuple[jax.Array, np.ndarray]] = deque(
            maxlen=4)
        if paged is None:
            paged = T.supports_paged_cache(cfg)
        elif paged and not T.supports_paged_cache(cfg):
            raise ValueError(f"{cfg.name}: pattern {cfg.pattern} cannot use "
                             "the block-paged cache (needs pure ATTN)")
        if share_prefix:
            if not paged:
                raise ValueError(
                    "share_prefix reuses pages of the block-paged pool; "
                    "needs paged=True (docs/KV_SHARING.md)")
            if partition != "tile":
                raise ValueError(
                    "share_prefix requires partition='tile': chip-granular "
                    "tasks stage prompt KV in a separate per-mesh pool, "
                    "which would leave shared pages pointing at garbage")
        self.share_prefix = share_prefix
        self.pool = PagedKVPool(max_slots * max_len, block_size=page_size,
                                share_prefix=share_prefix)
        self.paged = paged
        self.page_size = page_size
        # fused spatial prefill+decode execution (§3.5): default wherever
        # the paged layout covers the architecture; the serial path stays
        # as numerics reference and fallback
        if fused is None:
            fused = paged
        elif fused and not paged:
            raise ValueError(
                f"{cfg.name}: fused spatial execution streams decode KV "
                "from the block-paged pool; needs paged=True")
        self.fused = fused
        # chip-granular sub-mesh partitions (§3.4 second granularity,
        # docs/PARTITIONS.md): "chip" forces every prefill task onto a
        # disjoint (prefill sub-mesh, decode sub-mesh) split with a KV
        # handoff at migration; "auto" lets the scheduler's combined-table
        # argmin pick per task; "tile" (default) keeps the single-mesh
        # fused/serial paths untouched.
        if partition not in ("tile", "chip", "auto"):
            raise ValueError(f"partition={partition!r}: want tile|chip|auto")
        self.partition = partition
        splits: List[SubMeshSplit] = []
        if partition in ("chip", "auto"):
            if not paged and partition == "chip":
                raise ValueError(
                    f"{cfg.name}: chip-granular partitions hand KV off "
                    "through the block-paged pool; needs paged=True")
            splits = carve_submeshes(devs) if paged else []
            if partition == "chip" and not splits:
                raise ValueError(
                    "partition='chip' needs >= 2 jax devices to carve "
                    f"sub-meshes from (have {len(devs)}); run under "
                    "XLA_FLAGS=--xla_force_host_platform_device_count=8 "
                    "or use partition='auto' to fall back to tile")
        self._chip_enabled = bool(splits)
        self._decode_sharding = None
        # the scheduler's contention estimates must match the execution
        # mode: serial dispatches never co-locate phases spatially
        sched = replace(sched, fused=fused)
        self.scheduler = SLOScheduler(cfg, self.est, slo, sched)
        self.scheduler.obs = self.obs
        # pre-build one execution state per partition (§3.4.2) so _switch
        # selects among real execution states, not just numbers: fused
        # executables for the tile half, pjit pairs for the chip half
        self.rm = ResourceManager(
            self.est.hw, sched.unit_quantum,
            builder=self._build_fused_executable if fused else None,
            chip_splits=[s.key for s in splits],
            chip_builder=(functools.partial(self._build_chip_executable,
                                            splits=splits)
                          if splits else None))
        # the scheduler may only propose partitions this table pre-built
        # (fused mode additionally searches them under the fused-cycle
        # objective); _switch asserts the contract held
        self.scheduler.split_candidates = [
            (p.prefill_units, p.decode_units) for p in self.rm.tile_entries]
        if self._chip_enabled:
            # the combined table: the fused-objective search prices chip
            # entries (no co-location contention + handoff) against tile
            # entries (Eq. 2 contention) — disaggregation-vs-sharing as a
            # table argmin
            self.scheduler.partition_table = self.rm.partitions
        # online estimator refit (§3.2.2 closed loop): refit=False pins
        # the offline params; True/None builds a default OnlineRefitter;
        # an OnlineRefitter instance is used as-is. Refits only happen
        # when a driver feeds measured cycle durations through
        # record_cycle_actual (the frontend's virtual replay does).
        if refit is False:
            self.refitter: Optional[OnlineRefitter] = None
        elif isinstance(refit, OnlineRefitter):
            self.refitter = refit
        else:
            self.refitter = OnlineRefitter(cfg, self.est)
        self.refit_interval = refit_interval
        self._obs_since_refit = 0
        #: (kind, predicted, actual) per cycle with a recorded actual —
        #: same shape as the simulator's pred_actual log. Bounded so a
        #: long-running server can feed actuals forever without leaking
        #: (~1.5 days at 1 cycle/ms); consumers needing slices should
        #: ``list(...)`` it.
        self.pred_actual: Deque[Tuple[str, float, float]] = deque(
            maxlen=1 << 17)
        #: observation indices at which a refit was applied (params swap
        #: points, for before/after error attribution); positions are
        #: counted from the first observation and stay aligned with
        #: pred_actual until it wraps its maxlen
        self.refit_log: List[int] = []
        if paged:
            # unified device page pool: PagedKVPool block ids address these
            # pages directly; the trailing trash page absorbs masked writes
            self.cache = T.init_paged_cache(cfg, self.pool.n_blocks,
                                            page_size, dtype)
            self.max_blocks = self.pool.blocks_for(max_len)
            self._trash_page = self.pool.n_blocks
            self._host_tables = np.full((max_slots, self.max_blocks),
                                        self._trash_page, np.int32)
            self._tables_dirty = False
            #: device copies of the (sliced) host table, keyed by bucket
            #: width — re-uploaded only when ownership changes
            self._dev_tables: Dict[int, jax.Array] = {}
        else:
            # dense fallback: one fixed max_len decode row per slot
            self.cache = T.init_cache(cfg, max_slots, max_len, dtype)
        # slot bookkeeping
        self.slot_req: List[Optional[Request]] = [None] * max_slots
        self.tokens = jnp.zeros((max_slots, 1), jnp.int32)
        self.pos = jnp.zeros((max_slots,), jnp.int32)
        self.active = jnp.zeros((max_slots,), bool)
        self.pending: List[Request] = []
        self.finished: List[Request] = []
        self.outputs: Dict[int, List[int]] = {}
        #: in-flight resumable prefill (at most one batch at a time)
        self.ptask: Optional[PrefillTask] = None
        #: streaming hook: called as on_token(req, token, now) for every
        #: emitted token (first token at migration, then one per decode
        #: iteration)
        self.on_token: Optional[Callable[[Request, int, float], None]] = None
        #: what the most recent step() actually executed — consumed by
        #: virtual-clock replay to charge exactly the work that ran
        self.last_prefill_tokens: int = 0
        #: of which, tokens served from shared prefix pages (the cycle's
        #: prefill started at this context offset — estimator charging)
        self.last_reused_tokens: int = 0
        self.last_decode: Optional[DecodeWork] = None
        #: True when the last step ran the fused spatial cycle (replay then
        #: charges the Eq. 2 co-located max, not the serial sum)
        self.last_fused: bool = False
        #: config_id of the pre-built executable the last fused cycle ran
        self.last_fused_exec: Optional[int] = None
        #: True when the last step ran a chip-granular (disjoint sub-mesh)
        #: cycle; handoff_tokens > 0 on the cycle whose finished prefill
        #: re-sharded its pages across the interconnect
        self.last_chip: bool = False
        self.last_handoff_tokens: int = 0
        if self._chip_enabled:
            # ``devs`` bound above when the split table was carved
            self._global_sharding = submesh_param_sharding(chip_mesh(devs))
            #: params replicated per sub-mesh, device_put lazily and cached
            #: by placement (each split reuses its sides' copies)
            self._mesh_params: Dict[object, object] = {}
            #: shard_map'd steps for state replicated on several devices,
            #: keyed by (raw step, placement)
            self._mesh_steps: Dict[Tuple[Callable, object], Callable] = {}
            #: prefill-side staging page pool: chip tasks scatter prompt KV
            #: here (resident on the prefill sub-mesh); transfer_pages
            #: re-shards written pages into self.cache at migration
            self.cache_p = T.init_paged_cache(cfg, self.pool.n_blocks,
                                              page_size, dtype)
            # decode-side state starts homed on the global mesh (tile
            # semantics: every chip co-resident); chip cycles re-home it
            self._home_decode(self._global_sharding)
        #: SLO watchdog (resilience.guard.SLOGuard), consulted in step();
        #: None runs ungoverned — deadline misses and dispatch failures
        #: surface to the caller untouched
        self.guard = guard
        if guard is not None:
            guard.attach(self)
        #: tenant layer (serving.tenancy.TenancyController,
        #: docs/MULTITENANCY.md): the frontend gates admissions through
        #: it, the scheduler's slack sort gains a credit-tier bias, and
        #: preemption picks its victim within the lowest-credit tenant.
        #: None (default) keeps every path byte-identical to the
        #: single-tenant engine.
        self.tenancy = config.tenancy
        if self.tenancy is not None:
            self.tenancy.attach(self)
            if self.tenancy.credit_enabled:
                self.scheduler.priority = self.tenancy.tier

    def _build_fused_executable(self, part) -> FusedExecutable:
        """ResourceManager builder: one fused-step launcher per quantized
        PartitionConfig, its decode_share a static jit argument (compiled
        lazily per activation shape; switching never recompiles)."""
        fn = functools.partial(_fused_step, cfg=self.cfg,
                               decode_share=round(part.decode_share, 6))
        return FusedExecutable(part.config_id, part.decode_share, fn)

    def _build_chip_executable(self, part, *, splits) -> ChipExecutable:
        """ResourceManager chip builder: one pjit pair per chip split —
        the prefill layer-group step bound (by input placement) to the
        prefill sub-mesh and the decode iteration to the decode sub-mesh.
        Each entry owns its jit wrappers, so switching entries never
        evicts another entry's compiled executables (lowering is lazy per
        activation shape, as for the tile half)."""
        split = find_split(splits, part.prefill_chips, part.decode_chips)
        assert split is not None, part
        p_sharding = submesh_param_sharding(split.prefill_mesh)
        d_sharding = submesh_cache_sharding(split.decode_mesh)
        return ChipExecutable(
            part.config_id, split, p_sharding, d_sharding,
            functools.partial(_mesh_jit(_prefill_group_paged_impl,
                                        p_sharding), cfg=self.cfg),
            functools.partial(_mesh_jit(_decode_iteration_impl, d_sharding,
                                        donate_argnums=(1,)),
                              cfg=self.cfg))

    def _placed(self, impl: Callable, sharding, module_jit: Callable,
                donate_argnums=()) -> Callable:
        """The step ``impl`` for state on ``sharding``: the module-level
        jit ``module_jit`` unless chip-enabled serving placed the state on
        several devices, then a cached :func:`_mesh_jit` of it."""
        if (not self._chip_enabled or sharding is None
                or len(sharding.device_set) == 1):
            return module_jit
        key = (impl, sharding)
        fn = self._mesh_steps.get(key)
        if fn is None:
            fn = self._mesh_steps[key] = _mesh_jit(
                impl, sharding, donate_argnums=donate_argnums)
        return fn

    # -- sub-mesh placement (chip-enabled serving only) ------------------
    def _params_for(self, sharding):
        """The model params replicated onto ``sharding``, cached per
        placement — the resident per-sub-mesh copies of the pre-configured
        execution states."""
        if not self._chip_enabled or sharding is None:
            return self.params
        p = self._mesh_params.get(sharding)
        if p is None:
            p = jax.tree.map(lambda a: jax.device_put(a, sharding),
                             self.params)
            self._mesh_params[sharding] = p
        return p

    def _home_decode(self, sharding) -> None:
        """Re-home the decode-side device state (page pool, slot tokens /
        positions / active mask) onto ``sharding``: the decode sub-mesh of
        the current chip entry, or the global mesh for tile-granular and
        serial cycles. No-op when already there."""
        if not self._chip_enabled or self._decode_sharding == sharding:
            return
        put = functools.partial(jax.device_put, device=sharding)
        self.cache = jax.tree.map(put, self.cache)
        self.tokens = put(self.tokens)
        self.pos = put(self.pos)
        self.active = put(self.active)
        self._dev_tables.clear()
        self._decode_sharding = sharding

    def _home_task(self, task: PrefillTask, sharding) -> None:
        """Home an in-flight prefill task's device state onto ``sharding``
        (the current chip entry's prefill sub-mesh, or the global mesh for
        tile tasks under chip-enabled serving)."""
        if not self._chip_enabled or task.sharding == sharding:
            return
        task.x = jax.device_put(task.x, sharding)
        task.positions = jax.device_put(task.positions, sharding)
        task.lengths = jax.device_put(task.lengths, sharding)
        if task.page_map is not None:
            task.page_map = jax.device_put(task.page_map, sharding)
        if task.granularity == "chip":
            put = functools.partial(jax.device_put, device=sharding)
            self.cache_p = jax.tree.map(put, self.cache_p)
        task.sharding = sharding

    def _to_host(self, x: jax.Array) -> np.ndarray:
        """``x`` on the host. Every device->host read of serving goes
        through here: the host waits until the device has computed ``x``
        and copied it back, so each read is counted
        (``EngineStats.host_syncs``) and spanned (``engine.readback``).
        Reading a recently read array again is free and counts nothing."""
        for i, (dev, host) in enumerate(self._host_copies):
            if dev is x:
                del self._host_copies[i]
                self._host_copies.append((dev, host))
                return host
        self.stats.host_syncs += 1
        with phase("engine.readback"):
            out = np.asarray(x)
        self._host_copies.append((x, out))
        return out

    # -- device block tables (paged mode) -------------------------------
    def _sync_tables(self) -> None:
        """Re-export the pool's block tables in slot order. Ownership moves
        (migrate / preempt / finish) are table edits only — the pages
        themselves never move on device. Only DECODE-phase slots are
        mapped: a slot mid-prefill must stay on the trash page, or the
        decode iteration's unconditional per-slot KV write (driven by the
        slot's stale pos/tokens) would poison the pages its new occupant
        is concurrently scattering prompt KV into."""
        self._host_tables = self.pool.device_block_table(
            [r.rid if r is not None and r.phase == Phase.DECODE else None
             for r in self.slot_req],
            self.max_blocks, fill=self._trash_page)
        self._dev_tables.clear()
        self._tables_dirty = False

    def _device_tables(self, n_b: int) -> jax.Array:
        """The first ``n_b`` table columns on device, uploaded lazily and
        reused across iterations until ownership changes (or, under
        chip-enabled serving, until the decode state re-homes — the cache
        is cleared on both events, so the key stays the bucket width)."""
        bt = self._dev_tables.get(n_b)
        if bt is None:
            bt = jnp.asarray(self._host_tables[:, :n_b])
            if self._chip_enabled and self._decode_sharding is not None:
                bt = jax.device_put(bt, self._decode_sharding)
            self._dev_tables[n_b] = bt
        return bt

    def _decode_block_bucket(self, ctxs_ran: Tuple[int, ...]) -> int:
        """Max live page count across the slots that run, rounded up to a
        power of two: the paged kernel's grid depth. Bucketing bounds
        decode recompiles to O(log max_blocks) executables while the
        streamed pages still track live context."""
        need = -(-max(ctxs_ran) // self.page_size) if ctxs_ran else 1
        b = 1
        while b < need:
            b <<= 1
        return max(1, min(b, self.max_blocks))

    # -- request ingress ------------------------------------------------
    def submit(self, req: Request, prompt_tokens: np.ndarray):
        # a request's pool footprint (prompt + output) is invariant across
        # preemption/resume, so an oversized request can be rejected here
        # instead of spinning unadmittable in the queue forever
        footprint = req.prompt_len + max(req.output_len, 1)
        if self.pool.blocks_for(footprint) > self.pool.n_blocks:
            raise ValueError(
                f"request {req.rid} needs {footprint} KV tokens; the pool "
                f"holds {self.pool.n_blocks * self.pool.block_size}")
        req.phase = Phase.QUEUED
        req._prompt = np.asarray(prompt_tokens, np.int32)   # type: ignore
        self.pending.append(req)
        if self.tenancy is not None:
            self.tenancy.track(req)
        if self.obs.enabled:
            self.obs.requests_submitted.inc()
            self.obs.spans.mark(req.rid, "submit", req.arrival,
                                prompt_len=req.prompt_len,
                                output_len=req.output_len)

    def _free_slot(self) -> Optional[int]:
        for i, r in enumerate(self.slot_req):
            if r is None:
                return i
        return None

    def _pending_meta(self) -> List[Tuple[int, float, int]]:
        return [(r.rid, r.arrival, r.prompt_len) for r in self.pending]

    def _apply_reorder(self, order: Optional[List[int]]) -> None:
        """Honor the scheduler's Decision.reorder (slack-sorted rids)."""
        if not order or len(self.pending) < 2:
            return
        pos = {rid: i for i, rid in enumerate(order)}
        self.pending.sort(key=lambda r: pos.get(r.rid, len(pos)))

    def _switch(self, resources) -> None:
        """Swap partitions, counting only actual re-configurations."""
        if self.fused:
            # the split search is defined over the prebuilt executable
            # table (both granularities); a proposal not on it means
            # scheduler and resource manager have drifted apart (nearest()
            # would silently snap it, masking the bug — fail loudly)
            assert self.rm.on_table(resources), (
                f"scheduler proposed off-table partition "
                f"({resources.granularity}: {resources.prefill_units}, "
                f"{resources.decode_units}, chips "
                f"{resources.prefill_chips}+{resources.decode_chips}); "
                f"table quantum={self.rm.quantum}")
        before = self.rm.current.config_id
        part = self.rm.switch(resources)
        if part.config_id != before:
            self.stats.reconfigs += 1
        self.buffer.write(lambda s: (
            setattr(s.resources, "prefill_units", part.prefill_units),
            setattr(s.resources, "decode_units", part.decode_units),
            setattr(s.resources, "config_id", part.config_id),
            setattr(s.resources, "granularity", part.granularity),
            setattr(s.resources, "prefill_chips", part.prefill_chips),
            setattr(s.resources, "decode_chips", part.decode_chips)))

    # -- prefill engine ---------------------------------------------------
    def _resume_len(self, r: Request) -> int:
        """Tokens the prefill must cover: prompt plus any prefix generated
        before a preemption (resumed requests recompute their KV over it)."""
        return r.prompt_len + len(self.outputs.get(r.rid, []))

    def _seq_tokens(self, r: Request) -> np.ndarray:
        """The token ids the prefill must cover (prompt + resume prefix)."""
        seq = r._prompt                                     # type: ignore
        prefix = self.outputs.get(r.rid)
        if prefix:
            seq = np.concatenate([seq, np.asarray(prefix, np.int32)])
        return seq

    def _written_tokens(self, r: Request) -> np.ndarray:
        """The token ids whose KV actually sits in ``r``'s pages: prompt +
        generated output minus the last sampled token (its KV is written
        by the *next* decode iteration)."""
        out = self.outputs.get(r.rid) or []
        if not out:
            return np.asarray(r._prompt, np.int32)          # type: ignore
        return np.concatenate(
            [r._prompt, np.asarray(out[:-1], np.int32)])    # type: ignore

    def _need_tokens(self, r: Request) -> int:
        """Pool reservation for a request: the full prompt (+ resume
        prefix) and output footprint, reserved at admission so decode can
        never over-commit the pool mid-flight."""
        return self._resume_len(r) + max(r.output_len - r.generated, 1)

    def _preempt_candidates(self, req: Request) -> List[Request]:
        """Decode slots eligible for eviction: strictly younger arrivals
        (priority order prevents preemption cycles)."""
        return [r for r in self.slot_req
                if r is not None and r.phase == Phase.DECODE
                and r.arrival > req.arrival]

    def _preempt_for(self, req: Request, now: float) -> bool:
        """KV pressure (§3.5.2): evict the lowest-priority decode slot —
        the strictly younger request with the latest arrival — freeing its
        pool pages and requeueing it with its generated prefix. With a
        credit-scoring tenancy layer attached, the victim is the youngest
        request *within the lowest-credit tenant* among the candidates
        (docs/MULTITENANCY.md): a misbehaving tenant loses its own decode
        progress before anyone else's."""
        victims = self._preempt_candidates(req)
        if not victims:
            return False
        if self.tenancy is not None and self.tenancy.credit_enabled:
            lo = min(self.tenancy.credit_of(v) for v in victims)
            pool = [v for v in victims
                    if self.tenancy.credit_of(v) <= lo + 1e-12]
            victim = max(pool, key=lambda r: r.arrival)
        else:
            victim = max(victims, key=lambda r: r.arrival)
        slot = victim._slot                                 # type: ignore
        self.pool.preempt(victim.rid)
        if self.paged:
            self._tables_dirty = True    # ownership moved back to the pool
        self.active = self.active.at[slot].set(False)
        self.slot_req[slot] = None
        victim.phase = Phase.QUEUED
        self.pending.append(victim)
        self.stats.preempted += 1
        if self.obs.enabled:
            self.obs.spans.mark(victim.rid, "preempt", now,
                                generated=float(victim.generated))
        D = self.buffer.state.decode
        if victim.rid in D.batch:
            D.batch.remove(victim.rid)
        self._drop_request_meta(victim.rid)
        return True

    def _admit_prefill(self, now: float) -> bool:
        """Form the next prompt batch from the pending queue, honoring the
        scheduler's slack-sorted reorder; on pool pressure, preempt before
        head-of-line blocking."""
        if self.ptask is not None or not self.pending:
            return False
        if self._free_slot() is None:        # saturated: skip the slack scan
            return False
        state = self.buffer.read()
        if len(self.pending) > 1:
            self._apply_reorder(
                self.scheduler.reorder_pending(state, now,
                                               self._pending_meta()))
        share = self.paged and self.share_prefix
        batch: List[Request] = []
        batch_hit: Optional[bool] = None
        while (self.pending and len(batch) < self.max_prefill_batch
               and self._free_slot() is not None):
            r = self.pending[0]
            need = self._need_tokens(r)
            if share:
                # homogeneous batches only: cache-hit requests take the
                # suffix-prefill path, misses take the plain path — mixing
                # them would pad misses to hit geometry (and vice versa),
                # perturbing the sharing-off numerics they must match
                _, m_toks, cow = self.pool.match_prefix(
                    self._seq_tokens(r))
                hit = (m_toks + (cow[1] if cow else 0)) > 0
                if batch_hit is not None and hit != batch_hit:
                    break
            if not self.pool.can_admit(need):
                if batch:
                    break
                # evict only if the eligible victims' blocks actually
                # cover the shortfall — never waste decode progress (a
                # victim's shared pages survive its preemption, so only
                # sole-referenced blocks count toward the shortfall)
                reclaimable = sum(
                    self.pool.reclaimable_blocks(v.rid)
                    for v in self._preempt_candidates(r))
                if (self.pool.blocks_for(need)
                        > self.pool.available_blocks + reclaimable):
                    break
                while (not self.pool.can_admit(need)
                       and self._preempt_for(r, now)):
                    pass
                if not self.pool.can_admit(need):
                    break
            slot = self._free_slot()
            self.pool.allocate(r.rid, need,
                               prompt_tokens=(self._seq_tokens(r)
                                              if share else None))
            if share and batch_hit is None:
                batch_hit = hit
            if r.prefill_start is None:
                r.prefill_start = now
            r.phase = Phase.PREFILL
            self.pending.pop(0)
            batch.append(r)
            self.slot_req[slot] = r
            r._slot = slot                                  # type: ignore
            self.buffer.state.prefill.queue_wait[r.rid] = now - r.arrival
            if self.obs.enabled:
                # a request with a generated prefix re-enters after a
                # preemption: its span resumes instead of re-admitting
                self.obs.spans.mark(
                    r.rid,
                    "resume" if self.outputs.get(r.rid) else "admit",
                    now, queue_s=max(0.0, now - r.arrival))
        if not batch:
            return False

        lens = [self._resume_len(r) for r in batch]
        if share and batch_hit:
            self.ptask = self._build_shared_task(batch, lens)
        else:
            # on a TPU, padded to the kernels' block so prefill and the
            # fused cycle run the Pallas kernels
            plen = prefill_length(max(lens))
            toks = np.zeros((len(batch), plen), np.int32)
            for i, r in enumerate(batch):
                toks[i, :lens[i]] = self._seq_tokens(r)
            lengths = jnp.asarray(lens)
            x = _embed_prompt(self.params, jnp.asarray(toks), cfg=self.cfg)
            positions = jnp.arange(plen)[None, :]
            tmp_cache = page_map = None
            if self.paged:
                # route each request's prompt blocks to its pooled pages so
                # layer groups scatter KV in place (no handoff copy)
                self._tables_dirty = True
                ps = self.page_size
                page_map = np.full((len(batch), -(-plen // ps)),
                                   self._trash_page, np.int32)
                for i, r in enumerate(batch):
                    blocks = self.pool.table(r.rid).blocks[
                        :-(-lens[i] // ps)]
                    page_map[i, :len(blocks)] = blocks
                page_map = jnp.asarray(page_map)
            else:
                # temporary per-batch cache (migrated slot-wise at handoff)
                tmp_cache = T.init_cache(self.cfg, len(batch), self.max_len,
                                         jax.tree.leaves(self.cache)[0].dtype)
            self.ptask = PrefillTask(batch, x, positions, lengths, tmp_cache,
                                     n_tokens=int(sum(lens)),
                                     page_map=page_map)
        task = self.ptask
        self.stats.prefill_tokens += task.n_tokens
        self.stats.reused_prefill_tokens += task.reused_tokens
        if task.reused_tokens:
            self.stats.prefix_hits += len(batch)
            if self.obs.enabled:
                self.obs.prefix_hits.inc(len(batch))
                self.obs.prefix_reused_tokens.inc(task.reused_tokens)
        P = self.buffer.state.prefill
        P.active_rid = batch[0].rid
        P.started_at = now
        P.layers_done = 0
        P.total_layers = self.cfg.n_layers
        P.n_tokens = self.ptask.n_tokens
        P.n_waiting = len(self.pending)
        if self.obs.enabled:
            for r in batch:
                t = self.pool.table(r.rid)
                if t is not None and t.shared_tokens:
                    self.obs.spans.mark(r.rid, "prefix_hit", now,
                                        reused=float(t.shared_tokens))
        if self._chip_enabled and self.partition != "tile":
            # pin the task's granularity for its lifetime (pages scatter
            # into one pool consistently): forced under partition="chip",
            # the scheduler's combined-table argmin under "auto". A guard
            # degraded to partition="tile" keeps new tasks off the chip
            # path even though the split table stays built.
            self.ptask.granularity = (
                "chip" if self.partition == "chip"
                else self.scheduler.preferred_granularity(self.buffer.state))
        return True

    def _build_shared_task(self, batch: List[Request],
                           lens: List[int]) -> PrefillTask:
        """Build the PrefillTask for a batch whose every row hit the prefix
        index (docs/KV_SHARING.md): activations cover only each request's
        unshared suffix, positions start at the reuse boundary, and the
        page maps split into a read-only prefix gather and a suffix scatter
        that starts mid-page (after the copy-on-write tail, copied on
        device here before any group launches)."""
        ps = self.page_size
        self._tables_dirty = True
        tables = [self.pool.table(r.rid) for r in batch]
        reused = [t.shared_tokens for t in tables]
        s_lens = [ln - ru for ln, ru in zip(lens, reused)]
        assert all(s > 0 for s in s_lens), (s_lens, reused)
        n, sp = len(batch), max(s_lens)
        toks = np.zeros((n, sp), np.int32)
        positions = np.zeros((n, sp), np.int32)
        offsets = np.zeros((n,), np.int32)
        lp = max(-(-ru // ps) for ru in reused)
        prefix_map = np.full((n, lp), self._trash_page, np.int32)
        n_sc = max(-(-((ru % ps) + sp) // ps) for ru in reused)
        page_map = np.full((n, n_sc), self._trash_page, np.int32)
        cow_src: List[int] = []
        cow_dst: List[int] = []
        for i, r in enumerate(batch):
            ru = reused[i]
            toks[i, :s_lens[i]] = self._seq_tokens(r)[ru:]
            positions[i] = ru + np.arange(sp)
            offsets[i] = ru % ps
            blocks = tables[i].blocks
            prefix_map[i, :-(-ru // ps)] = blocks[:-(-ru // ps)]
            row = blocks[ru // ps:ru // ps + n_sc]
            page_map[i, :len(row)] = row
            for s_b, d_b in tables[i].cow_pairs:
                cow_src.append(s_b)
                cow_dst.append(d_b)
        if cow_src:
            # materialize COW tails across every repeat of every layer
            # BEFORE the first group scatter splices suffix KV into them
            src = jnp.asarray(np.asarray(cow_src, np.int32))
            dst = jnp.asarray(np.asarray(cow_dst, np.int32))
            for j in range(len(self.cfg.pattern)):
                leaf = self.cache["blocks"][j]
                leaf["k"] = _copy_pages(leaf["k"], src, dst)
                leaf["v"] = _copy_pages(leaf["v"], src, dst)
        x = _embed_prompt(self.params, jnp.asarray(toks), cfg=self.cfg)
        return PrefillTask(
            batch, x, jnp.asarray(positions), jnp.asarray(s_lens), None,
            n_tokens=int(sum(s_lens)), page_map=jnp.asarray(page_map),
            prefix_map=jnp.asarray(prefix_map),
            prefix_lens=jnp.asarray(np.asarray(reused, np.int32)),
            scatter_offsets=jnp.asarray(offsets),
            reused_tokens=int(sum(reused)))

    def _prefill_step(self, now: float) -> bool:
        """Launch ONE pattern-repeat group of the in-flight prefill, with a
        scheduling cycle before it (§3.3.1); migrate to decode when the
        last group completes. Decode iterations interleave between calls."""
        task = self.ptask
        if task is None:
            return False
        # ---- scheduling cycle between layer groups (§3.3.1) -----------
        with phase("engine.schedule"):
            state = self.buffer.read()
            decision = self.scheduler.schedule(state, now,
                                               self._pending_meta())
            self._apply_reorder(decision.reorder)
            self._switch(decision.resources)
        with phase("engine.prefill"):
            self._launch_prefill_group(task, now)
        return True

    def _launch_prefill_group(self, task: PrefillTask, now: float) -> None:
        """Launch ONE pattern-repeat group of ``task`` (serial dispatch —
        the fused cycle launches its group inside the fused executable
        instead) and migrate to decode when the last group completes."""
        if self.faults.enabled:
            self.faults.dispatch("prefill")
        rep = task.rep
        params = self.params
        if self._chip_enabled:
            # serial launches own the whole machine: tile semantics
            self._home_decode(self._global_sharding)    # paged scatter target
            self._home_task(task, self._global_sharding)
            params = self._params_for(self._global_sharding)
        p_slice = jax.tree.map(lambda a: a[rep], params["blocks"],
                               is_leaf=lambda a: hasattr(a, "shape"))
        if self.paged and task.prefix_map is not None:
            # shared-prefix suffix prefill: gather reused prefix KV from
            # the page pool, attend prefix+suffix, splice the suffix KV
            # back at each row's in-page offset (docs/KV_SHARING.md)
            rep_ix = jnp.int32(rep)
            task.x, kv_entries = _prefill_group_shared(
                p_slice, task.x, task.positions, self.cache["blocks"],
                task.prefix_map, task.prefix_lens, rep_ix, cfg=self.cfg)
            pm, off = task.page_map, task.scatter_offsets
            for j, (k_e, v_e) in enumerate(kv_entries):
                leaf = self.cache["blocks"][j]
                leaf["k"] = _scatter_suffix_group_pages(
                    leaf["k"], k_e, pm, off, rep_ix)
                leaf["v"] = _scatter_suffix_group_pages(
                    leaf["v"], v_e, pm, off, rep_ix)
        elif self.paged:
            step = self._placed(_prefill_group_paged_impl, task.sharding,
                                _prefill_group_paged)
            task.x, kv_entries = step(p_slice, task.x, task.positions,
                                      cfg=self.cfg)
            pm = task.page_map
            rep_ix = jnp.int32(rep)
            for j, (k_e, v_e) in enumerate(kv_entries):
                leaf = self.cache["blocks"][j]
                leaf["k"] = _scatter_group_pages(leaf["k"], k_e, pm, rep_ix)
                leaf["v"] = _scatter_group_pages(leaf["v"], v_e, pm, rep_ix)
        else:
            c_slice = jax.tree.map(lambda a: a[rep], task.tmp_cache["blocks"],
                                   is_leaf=lambda a: hasattr(a, "shape"))
            task.x, new_entries = _prefill_group(
                p_slice, task.x, task.positions, c_slice, task.lengths,
                cfg=self.cfg, repeat=rep)
            task.entries.append(new_entries)
        self._prefill_group_done(task, now)

    def _prefill_group_done(self, task: PrefillTask, now: float) -> None:
        """Post-group bookkeeping shared by the serial and fused paths:
        advance the group cursor, publish progress, and migrate to decode
        when the last group completed."""
        task.rep += 1
        self.stats.prefill_cycles += 1
        self.last_prefill_tokens = task.n_tokens
        self.last_reused_tokens = task.reused_tokens
        P = self.buffer.state.prefill
        P.layers_done = task.rep * len(self.cfg.pattern)
        for r in task.batch:
            r.prefill_done_layers = P.layers_done
            if self.obs.enabled:
                self.obs.spans.mark(r.rid, "prefill_group", now,
                                    rep=float(task.rep - 1))
        if task.rep >= self.cfg.n_pattern_repeats:
            with phase("engine.migrate"):
                self._finish_prefill(task, now)
            self.ptask = None

    def _finish_prefill(self, task: PrefillTask, now: float) -> None:
        """Migrate the finished batch to decode. Paged mode: the KV already
        sits in pooled pages, so the handoff is pure block-table ownership
        (pool.migrate) — no device copy. Chip-granular tasks additionally
        re-shard the written pages from the prefill sub-mesh's staging pool
        onto the decode sub-mesh first (the jax.device_put KV handoff the
        estimator charges at ici_bw). Dense fallback: copy each request's
        ``max_len`` cache row into its decode slot.

        Requests cancelled mid-prefill (deadline hit while the batch's
        device arrays were in flight — ``cancel_reason`` set) are finalized
        here instead of migrating: pages freed, no token emitted, no
        handoff blocks moved."""
        params = (self._params_for(task.sharding)
                  if task.sharding is not None else self.params)
        first_tokens = self._to_host(
            _final_logits(params, task.x, task.lengths, cfg=self.cfg))
        if task.granularity == "chip" and self._chip_enabled:
            lens = self._to_host(task.lengths)
            live = [r for r in task.batch if r.cancel_reason is None]
            blocks: List[int] = []
            tokens_moved = 0
            for i, r in enumerate(task.batch):
                if r.cancel_reason is not None:
                    continue
                blocks.extend(self.pool.written_blocks(r.rid, int(lens[i])))
                tokens_moved += int(lens[i])
            # transient cross-mesh handoff failures retry with backoff
            # (the injected fault hook raises before any page moves, so a
            # retry re-attempts the identical transfer); an exhausted
            # budget unwinds the whole batch back to the queue and lets
            # the guard leave the chip rung
            fault = self.faults.handoff_hook() if self.faults.enabled \
                else None
            attempt = 0
            while True:
                try:
                    self.cache = transfer_pages(
                        self.cache_p, self.cache, blocks,
                        self._decode_sharding, fault=fault)
                    break
                except HandoffError:
                    attempt += 1
                    self.stats.handoff_retries += 1
                    if attempt > self.handoff_policy.max_retries:
                        self._abort_prefill_task(task, now)
                        # clear before notifying: the guard's chip
                        # degrade aborts any live chip task, and this
                        # one is already torn down
                        self.ptask = None
                        if self.guard is not None:
                            self.guard.on_handoff_exhausted(self, now)
                        return
                    self.faults.charge_delay(
                        self.handoff_policy.backoff(attempt))
            self.stats.handoffs += len(live)
            self.last_handoff_tokens += tokens_moved
            if self.obs.enabled:
                for i, r in enumerate(task.batch):
                    if r.cancel_reason is None:
                        self.obs.spans.mark(r.rid, "handoff", now,
                                            tokens=float(lens[i]))
        P = self.buffer.state.prefill
        if self.paged:
            # migrated slots flip PREFILL->DECODE: re-map their pages into
            # the device tables before the next decode iteration
            self._tables_dirty = True
        for i, r in enumerate(task.batch):
            slot = r._slot                                  # type: ignore
            if r.cancel_reason is not None:
                # deadline hit mid-prefill: finalize the deferred cancel
                # at the group boundary — free pages, emit nothing
                self.pool.free(r.rid)
                self.slot_req[slot] = None
                self._cancelled(r, now, r.cancel_reason)
                continue
            if not self.paged:
                for j in range(len(self.cfg.pattern)):
                    for key in self.cache["blocks"][j]:
                        stacked = jnp.stack(
                            [task.entries[rep][j][key][i]
                             for rep in range(len(task.entries))])
                        self.cache["blocks"][j][key] = _write_slot(
                            self.cache["blocks"][j][key], stacked, slot)
            tok = int(first_tokens[i])
            prefix = self.outputs.get(r.rid)
            if prefix is None:
                self.outputs[r.rid] = [tok]
                r.first_token_time = now
            else:                         # resumed after preemption
                prefix.append(tok)
            r.generated = len(self.outputs[r.rid])
            r.token_times.append(now)
            r.phase = Phase.DECODE
            self.tokens = self.tokens.at[slot, 0].set(tok)
            self.pos = self.pos.at[slot].set(r.prompt_len + r.generated - 1)
            self.active = self.active.at[slot].set(True)
            self.pool.migrate(r.rid)
            if self.share_prefix and self.paged:
                # index the freshly written pages so concurrent prompts
                # can share them before this request even finishes
                self.pool.register_prefix(r.rid, self._written_tokens(r))
            self.stats.migrated += 1
            if self.obs.enabled:
                self.obs.spans.mark(r.rid, "migrate", now)
                if prefix is None:
                    self.obs.spans.mark(r.rid, "first_token", now)
            self.buffer.write(lambda s, rid=r.rid: s.ready_for_decode.append(
                (rid, self.outputs[rid][-1])))
            if self.on_token is not None:
                self.on_token(r, tok, now)
            if (r.generated >= r.output_len
                    or r.prompt_len + r.generated >= self.max_len):
                self._finish_request(r, slot, now)
        # prefill engine is idle until the next admission
        P.active_rid = None
        P.layers_done = 0
        P.n_tokens = 0

    def _finish_request(self, r: Request, slot: int, now: float) -> None:
        r.phase = Phase.FINISHED
        r.finish_time = now
        self.finished.append(r)
        if self.tenancy is not None:
            # recompute the tenant's credit from this outcome (SLO
            # violation + TTFT tail EWMAs, docs/MULTITENANCY.md)
            self.tenancy.on_finish(r, self.slo)
        if self.obs.enabled:
            self.obs.requests_finished.inc()
            self.obs.spans.mark(r.rid, "finish", now,
                                generated=float(r.generated))
        if self.share_prefix and self.paged:
            # extend the prefix index over the decode-written pages before
            # releasing them (ref-0 indexed pages stay cached for hits)
            self.pool.register_prefix(r.rid, self._written_tokens(r))
        self.pool.free(r.rid)
        if self.paged:
            self._tables_dirty = True
        self.slot_req[slot] = None
        self.active = self.active.at[slot].set(False)
        self._drop_request_meta(r.rid)

    def _drop_request_meta(self, rid: int) -> None:
        """Prune per-request shared-buffer entries so a long-running online
        server does not grow without bound."""
        s = self.buffer.state
        s.prefill.queue_wait.pop(rid, None)
        s.decode.out_tokens.pop(rid, None)
        s.decode.decode_time.pop(rid, None)
        s.ready_for_decode = [e for e in s.ready_for_decode if e[0] != rid]

    # -- resilience (docs/RESILIENCE.md) ----------------------------------
    def cancel_request(self, r: Request, now: float,
                       why: str = "deadline") -> None:
        """Cancel a live request (deadline miss, operator action): release
        its pool pages through the same table-ownership edits preemption
        uses and retire it with ``Phase.CANCELLED``. A request whose
        prefill batch is in flight is only *marked* — its device arrays
        are part of the batch, so the removal happens at the next layer-
        group boundary (``_finish_prefill``) instead of mid-dispatch."""
        if r.phase in (Phase.FINISHED, Phase.CANCELLED):
            return
        if r.phase == Phase.QUEUED:
            if r in self.pending:
                self.pending.remove(r)
        elif r.phase == Phase.PREFILL:
            r.cancel_reason = why
            return
        else:                                   # DECODE: live slot
            slot = r._slot                                  # type: ignore
            self.pool.free(r.rid)
            if self.paged:
                self._tables_dirty = True
            self.slot_req[slot] = None
            self.active = self.active.at[slot].set(False)
            D = self.buffer.state.decode
            if r.rid in D.batch:
                D.batch.remove(r.rid)
        self._cancelled(r, now, why)

    def _cancelled(self, r: Request, now: float, why: str) -> None:
        """Terminal cancel bookkeeping shared by the immediate and the
        deferred (mid-prefill) paths."""
        r.phase = Phase.CANCELLED
        r.cancel_reason = why
        r.finish_time = now
        self.stats.cancelled += 1
        if self.tenancy is not None:
            self.tenancy.on_cancel(r, why)
        if self.obs.enabled:
            self.obs.requests_cancelled.labels(why=why).inc()
            self.obs.spans.mark(r.rid, "cancel", now, why=why)
        self._drop_request_meta(r.rid)

    def _abort_prefill_task(self, task: PrefillTask, now: float) -> None:
        """Unwind an in-flight prefill batch without migrating: release
        every request's pages and requeue the survivors (they re-prefill
        from scratch deterministically, like a preemption); requests
        already marked for cancellation end here. The caller clears
        ``self.ptask``."""
        for r in task.batch:
            slot = r._slot                                  # type: ignore
            self.slot_req[slot] = None
            self.active = self.active.at[slot].set(False)
            if r.cancel_reason is not None:
                self.pool.free(r.rid)
                self._cancelled(r, now, r.cancel_reason)
                continue
            self.pool.preempt(r.rid)
            r.phase = Phase.QUEUED
            self.pending.append(r)
            if self.obs.enabled:
                self.obs.spans.mark(r.rid, "abort", now,
                                    rep=float(task.rep))
            self._drop_request_meta(r.rid)
        self.stats.prefill_aborts += 1
        if self.paged:
            self._tables_dirty = True
        P = self.buffer.state.prefill
        P.active_rid = None
        P.layers_done = 0
        P.n_tokens = 0

    def set_fused(self, flag: bool) -> None:
        """Flip fused spatial co-execution on/off at a cycle boundary (the
        guard's fused→serial rung). The scheduler's contention model must
        follow the execution mode, so both flip together."""
        if flag == self.fused:
            return
        if flag and not self.paged:
            raise ValueError("fused execution needs the paged cache")
        self.fused = flag
        self.scheduler.sc = replace(self.scheduler.sc, fused=flag)

    def set_cache_mode(self, paged: bool, now: float) -> None:
        """Swap between the block-paged pool and the dense fixed-slot
        reference layout (the guard's paged→dense rung, and its restore).
        The two layouts share no device state, so all in-flight work is
        unwound first: the prefill batch aborts back to the queue and
        every decode slot is preempted with its generated prefix — both
        re-enter through normal admission and re-prefill deterministically.
        """
        if paged == self.paged:
            return
        assert not self.fused, "degrade fused→serial before paged→dense"
        if paged and not T.supports_paged_cache(self.cfg):
            raise ValueError(f"{self.cfg.name}: cannot restore the paged "
                             "cache (pattern needs pure ATTN)")
        if self.ptask is not None:
            self._abort_prefill_task(self.ptask, now)
            self.ptask = None
        for slot, r in enumerate(self.slot_req):
            if r is None:
                continue
            self.pool.preempt(r.rid)
            self.active = self.active.at[slot].set(False)
            self.slot_req[slot] = None
            r.phase = Phase.QUEUED
            self.pending.append(r)
            self.stats.preempted += 1
            if self.obs.enabled:
                self.obs.spans.mark(r.rid, "preempt", now,
                                    generated=float(r.generated))
            D = self.buffer.state.decode
            if r.rid in D.batch:
                D.batch.remove(r.rid)
            self._drop_request_meta(r.rid)
        if self.share_prefix:
            # the device pages behind the prefix index are about to be
            # reinitialized: drop the index and cached pages. All tables
            # were just unwound, so no page has multiple live readers —
            # flush_shared would refuse otherwise (docs/RESILIENCE.md)
            self.pool.flush_shared()
        dtype = jax.tree.leaves(self.cache)[0].dtype
        self.paged = paged
        if paged:
            self.cache = T.init_paged_cache(self.cfg, self.pool.n_blocks,
                                            self.page_size, dtype)
            self.max_blocks = self.pool.blocks_for(self.max_len)
            self._trash_page = self.pool.n_blocks
            self._host_tables = np.full((self.max_slots, self.max_blocks),
                                        self._trash_page, np.int32)
            self._tables_dirty = False
            self._dev_tables = {}
        else:
            self.cache = T.init_cache(self.cfg, self.max_slots, self.max_len,
                                      dtype)
        if self._chip_enabled:
            # fresh arrays have default placement: re-home lazily on the
            # next cycle that pins one
            self._decode_sharding = None

    def check_invariants(self) -> None:
        """Crash-on-corruption audit, run by chaos tests after every cycle:
        pool block ownership is a partition of allocated pages; every pool
        owner is a live request (no dead-request leaks — fault-injected
        pool-squeeze phantoms are accounted); slot bookkeeping agrees with
        request phases; live spans are well-ordered."""
        self.pool.check_invariants()
        owners = set(self.pool.owners())
        holders = {r.rid for r in self.slot_req if r is not None}
        if self.ptask is not None:
            holders |= {r.rid for r in self.ptask.batch}
        phantoms = self.faults.phantom_rids() if self.faults.enabled \
            else set()
        leaked = owners - holders - phantoms
        assert not leaked, (
            f"pool pages leaked: rids {sorted(leaked)} own blocks but are "
            f"neither in a slot, the prefill batch, nor fault phantoms")
        act = np.asarray(self.active)
        for slot, r in enumerate(self.slot_req):
            if r is None:
                assert not bool(act[slot]), f"empty slot {slot} active"
                continue
            assert getattr(r, "_slot", None) == slot, \
                f"slot {slot} holds rid {r.rid} with _slot={r._slot}"
            assert r.phase in (Phase.PREFILL, Phase.DECODE), \
                f"slot {slot} rid {r.rid} in phase {r.phase}"
            assert r.rid in owners, \
                f"slot {slot} rid {r.rid} owns no pool pages"
            assert bool(act[slot]) == (r.phase == Phase.DECODE), (
                f"slot {slot} rid {r.rid}: active={bool(act[slot])} but "
                f"phase={r.phase}")
        if self.obs.enabled:
            self.obs.spans.check_invariants()

    # -- decode engine ----------------------------------------------------
    def _decode_cycle(self, now: float) -> bool:
        if not bool(np.any(self._to_host(self.active))):
            return False
        # ---- scheduling cycle before the iteration (§3.3.1) ------------
        with phase("engine.schedule"):
            state = self.buffer.read()
            decision = self.scheduler.schedule(state, now,
                                               self._pending_meta())
            self._apply_reorder(decision.reorder)
            if decision.pause_decode:
                self.stats.paused_cycles += 1
                self.buffer.state.decode.paused = True
                return False
            self.buffer.state.decode.paused = False
            self._switch(decision.resources)
        if self.faults.enabled:
            self.faults.dispatch("decode")

        params = self.params
        if self._chip_enabled:
            # decode-only cycles run wherever the decode state already
            # lives (the global mesh at init, the last chip entry's
            # decode sub-mesh between chip tasks): re-homing is left to
            # the cycle kinds that require a specific placement, so the
            # page pool never ping-pongs sub-mesh <-> global mesh across
            # task boundaries — interconnect traffic the estimator's
            # handoff charge does not cover
            params = self._params_for(self._decode_sharding)
        act_np = self._to_host(self.active)
        pos_np = self._to_host(self.pos)
        # live context per slot that runs this iteration — the bytes the
        # cache stream actually touches (paged) / the estimator charges
        ctxs_ran = tuple(int(p) + 1 for p, a in zip(pos_np, act_np) if a)
        n_ran = len(ctxs_ran)
        if self.paged:
            with phase("engine.tables"):
                if self._tables_dirty:
                    self._sync_tables()
                n_b = self._decode_block_bucket(ctxs_ran)
                tables = self._device_tables(n_b)
            streamed = (n_b * self.page_size * self.max_slots
                        // max(n_ran, 1),) * n_ran
            with phase("engine.decode"):
                step = self._placed(_decode_iteration_impl,
                                    self._decode_sharding, _decode_iteration,
                                    donate_argnums=(1,))
                next_tokens, self.cache = step(
                    params, self.cache, self.tokens, self.pos, self.active,
                    tables, cfg=self.cfg)
        else:
            streamed = (self.max_len * self.max_slots
                        // max(n_ran, 1),) * n_ran
            with phase("engine.decode"):
                next_tokens, self.cache = _decode_iteration(
                    params, self.cache, self.tokens, self.pos, self.active,
                    cfg=self.cfg)
        self._finish_decode_iteration(next_tokens, act_np, ctxs_ran,
                                      streamed, now)
        return True

    def _finish_decode_iteration(self, next_tokens, act_np, ctxs_ran,
                                 streamed, now: float) -> None:
        """Post-iteration bookkeeping shared by the serial and fused
        paths: advance slot state, stream tokens, retire finished
        requests, publish DecodeStatus, and record what ran."""
        n_ran = len(ctxs_ran)
        self.tokens = next_tokens
        self.pos = self.pos + act_np.astype(np.int32)
        self.stats.decode_iterations += 1
        nt = self._to_host(next_tokens)[:, 0]
        with phase("engine.emit"):
            D = self.buffer.state.decode
            for slot, r in enumerate(self.slot_req):
                if r is None or r.phase != Phase.DECODE:
                    continue
                tok = int(nt[slot])
                self.outputs[r.rid].append(tok)
                r.generated += 1
                r.token_times.append(now)
                D.out_tokens[r.rid] = r.generated
                D.decode_time[r.rid] = now - (
                    r.first_token_time if r.first_token_time is not None
                    else now)
                if self.on_token is not None:
                    self.on_token(r, tok, now)
                if (r.generated >= r.output_len
                        or r.prompt_len + r.generated >= self.max_len):
                    self._finish_request(r, slot, now)
            live = [x for x in self.slot_req
                    if x is not None and x.phase == Phase.DECODE]
            D.batch = [x.rid for x in live]
            D.ctx_tokens = int(sum(x.prompt_len + x.generated for x in live))
            D.mean_context = int(D.ctx_tokens / len(live)) if live else 0
            self.last_decode = DecodeWork(
                n_ran, max(int(sum(ctxs_ran) / max(n_ran, 1)), 1), ctxs_ran,
                streamed)

    # -- fused engine (spatial co-execution, §3.5) ------------------------
    def _fused_cycle(self, now: float) -> bool:
        """One fused engine cycle: the current prefill layer group and one
        decode iteration launch as a single pre-built executable whose
        fused schedule splits grid slots by the active partition's
        ``decode_share``. One scheduling cycle covers both phases; the
        §3.3.3 pause branch still borrows the whole machine for prefill
        alone (serial group launch)."""
        task = self.ptask
        with phase("engine.schedule"):
            state = self.buffer.read()
            decision = self.scheduler.schedule(state, now,
                                               self._pending_meta())
            self._apply_reorder(decision.reorder)
            self._switch(decision.resources)
        if decision.pause_decode:
            self.stats.paused_cycles += 1
            self.buffer.state.decode.paused = True
            with phase("engine.prefill"):
                self._launch_prefill_group(task, now)
            return True
        self.buffer.state.decode.paused = False
        ex = self.rm.executable()
        if self.faults.enabled:
            self.faults.dispatch("fused")

        params = self.params
        if self._chip_enabled:
            # tile-granular fused cycle: every chip co-resident
            self._home_decode(self._global_sharding)
            self._home_task(task, self._global_sharding)
            params = self._params_for(self._global_sharding)
        act_np = self._to_host(self.active)
        pos_np = self._to_host(self.pos)
        ctxs_ran = tuple(int(p) + 1 for p, a in zip(pos_np, act_np) if a)
        n_ran = len(ctxs_ran)
        with phase("engine.tables"):
            if self._tables_dirty:
                self._sync_tables()
            n_b = self._decode_block_bucket(ctxs_ran)
            tables = self._device_tables(n_b)
        streamed = (n_b * self.page_size * self.max_slots
                    // max(n_ran, 1),) * n_ran
        with phase("engine.prefill"):
            fn = ex.fn
            if self._chip_enabled:
                # a chip-enabled server's tile cycles run on the global mesh
                fn = functools.partial(
                    self._placed(_fused_step_impl, self._global_sharding,
                                 _fused_step, donate_argnums=(1,)),
                    cfg=self.cfg, decode_share=round(ex.decode_share, 6))
            task.x, next_tokens, self.cache = fn(
                params, self.cache, task.x, task.positions,
                task.page_map, self.tokens, self.pos, self.active,
                tables, rep=task.rep)
        self.last_fused = True
        self.last_fused_exec = ex.config_id
        self.stats.fused_cycles += 1

        # decode-side bookkeeping first, prefill-side after: migration
        # happens in _prefill_group_done, so slots that finish prefill
        # this cycle take their first decode step next cycle
        self._finish_decode_iteration(next_tokens, act_np, ctxs_ran,
                                      streamed, now)
        self._prefill_group_done(task, now)
        return True

    # -- chip engine (disjoint sub-mesh co-execution, §3.4) ---------------
    def _chip_cycle(self, now: float) -> bool:
        """One chip-granular engine cycle: the prefill layer group and the
        decode iteration dispatch onto DISJOINT sub-meshes — concurrent
        spatial execution with no shared chip (async dispatch overlaps
        them for real; the estimator charges the max of the sides). One
        scheduling cycle covers both phases, restricted to the chip half
        of the table; the §3.3.3 pause never fires (decode owns its chips
        — nothing to borrow). Prefill scatters prompt KV into the
        prefill-mesh staging pool; the finished prompt's pages re-shard
        onto the decode mesh in _finish_prefill."""
        task = self.ptask
        with phase("engine.schedule"):
            state = self.buffer.read()
            decision = self.scheduler.schedule(
                state, now, self._pending_meta(), granularity="chip")
            self._apply_reorder(decision.reorder)
            self._switch(decision.resources)
        ex = self.rm.executable()
        assert isinstance(ex, ChipExecutable), (
            f"chip task but executable {type(ex).__name__} for config "
            f"{self.rm.current}")

        # prefill side first, so both sub-meshes run concurrently. Both
        # chip seams fire before any device work: the prefill dispatch
        # advances task.x, so a later raise would double-apply the layer
        # group when the cycle retries at the same ``rep``.
        if self.faults.enabled:
            self.faults.dispatch("chip_prefill")
            if bool(np.any(self._to_host(self.active))):
                self.faults.dispatch("chip_decode")
        with phase("engine.prefill"):
            self._home_task(task, ex.p_sharding)
            p_params = self._params_for(ex.p_sharding)
            rep = task.rep
            p_slice = jax.tree.map(lambda a: a[rep], p_params["blocks"],
                                   is_leaf=lambda a: hasattr(a, "shape"))
            task.x, kv_entries = ex.prefill_fn(p_slice, task.x,
                                               task.positions)
            pm = task.page_map
            rep_ix = jnp.int32(rep)
            for j, (k_e, v_e) in enumerate(kv_entries):
                leaf = self.cache_p["blocks"][j]
                leaf["k"] = _scatter_group_pages(leaf["k"], k_e, pm, rep_ix)
                leaf["v"] = _scatter_group_pages(leaf["v"], v_e, pm, rep_ix)

        # decode side on its own sub-mesh (when any slot is live)
        act_np = self._to_host(self.active)
        did_decode = bool(np.any(act_np))
        if did_decode:
            self._home_decode(ex.d_sharding)
            d_params = self._params_for(ex.d_sharding)
            pos_np = self._to_host(self.pos)
            ctxs_ran = tuple(int(p) + 1
                             for p, a in zip(pos_np, act_np) if a)
            n_ran = len(ctxs_ran)
            with phase("engine.tables"):
                if self._tables_dirty:
                    self._sync_tables()
                n_b = self._decode_block_bucket(ctxs_ran)
                tables = self._device_tables(n_b)
            streamed = (n_b * self.page_size * self.max_slots
                        // max(n_ran, 1),) * n_ran
            with phase("engine.decode"):
                next_tokens, self.cache = ex.decode_fn(
                    d_params, self.cache, self.tokens, self.pos,
                    self.active, tables)
        self.last_chip = True
        self.stats.chip_cycles += 1
        if did_decode:
            self._finish_decode_iteration(next_tokens, act_np, ctxs_ran,
                                          streamed, now)
        self._prefill_group_done(task, now)
        return True

    # -- online estimator refit (§3.2.2 closed loop) ----------------------
    def last_cycle_observation(self) -> Optional[CycleObservation]:
        """What the most recent step() executed, as the estimator-facing
        CycleObservation — the record virtual-clock replay prices
        (serving.frontend.estimator_cycle_cost) and the OnlineRefitter
        fits against. None when the step ran no device work."""
        w = self.last_decode
        if w is None and not self.last_prefill_tokens:
            return None
        R = self.buffer.state.resources
        if self.last_chip:
            return CycleObservation(
                "chip", self.last_prefill_tokens,
                max(R.prefill_units, 1), max(R.decode_units, 1),
                w.batch if w is not None else 0,
                max(w.mean_context, 1) if w is not None else 1,
                (tuple(w.streamed) or None) if w is not None else None,
                handoff_tokens=self.last_handoff_tokens)
        if self.last_fused and w is not None and self.last_prefill_tokens:
            return CycleObservation(
                "fused", self.last_prefill_tokens,
                max(R.prefill_units, 1), max(R.decode_units, 1),
                max(w.batch, 1), max(w.mean_context, 1),
                tuple(w.streamed) or None,
                reused_tokens=self.last_reused_tokens)
        return CycleObservation(
            "serial", self.last_prefill_tokens,
            R.prefill_units, R.decode_units,
            w.batch if w is not None else 0,
            max(w.mean_context, 1) if w is not None else 1,
            (tuple(w.streamed) or None) if w is not None else None,
            reused_tokens=self.last_reused_tokens)

    def record_cycle_actual(self, actual_s: float) -> None:
        """Feed the measured duration of the cycle the last step() ran.

        Drivers that know real time call this once per step — the online
        frontend does it on every virtual-clock replay cycle; a hardware
        deployment would pass device wall time. Each call logs one
        (kind, predicted, actual) pair and hands the observation to the
        OnlineRefitter; nothing refits until the engine's refit interval
        elapses inside step()."""
        obs = self.last_cycle_observation()
        if obs is None or actual_s <= 0:
            return
        pred = predict_cycle(self.est, self.cfg, obs)
        self.pred_actual.append((obs.kind, pred, actual_s))
        if self.guard is not None:
            self.guard.on_cycle_actual(self, obs.kind, pred, actual_s)
        self.record_cycle_duration(actual_s)
        if self.refitter is not None:
            self.refitter.observe(obs, actual_s)
            self._obs_since_refit += 1

    def record_cycle_duration(self, actual_s: float) -> None:
        """Attach the measured duration of the cycle the last step() ran
        to its trace event (``CycleEvent.actual_s``) and nothing else: the
        estimator, the refitter and the guard do not see it, unlike
        :meth:`record_cycle_actual`."""
        if self.obs.enabled and self._open_cycle is not None:
            self.obs.complete_cycle(self._open_cycle, actual_s)
            self._open_cycle = None

    def _maybe_refit(self) -> None:
        """Owned by step(): every ``refit_interval`` recorded cycles, ask
        the refitter for better params and swap them into the engine AND
        the scheduler via PerfEstimator.with_params — both must price
        cycles with the same model, or split decisions and replay charges
        diverge."""
        if (self.refitter is None
                or self._obs_since_refit < self.refit_interval):
            return
        self._obs_since_refit = 0
        with phase("engine.refit"):
            new = self.refitter.refit()
            self.stats.refits_rejected = self.refitter.refits_rejected
            if new is not None:
                self.est = self.est.with_params(new)
                self.scheduler.est = self.est
                self.refitter.est = self.est
                self.stats.refits += 1
                self.refit_log.append(len(self.pred_actual))

    # -- observability (docs/OBSERVABILITY.md) ----------------------------
    def _record_cycle_event(self, now: float) -> None:
        """Append the cycle that step() just executed to the structured
        trace: kind, the partition descriptor that ran, predicted
        duration (the actual arrives via record_cycle_actual), handoff
        bytes, KV-pool occupancy, and the scheduler's decision rationale.
        No-op when the step ran no device work."""
        self._open_cycle = None
        rec = self.last_cycle_observation()
        if rec is None:
            return
        R = self.buffer.state.resources
        d = self.scheduler.last_decision
        ev = CycleEvent(
            t=now, kind=rec.kind,
            predicted_s=predict_cycle(self.est, self.cfg, rec),
            config_id=R.config_id, granularity=R.granularity,
            prefill_units=R.prefill_units, decode_units=R.decode_units,
            prefill_chips=R.prefill_chips, decode_chips=R.decode_chips,
            prefill_tokens=self.last_prefill_tokens,
            decode_batch=(self.last_decode.batch
                          if self.last_decode is not None else 0),
            handoff_tokens=self.last_handoff_tokens,
            handoff_bytes=int(analytics.kv_transfer_bytes(
                self.cfg, self.last_handoff_tokens))
            if self.last_handoff_tokens else 0,
            kv_used_blocks=self.pool.allocated_blocks,
            kv_total_blocks=self.pool.n_blocks,
            kv_occupancy=self.pool.occupancy(),
            kv_fragmentation=self.pool.fragmentation(),
            paused=self.buffer.state.decode.paused,
            reason=d.reason if d is not None else "")
        self.obs.record_cycle(ev)
        self._open_cycle = ev

    # -- main loop --------------------------------------------------------
    def step(self, now: float) -> bool:
        """One engine cycle at time ``now``: admit newly-pending prompts,
        launch one prefill layer group, run one decode iteration — as a
        single fused spatial dispatch when both phases are co-resident
        (and the engine runs fused), as serial back-to-back dispatches
        otherwise. Returns True if any engine did work. Drive this from an
        online frontend (serving.frontend) or via :meth:`run` for offline
        batches."""
        with phase("engine.step"):
            if self.guard is not None:
                self.guard.before_step(self, now)
            try:
                did = self._step_inner(now)
            except DispatchError as e:
                if self.guard is None:
                    raise
                # the cycle's work is lost but no state was mutated (every
                # dispatch seam raises before device arrays change); the
                # guard counts the failure and degrades once failures
                # persist
                self.guard.on_dispatch_failure(self, e, now)
                did = True
            if self.obs.enabled:
                self._record_cycle_event(now)
            return did

    def _step_inner(self, now: float) -> bool:
        self._maybe_refit()
        if self.faults.enabled:
            self.faults.begin_cycle(self)
        self.last_prefill_tokens = 0
        self.last_reused_tokens = 0
        self.last_decode = None
        self.last_fused = False
        self.last_chip = False
        self.last_handoff_tokens = 0
        with phase("engine.admit"):
            did_admit = self._admit_prefill(now)
        if self.ptask is not None and self.ptask.granularity == "chip":
            # chip-pinned task: every layer group runs on its sub-mesh,
            # with the decode iteration concurrent on the disjoint one
            return self._chip_cycle(now) or did_admit
        if (self.fused and self.ptask is not None
                and self.ptask.prefix_map is None
                and bool(np.any(self._to_host(self.active)))):
            return self._fused_cycle(now) or did_admit
        did_p = self._prefill_step(now)
        did_d = self._decode_cycle(now)
        return did_admit or did_p or did_d

    @property
    def idle(self) -> bool:
        """No queued, in-flight, or decoding work remains."""
        return (not self.pending and self.ptask is None
                and all(r is None for r in self.slot_req))

    def run(self, max_cycles: int = 10_000) -> Dict[int, List[int]]:
        """Drive both engines until all submitted requests finish."""
        t0 = time.perf_counter()
        cycles = 0
        while cycles < max_cycles:
            cycles += 1
            now = time.perf_counter() - t0
            if not self.step(now) and self.idle:
                break
        self.pool.check_invariants()
        return self.outputs
