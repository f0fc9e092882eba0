"""Residency-backend architecture: one orchestrator, five state substrates.

The paper's §V GPU-CPU co-processing story has a single control flow —
plan each update batch on the host (Alg. 4), pack it into a transfer
format, ship it, execute the reordered incremental workflow (Alg. 1), and
overlap batch-t+1 planning with batch-t execution — but the *residency* of
the historical state (which memory tier holds h/a/nct, and how rows reach
the compute) is a deployment decision.  This module separates the two:

                          ┌──────────────────────────┐
     UpdateBatch stream → │    StreamOrchestrator    │  plan/pack/hysteresis,
                          │  plan(t+1) on host while │  honest StreamStats
                          │  the device executes (t) │  timing, refresh cadence
                          └────────────┬─────────────┘
                                       │  StateBackend protocol
                                       │  (plan / dispatch / flush / sync)
        ┌──────────────────┬───────────┴──────┬─────────────────────┐
  DeviceBackend      OffloadBackend     ShardBackend      ShardedOffloadBackend
  state in HBM,      state host-        state row-sharded  per-shard host row
  one fused donated  resident; compact  [S, rows_per+1,·]  blocks; per layer a
  L-layer step per   affected rows      blocks; one psum   compact [halo|local]
  batch (PackedPlan) staged per layer   of frontier rows   workspace staged per
                     (paper §V-B)       per layer          shard (HBM footprint
                                                           O(affected), not O(V))

All four backends execute the *same* layer implementation
(:func:`repro.core.incremental._layer_body`) and are fed by the same Alg.-4
planner (:func:`repro.core.affected.build_plan`) through one packing layer
(:mod:`repro.core.affected`'s ``PackedPlan``/``ShardedPlan``/remap tables).
The public engine classes (``RTECEngine``, ``OffloadedRTECEngine``,
``ShardedRTECEngine``, ``ShardedOffloadRTECEngine``) are thin facades over
``StreamOrchestrator`` + one backend — no engine owns a plan/overlap loop.

Protocol contract (what ``StreamOrchestrator`` relies on):

* ``plan(g_old, g_new, batch)`` is host-only and **value-independent** (it
  may read graph structure and batch indices, never state values), so it can
  run while the devices still execute the previous batch;
* ``dispatch(prep)`` is as asynchronous as the substrate allows; any work it
  must defer to keep the next plan off the critical path is completed by
  ``flush()`` (a no-op for fully-async device substrates);
* ``flush()`` + ``jax.block_until_ready(sync_arrays())`` is a full barrier:
  after it, ``embeddings`` reflects every dispatched batch.

A fifth substrate, :class:`ChunkedBackend`, executes batches by chunked
constrained re-computation through the §V-C scheduler (host-resident state,
device residency bounded by ``chunk_size``) — the fallback when a batch's
affected subgraph exceeds what the staging substrates can hold at once.

Serving (ISSUE 6): every substrate additionally implements the Serving API
(``snapshot_rows`` / ``changed_rows``, documented on :class:`StateBackend`),
which :class:`repro.serve.frontend.ServingFrontend` uses to answer
embedding reads pinned to historical versions bitwise-consistently while
updates continue to stream.  Construct any of the five through
:func:`repro.serve.create_engine`.
"""
from __future__ import annotations

import abc
import dataclasses
import threading
import time
import warnings
from functools import partial
from typing import Any, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.core.affected import (
    BatchPlan,
    BucketHysteresis,
    FusionConfig,
    FusionWindow,
    HybridLayerPlan,
    LayerPlan,
    PackedPlan,
    ShardedPlan,
    build_packed_plan,
    build_plan,
    final_write_rows,
    hybrid_plan,
    pack_plan,
    packed_nbytes,
    remap_compact,
    shard_plan,
    shard_rows,
)
from repro.core.full import full_forward
from repro.core.incremental import (
    fused_stream_step,
    hybrid_layer_step_fn,
    incremental_layer,
    sharded_step_fn,
    with_scratch,
)
from repro.core.operators import GNNModel, Params
from repro.core.policy import ExecutionPolicy, PlanCostEstimate
from repro.graph.csr import CSRGraph
from repro.graph.streaming import UpdateBatch
from repro.serve.hotcache import CacheStats, HotRowCache
from repro.serve.staging import HostStagingPipeline, StagingStats, StagingTicket


# ====================================================================== #
# Stats (shared by every engine facade)
# ====================================================================== #
@dataclasses.dataclass
class BatchStats:
    inc_edges: int
    full_edges: int
    out_vertices: int
    plan_time_s: float
    exec_time_s: float
    graph_time_s: float
    #: execution shape the batch ran as (ISSUE 7): "incremental" (the
    #: backend's native dispatch), "chunked" (orchestrator-level §V-C
    #: subset recompute) or "full" (refresh over the post-batch graph).
    #: Always "incremental" without an ExecutionPolicy.
    mode: str = "incremental"
    #: the policy cost model's raw edge-work for the chosen mode — the
    #: deterministic quantity the adversarial CI gate compares against the
    #: best fixed mode.  0 when no policy is attached.
    est_edges: int = 0
    #: the chosen mode's *weighted* cost (``PolicyDecision.costs[mode]``) —
    #: the decision surface itself.  Plans are mode-independent, so the
    #: adaptive policy's stream total is ≤ every fixed mode's by
    #: construction; the CI wall-clock-free "policy matches the best fixed
    #: mode" gate compares these.  0.0 when no policy is attached.
    est_cost: float = 0.0
    #: batch-window fusion (ISSUE 9): how many logical batches shared this
    #: batch's device dispatch.  1 = dispatched alone (the serial path);
    #: k ≥ 2 on every constituent of a fused window (the window's one
    #: dispatch time is charged to its first constituent, the others
    #: report ``exec_time_s == 0``).
    fused_window: int = 1
    #: spans inside the batch (repro.obs), filled by
    #: ``StreamOrchestrator.apply_batch``: ``pack_time_s`` is the part of
    #: ``plan_time_s`` spent packing the plan (``repro/plan/pack``),
    #: ``hook_time_s`` the part of ``exec_time_s`` spent in the ``on_plan``
    #: hook (``repro/undo_capture``); ``h2d_bytes`` counts the packed plan's
    #: bytes copied host→device; ``compiles`` / ``compile_time_s`` are the
    #: executables built inside the batch's graph, plan and exec spans.
    pack_time_s: float = 0.0
    hook_time_s: float = 0.0
    h2d_bytes: int = 0
    compiles: int = 0
    compile_time_s: float = 0.0

    @property
    def edges_processed(self) -> int:
        return self.inc_edges + self.full_edges


@dataclasses.dataclass
class StreamStats:
    """Aggregate result of a pipelined ``apply_stream`` run.

    ``wall_s`` is honest end-to-end time including the final flush + device
    sync; per-batch ``exec_time_s`` entries are dispatch-only (execution
    overlaps the next batch's planning, so per-batch completion is
    unobservable without breaking the pipeline).

    Per-phase overlap accounting (ISSUE 5): ``prefetch_hits`` counts the
    batches whose Alg.-4 plan completed with **no intervening backend
    barrier** (verified via ``StateBackend.barrier_epoch``, so a substrate
    that silently flushes per batch scores 0) — ``len(batches) - 1`` for a
    healthy pipeline, deterministic, CI-gated; ``staged_bytes`` is the byte
    volume moved through the backend's :class:`HostStagingPipeline`
    (deterministic, CI-gated ceiling); ``sync_wait_s`` is caller time
    blocked on host staging (gather waits + drain barriers) and
    ``compute_s`` is caller time blocked on the device (D2H waits) —
    timing telemetry, never gated.  All four stay zero for backends
    without a staging pipeline.

    Read-side serving fields (ISSUE 6): populated only by
    :class:`repro.serve.frontend.ServingFrontend` — ``reads_served`` /
    ``reads_rejected`` / ``staleness_batches`` are deterministic counters
    (CI-gated exactly in the smoke bench).  All default to zero so
    pre-serving baselines and gates keep passing.

    Device hot-row cache counters (ISSUE 8): ``cache_hit_rows`` /
    ``cache_miss_rows`` / ``cache_evictions`` mirror the backend's
    :class:`repro.serve.hotcache.CacheStats` over the stream —
    deterministic (admission and eviction are value-independent plan-time
    decisions), CI-gated exactly on the hub_burst smoke cell.  All three
    stay zero for backends without a cache (or with ``enabled=False``).

    Halo-exchange counters (ISSUE 10): ``comms_halo_rows_sent`` /
    ``comms_halo_bytes`` mirror the sharded backends'
    :class:`CommsStats` over the stream — plan-derived and deterministic
    (under ``halo="ppermute"`` they count per-consumer deliveries; under
    ``"psum"`` the global-frontier broadcast volume, the ceiling the CI
    gate compares against).  Both stay zero for unsharded backends.

    ``StreamStats`` is the single result type for *every* entry point
    (``apply_stream``, the serving front-end, the bench cells);
    :meth:`as_dict` is the normalized scalar view the benchmark emitters
    consume instead of ad-hoc attribute plucking."""

    batches: List[BatchStats]
    wall_s: float
    plan_s: float  # total host planning time (hidden behind device exec)
    staged_bytes: int = 0
    prefetch_hits: int = 0
    sync_wait_s: float = 0.0
    compute_s: float = 0.0
    # read-side serving metrics (repro.serve.frontend)
    reads_served: int = 0
    reads_rejected: int = 0
    staleness_batches: int = 0
    # device hot-row cache counters (repro.serve.hotcache)
    cache_hit_rows: int = 0
    cache_miss_rows: int = 0
    cache_evictions: int = 0
    # batch-window fusion counters (ISSUE 9): deterministic — which batches
    # fuse depends only on the update stream's plan footprints
    fusion_windows: int = 0
    fused_batches: int = 0
    fusion_fallbacks: int = 0
    # halo-exchange counters (ISSUE 10): plan-derived, deterministic
    comms_halo_rows_sent: int = 0
    comms_halo_bytes: int = 0

    @property
    def mean_batch_s(self) -> float:
        return self.wall_s / max(1, len(self.batches))

    def as_dict(self) -> dict:
        """Normalized scalar view: every entry point reports through these
        keys (benchmarks/common.py ``emit_stream_stats`` renders them).

        THE documented key namespace — benchmarks and
        ``benchmarks/check_regression.py`` consume only these names
        (pinned by ``STREAM_STAT_KEYS`` and tests/test_hotcache.py, so a
        rename can never silently drop a CI gate):

        ==========================  =========================================
        key                         meaning (D = deterministic, CI-gateable)
        ==========================  =========================================
        n_batches                   batches in the stream (D)
        wall_s                      honest end-to-end wall, incl. final sync
        plan_s                      host planning time (hidden behind exec)
        mean_batch_s                wall_s / n_batches
        inc_edges                   signed incremental records executed (D)
        full_edges                  constrained full-recompute edges (D)
        out_vertices                rows written, summed over layers (D)
        staged_bytes                bytes through HostStagingPipeline (D)
        prefetch_hits               plans built with no backend barrier (D)
        sync_wait_s                 caller time blocked on host staging
        compute_s                   caller time blocked on the device
        reads_served                frontend reads answered (D)
        reads_rejected              frontend reads shed by admission (D)
        staleness_batches           versions behind head at serve time (D)
        cache_hit_rows              rows served from device cache slots (D)
        cache_miss_rows             rows staged from host (D)
        cache_evictions             cache capacity evictions (D)
        fusion_windows              fused multi-batch dispatches (D)
        fused_batches               batches absorbed into fused windows (D)
        fusion_fallbacks            windows broken up by overlap/policy (D)
        comms_halo_rows_sent        halo rows moved between shards (D)
        comms_halo_bytes            halo bytes moved between shards (D)
        policy_incremental_batches  batches decided incremental (D)
        policy_chunked_batches      batches decided chunked-subset (D)
        policy_full_batches         batches decided full recompute (D)
        policy_edges                cost model's raw edge-work estimate (D)
        policy_cost                 chosen-mode weighted cost total (D)
        ==========================  =========================================
        """
        return {
            "n_batches": len(self.batches),
            "wall_s": self.wall_s,
            "plan_s": self.plan_s,
            "mean_batch_s": self.mean_batch_s,
            "inc_edges": sum(b.inc_edges for b in self.batches),
            "full_edges": sum(b.full_edges for b in self.batches),
            "out_vertices": sum(b.out_vertices for b in self.batches),
            "staged_bytes": self.staged_bytes,
            "prefetch_hits": self.prefetch_hits,
            "sync_wait_s": self.sync_wait_s,
            "compute_s": self.compute_s,
            "reads_served": self.reads_served,
            "reads_rejected": self.reads_rejected,
            "staleness_batches": self.staleness_batches,
            "cache_hit_rows": self.cache_hit_rows,
            "cache_miss_rows": self.cache_miss_rows,
            "cache_evictions": self.cache_evictions,
            # batch-window fusion counters (ISSUE 9): deterministic, gated
            # exactly on the high-rate smoke cell.  All three stay zero
            # without a FusionConfig (or with window=1/enabled=False).
            "fusion_windows": self.fusion_windows,
            "fused_batches": self.fused_batches,
            "fusion_fallbacks": self.fusion_fallbacks,
            # halo-exchange counters (ISSUE 10): plan-derived (never read
            # from device), deterministic, gated exactly on the 8-shard
            # smoke cell.  Zero for unsharded backends.
            "comms_halo_rows_sent": self.comms_halo_rows_sent,
            "comms_halo_bytes": self.comms_halo_bytes,
            # adaptive-execution-policy accounting (ISSUE 7): per-mode
            # decision counts and the cost model's raw edge-work, both
            # deterministic (CI-gated exactly in the adversarial suite).
            # Without a policy every batch is "incremental" and
            # policy_edges stays 0.
            "policy_incremental_batches": self._mode_count("incremental"),
            "policy_chunked_batches": self._mode_count("chunked"),
            "policy_full_batches": self._mode_count("full"),
            "policy_edges": sum(b.est_edges for b in self.batches),
            "policy_cost": sum(b.est_cost for b in self.batches),
        }

    def _mode_count(self, mode: str) -> int:
        return sum(1 for b in self.batches if b.mode == mode)


#: the complete documented ``StreamStats.as_dict`` key namespace (see the
#: table in :meth:`StreamStats.as_dict`) — consumers assert against this
#: instead of hard-coding strings, so a rename fails loudly
STREAM_STAT_KEYS: Tuple[str, ...] = tuple(
    StreamStats([], 0.0, 0.0).as_dict().keys()
)


@dataclasses.dataclass(frozen=True)
class CommsStats:
    """Cumulative halo-exchange volume of a sharded backend (ISSUE 10).

    Plan-derived — computed from the value-independent per-consumer
    delivery sets, never measured off the device — so the counters are
    bit-stable and CI-gateable.  ``halo_rows_sent`` counts (row, consumer)
    deliveries: under ``halo="ppermute"`` each halo row is counted once
    per shard that actually gathers it; under ``"psum"`` once per shard
    on the mesh (the broadcast ceiling).  ``halo_bytes`` weights each
    delivery by the rows' staged payload (old+new views where both
    cross)."""

    halo_rows_sent: int = 0
    halo_bytes: int = 0


def _resolve_backend_comms(comms, use_pallas_delta: Optional[bool],
                           name: str):
    """Canonicalize a sharded backend's comms knobs: the typed
    :class:`~repro.dist.sharding.CommsConfig` is the documented surface;
    the loose ``use_pallas_delta=`` kwarg survives as a deprecated alias
    that folds into it (None — the default — means "not passed")."""
    from repro.dist.sharding import CommsConfig

    if use_pallas_delta is not None:
        warnings.warn(
            f"{name}(use_pallas_delta=...) is a deprecated alias; pass "
            f"comms=CommsConfig(use_pallas_delta=...) (or create the "
            f"engine with create_engine and EngineConfig.comms) instead",
            DeprecationWarning, stacklevel=3)
        if comms is None:
            return CommsConfig(use_pallas_delta=use_pallas_delta)
        return dataclasses.replace(comms, use_pallas_delta=use_pallas_delta)
    return comms if comms is not None else CommsConfig()


# ====================================================================== #
# StateBackend protocol
# ====================================================================== #
class StateBackend(abc.ABC):
    """Execution substrate under a :class:`StreamOrchestrator`.

    A backend owns the residency of the per-layer historical state
    (h, a, nct) and knows how to (1) turn a batch into a substrate-specific
    prepared plan (host-only, value-independent), (2) dispatch that plan,
    and (3) surface the state back (``embeddings``/``sync_arrays``).  The
    returned prep object must expose ``n_inc_edges``/``n_full_edges``/
    ``n_out_rows`` counters for :class:`BatchStats` accounting."""

    model: GNNModel
    L: int

    @property
    def overlap_capable(self) -> bool:
        """Whether ``apply_stream``'s plan/execute overlap is supported."""
        return True

    @abc.abstractmethod
    def plan(self, g_old: CSRGraph, g_new: CSRGraph, batch: UpdateBatch) -> Any:
        """Host-only, value-independent planning (may overlap execution)."""

    @abc.abstractmethod
    def dispatch(self, prep: Any) -> None:
        """Execute a prepared plan (as asynchronously as the substrate allows)."""

    #: bumped by every ``flush()``: the orchestrator uses it to verify a
    #: batch's plan really was built with no intervening backend barrier
    #: (the ``prefetch_hits`` counter would otherwise be tautological)
    barrier_epoch: int = 0

    def flush(self) -> None:
        """Complete any work ``dispatch`` deferred (a barrier: bump the
        epoch even when there is nothing to complete)."""
        self.barrier_epoch += 1

    def staging_snapshot(self) -> Optional[StagingStats]:
        """Snapshot of the backend's host-staging counters (None when the
        substrate has no :class:`HostStagingPipeline`)."""
        return None

    def cache_snapshot(self) -> Optional[CacheStats]:
        """Snapshot of the backend's device hot-row-cache counters (None
        when the substrate has no :class:`repro.serve.hotcache.HotRowCache`
        attached)."""
        return None

    def comms_snapshot(self) -> Optional[CommsStats]:
        """Snapshot of the backend's halo-exchange counters (None for
        unsharded substrates — no inter-shard traffic exists)."""
        return None

    # ------------------------------------------------------------------ #
    # Serving API (ISSUE 6): versioned snapshot reads.
    #
    # The version/consistency contract the serving front-end
    # (:class:`repro.serve.frontend.ServingFrontend`) builds on:
    #
    # * a **version** is one flushed batch — after ``flush()`` +
    #   ``block_until_ready(sync_arrays())`` the substrate's state *is* the
    #   post-batch-v state, bitwise;
    # * ``snapshot_rows(rows)`` is a consistent host gather of final-layer
    #   embedding rows at such a boundary.  It must not inject work into a
    #   live staging pipeline: the host-resident substrates flush first
    #   (a no-op at a boundary — the worker queue is already drained), so
    #   reads never contend with the async worker's pristine-gather
    #   schedule;
    # * ``changed_rows(prep)`` names, *before dispatch*, every final-layer
    #   row the prepared plan may write.  Snapshotting exactly these rows
    #   pre-dispatch yields a per-version undo record, which is how the
    #   front-end answers a read pinned to version v bitwise-equal to the
    #   post-batch-v state after later batches have run.
    # ------------------------------------------------------------------ #
    def snapshot_rows(self, rows: np.ndarray) -> np.ndarray:
        """Host gather of final-layer embedding rows (consistent at a
        version boundary).  Substrates override with an O(len(rows)) path;
        this fallback materializes the full embedding table."""
        return np.asarray(self.embeddings)[np.asarray(rows, np.int64)]

    def changed_rows(self, prep: Any) -> np.ndarray:
        """Global ids of final-layer rows ``dispatch(prep)`` may write
        (value-independent: derived from the plan, usable pre-dispatch)."""
        raise NotImplementedError(
            f"{type(self).__name__} does not expose plan write sets; "
            "versioned serving reads are unsupported on this substrate")

    # ------------------------------------------------------------------ #
    # Policy-execution primitives (ISSUE 7): the orchestrator-level
    # ExecutionPolicy runs chunked-subset and full-recompute batches on
    # *any* substrate through three generic state operations.  The caller
    # (StreamOrchestrator) flushes first, so implementations may assume no
    # deferred write-back is in flight.
    # ------------------------------------------------------------------ #
    @property
    def host_params(self) -> List[Params]:
        """Per-layer params as host-usable values (mesh backends override:
        their ``params`` are device-replicated)."""
        return self.params

    def chunk_scheduler(self):
        """The substrate's own §V-C scheduler, if it has one (ChunkedBackend)
        — lets the policy path share its reuse/transfer counters.  None →
        the orchestrator lazily creates a generic one."""
        return None

    def apply_feature_updates(self, rows: np.ndarray, vals: np.ndarray) -> None:
        """Persist a batch's layer-0 feature updates into the state."""
        raise NotImplementedError(
            f"{type(self).__name__} does not implement the policy "
            "execution primitives")

    def layer_input_host(self, l: int) -> np.ndarray:
        """Layer ``l``'s input embeddings (h^l) as a host ``[n, d]`` array
        (no scratch row) — what the chunked scheduler recomputes from."""
        raise NotImplementedError(
            f"{type(self).__name__} does not implement the policy "
            "execution primitives")

    def scatter_layer_rows(self, l: int, rows: np.ndarray, a_rows: np.ndarray,
                           nct_rows: np.ndarray, h_rows: np.ndarray) -> None:
        """Write one layer's recomputed (a, nct, h^{l+1}) rows back into the
        substrate's state at global ``rows``."""
        raise NotImplementedError(
            f"{type(self).__name__} does not implement the policy "
            "execution primitives")

    @abc.abstractmethod
    def sync_arrays(self) -> list:
        """Arrays to ``jax.block_until_ready`` at timed boundaries."""

    @abc.abstractmethod
    def refresh(self, graph: CSRGraph) -> None:
        """Full recomputation over ``graph`` and the *current* features."""

    @property
    @abc.abstractmethod
    def embeddings(self):
        """Final-layer embeddings for all n vertices."""

    @abc.abstractmethod
    def state_bytes(self) -> int:
        """Bytes of persistent cached state (all tiers)."""


# ====================================================================== #
# Policy execution payloads (ISSUE 7): when an ExecutionPolicy routes a
# batch away from the substrate's native incremental dispatch, the
# orchestrator carries one of these instead of a backend prep.  They expose
# the same n_inc_edges / n_full_edges / n_out_rows counters BatchStats reads.
# ====================================================================== #
@dataclasses.dataclass
class _PolicyChunkedPrep:
    """Chunked-subset recompute payload: the policy chose ``"chunked"``, so
    the orchestrator drives the §V-C scheduler over the plan's live out rows
    through the backend's policy-execution primitives (any substrate)."""

    plan: BatchPlan
    batch: UpdateBatch
    g_new: CSRGraph
    rows_per_layer: List[np.ndarray]  # live out_rows per layer (global ids)
    est: PlanCostEstimate

    @property
    def n_inc_edges(self) -> int:
        return 0  # no signed delta records execute in this mode

    @property
    def n_full_edges(self) -> int:
        return self.est.chunked_edges

    @property
    def n_out_rows(self) -> int:
        return int(sum(r.shape[0] for r in self.rows_per_layer))


@dataclasses.dataclass
class _PolicyFullPrep:
    """Full-recompute payload: the policy chose ``"full"`` — the batch runs
    as ``backend.refresh`` over the post-batch graph (after the feature
    scatter), exactly the refresh-cadence path."""

    batch: UpdateBatch
    g_new: CSRGraph
    est: PlanCostEstimate

    @property
    def n_inc_edges(self) -> int:
        return 0

    @property
    def n_full_edges(self) -> int:
        return self.est.full_edges

    @property
    def n_out_rows(self) -> int:
        return self.est.n * self.est.L


@dataclasses.dataclass
class _PendingPlan:
    """One planned-but-not-dispatched batch in the fusion lookahead window
    (ISSUE 9).  Everything here is host-only and value-independent (graph
    snapshots, the Alg.-4 plan, its footprint), so the window may run
    arbitrarily far ahead of device execution."""

    batch: UpdateBatch
    g_old: CSRGraph
    g_new: CSRGraph
    plan: BatchPlan
    fp: np.ndarray  # sorted unique row footprint (FusionWindow.footprint)


# ====================================================================== #
# StreamOrchestrator — the single plan/pack/overlap loop
# ====================================================================== #
class StreamOrchestrator:
    """Drives one :class:`StateBackend` over an update stream.

    Owns the evolving graph snapshot, the refresh cadence, and the paper's
    §V co-processing schedule: ``apply_stream`` dispatches batch t and then
    runs host planning of batch t+1 while the substrate executes, syncing
    only at the end of the stream (and around refreshes).  ``apply_batch``
    keeps the per-batch API with honest timing (``block=True`` syncs at the
    timed boundary so ``exec_time_s`` measures completion, not dispatch)."""

    def __init__(self, backend: StateBackend, graph: CSRGraph,
                 refresh_every: int = 0,
                 policy: Optional[ExecutionPolicy] = None,
                 fusion: Optional[FusionConfig] = None):
        self.backend = backend
        self.graph = graph
        self.refresh_every = refresh_every
        self.policy = policy
        # batch-window fusion (ISSUE 9): inert unless a FusionConfig with
        # window >= 2 is attached — None keeps every entry point on the
        # serial per-batch loop, byte-identical to pre-fusion behavior
        if fusion is not None and (not fusion.enabled or fusion.window < 2):
            fusion = None
        self.fusion = fusion
        # cumulative fusion counters (deterministic; StreamStats reports
        # per-stream deltas of these)
        self.fusion_windows = 0
        self.fused_batches = 0
        self.fusion_fallbacks = 0
        self._batches_seen = 0
        self._chunk_sched = None  # lazy generic §V-C scheduler (policy path)

    # ------------------------------------------------------------------ #
    def refresh(self) -> None:
        """Full recomputation (drift reset / MTEC-style refresh)."""
        self.backend.refresh(self.graph)

    def _apply_graph(self, batch: UpdateBatch) -> CSRGraph:
        return self.graph.apply_updates(
            batch.ins_src, batch.ins_dst, batch.del_src, batch.del_dst,
            batch.ins_weights, batch.ins_etypes,
        )

    def _after_batch(self, sync_before_refresh: bool = False) -> None:
        self._batches_seen += 1
        if self.refresh_every and self._batches_seen % self.refresh_every == 0:
            self.backend.flush()
            if sync_before_refresh:
                jax.block_until_ready(self.backend.sync_arrays())
            self.refresh()

    # ------------------------------------------------------------------ #
    # policy routing (ISSUE 7): per batch, score the three execution
    # shapes on the Alg.-4 plan and dispatch the winner.  Without a
    # policy every batch takes the pre-policy incremental path unchanged.
    # ------------------------------------------------------------------ #
    def _prepare(self, g_new: CSRGraph, batch: UpdateBatch,
                 base: Optional[BatchPlan] = None):
        """Plan one batch → ``(mode, payload, decision)``.

        Host-only and value-independent (the decision reads plan counters
        and degree tables, never state values), so it keeps the §V overlap
        contract: ``apply_stream`` runs it behind the previous batch's
        device execution.  ``base`` short-circuits the Alg.-4 build when
        the caller already planned the batch (the fusion lookahead's serial
        fallback) — ``build_plan`` is deterministic, so reusing the
        lookahead's plan is bitwise-identical to rebuilding it."""
        if self.policy is None:
            if base is not None:
                return ("incremental",
                        self.backend.plan(self.graph, g_new, batch,
                                          base_plan=base), None)
            return "incremental", self.backend.plan(self.graph, g_new, batch), None
        if base is None:
            base = build_plan(self.backend.model, self.graph, g_new, batch,
                              self.backend.L)
        decision = self.policy.decide(base)
        if decision.mode == "incremental":
            prep = self.backend.plan(self.graph, g_new, batch, base_plan=base)
            return "incremental", prep, decision
        if decision.mode == "chunked":
            rows = [np.unique(lp.out_rows[lp.out_mask].astype(np.int64))
                    for lp in base.layers]
            return "chunked", _PolicyChunkedPrep(
                plan=base, batch=batch, g_new=g_new, rows_per_layer=rows,
                est=decision.estimate), decision
        return "full", _PolicyFullPrep(batch=batch, g_new=g_new,
                                       est=decision.estimate), decision

    def _dispatch_mode(self, mode: str, prep: Any) -> None:
        if mode == "incremental":
            self.backend.dispatch(prep)
        elif mode == "chunked":
            self._execute_chunked(prep)
        else:
            self._execute_full(prep)

    def _chunk_scheduler(self):
        sched = self.backend.chunk_scheduler()
        if sched is not None:
            return sched  # ChunkedBackend: share its reuse/transfer counters
        if self._chunk_sched is None:
            # deferred import: repro.serve.scheduler pulls repro.core.full
            # while this module is itself mid-import under repro.core
            from repro.serve.scheduler import ChunkedLayerScheduler

            self._chunk_sched = ChunkedLayerScheduler(self.backend.model)
        return self._chunk_sched

    def _apply_features(self, batch: UpdateBatch) -> None:
        if batch.feat_vertices is not None and batch.feat_vertices.size:
            self.backend.apply_feature_updates(
                np.asarray(batch.feat_vertices, np.int64),
                np.asarray(batch.feat_values, np.float32))

    def _execute_chunked(self, prep: _PolicyChunkedPrep) -> None:
        """Chunked-subset recompute on any substrate: per layer, recompute
        the plan's live out rows from the post-batch graph through the §V-C
        scheduler and scatter them back.  Layer ``l`` reads ``h[l]`` after
        the previous layer's scatter (and the feature scatter for layer 0),
        so the recompute sees exactly the incremental path's layer inputs —
        the same schedule as :meth:`ChunkedBackend.dispatch`."""
        self.backend.flush()  # primitives assume no in-flight write-back
        self._apply_features(prep.batch)
        sched = self._chunk_scheduler()
        params = self.backend.host_params
        deg = prep.plan.deg_new[:-1]  # [n] new-graph degrees (drop scratch)
        for l in range(self.backend.L):
            rows = prep.rows_per_layer[l]
            if not rows.size:
                continue
            h_prev = self.backend.layer_input_host(l)
            a_r, nct_r, h_r = sched.run_layer(params[l], prep.g_new,
                                              h_prev, rows, deg)
            self.backend.scatter_layer_rows(l, rows, a_r, nct_r, h_r)

    def _execute_full(self, prep: _PolicyFullPrep) -> None:
        """Full recompute over the post-batch graph — the refresh-cadence
        path, with the batch's feature updates applied first so ``refresh``
        (which recomputes from the *current* h[0]) sees them."""
        self.backend.flush()
        self._apply_features(prep.batch)
        self.backend.refresh(prep.g_new)

    def write_set(self, prep: Any) -> np.ndarray:
        """Serving write set of one prepared batch payload, whatever mode
        the policy chose (the frontend's undo-log hook goes through here;
        full-recompute payloads never reach it — the frontend resets).
        Inside a fused window the hook receives each constituent's raw
        :class:`BatchPlan` (the per-logical-batch write sets the undo log
        needs), handled here directly."""
        if isinstance(prep, BatchPlan):
            return final_write_rows(prep)
        if isinstance(prep, _PolicyChunkedPrep):
            return prep.rows_per_layer[-1]
        return self.backend.changed_rows(prep)

    # ------------------------------------------------------------------ #
    # per-batch API (honest timing: block=True syncs at the boundary)
    # ------------------------------------------------------------------ #
    def apply_batch(self, batch: UpdateBatch, block: bool = True,
                    on_plan=None) -> BatchStats:
        with obs.span("graph") as sg:
            g_new = self._apply_graph(batch)
        with obs.span("plan") as sp:
            mode, prep, decision = self._prepare(g_new, batch)
        with obs.span("exec") as sx:
            if on_plan is not None and mode != "full":
                # serving hook (repro.serve.frontend): runs between plan and
                # dispatch, while the substrate still holds the *pre-batch*
                # state — the front-end snapshots the plan's write set here
                # to build its per-version undo log.  Skipped for
                # full-recompute batches: their pre-images degenerate into a
                # whole-state copy, so the front-end resets its history
                # instead (BatchStats.mode tells it to).
                with obs.span("undo_capture"):
                    on_plan(prep)
            self._dispatch_mode(mode, prep)
            if block:
                with obs.span("sync"):
                    self.backend.flush()
                    jax.block_until_ready(self.backend.sync_arrays())
        self.graph = g_new
        if decision is not None:
            # online cost-weight calibration (ISSUE 9): a no-op unless the
            # policy was built with calibrate=True.  block=False feeds the
            # dispatch-only time (the overlap pipeline cannot observe
            # per-batch completion without breaking itself).
            self.policy.observe(decision, sx.seconds)
        self._after_batch()
        return BatchStats(
            inc_edges=prep.n_inc_edges,
            full_edges=prep.n_full_edges,
            out_vertices=prep.n_out_rows,
            plan_time_s=sp.seconds,
            exec_time_s=sx.seconds,
            graph_time_s=sg.seconds,
            mode=mode,
            est_edges=decision.est_edges if decision is not None else 0,
            est_cost=decision.costs[mode] if decision is not None else 0.0,
            pack_time_s=sp.inner("plan/pack"),
            hook_time_s=sx.inner("undo_capture"),
            h2d_bytes=sx.counts.get("h2d_bytes", 0),
            compiles=sg.compiles + sp.compiles + sx.compiles,
            compile_time_s=sg.compile_s + sp.compile_s + sx.compile_s,
        )

    # ------------------------------------------------------------------ #
    # pipelined stream API: plan t+1 on host while the substrate runs t
    # ------------------------------------------------------------------ #
    def apply_stream(self, batches: Sequence[UpdateBatch]) -> StreamStats:
        """Double-buffered batch application (paper §V co-processing).

        Batch t is dispatched; Alg.-4 planning of batch t+1 (host numpy)
        then runs while the substrate executes.  The only full barrier is
        the end of the stream (and around refreshes)."""
        assert self.backend.overlap_capable, "apply_stream requires the fused engine"
        batches = list(batches)
        if not batches:
            return StreamStats([], 0.0, 0.0)
        if self._fusion_active():
            return self._apply_stream_fused(batches)
        t_start = time.perf_counter()
        stats: List[BatchStats] = []
        plan_total = 0.0
        prefetch_hits = 0  # batches whose plan was built behind execution
        staging0 = self.backend.staging_snapshot()
        cache0 = self.backend.cache_snapshot()
        comms0 = self.backend.comms_snapshot()

        tp = time.perf_counter()
        g_new = self._apply_graph(batches[0])
        mode, prep, decision = self._prepare(g_new, batches[0])
        plan_total += time.perf_counter() - tp

        for i in range(len(batches)):
            epoch0 = self.backend.barrier_epoch
            td = time.perf_counter()
            # async for incremental; chunked/full execute synchronously
            # (they flush first), which honestly costs this batch its
            # prefetch hit — the flush bumps barrier_epoch
            self._dispatch_mode(mode, prep)
            dispatch_s = time.perf_counter() - td
            self.graph = g_new
            stats.append(
                BatchStats(
                    inc_edges=prep.n_inc_edges,
                    full_edges=prep.n_full_edges,
                    out_vertices=prep.n_out_rows,
                    plan_time_s=0.0,
                    exec_time_s=dispatch_s,  # dispatch-only; see StreamStats
                    graph_time_s=0.0,
                    mode=mode,
                    est_edges=decision.est_edges if decision is not None else 0,
                    est_cost=(decision.costs[mode]
                              if decision is not None else 0.0),
                )
            )
            if decision is not None:
                # dispatch-time calibration proxy (a no-op unless the
                # policy was built with calibrate=True): per-batch
                # completion is unobservable inside the overlap pipeline
                self.policy.observe(decision, dispatch_s)
            if i + 1 < len(batches):
                tp = time.perf_counter()  # overlapped with device execution
                nxt = self._apply_graph(batches[i + 1])
                mode, prep, decision = self._prepare(nxt, batches[i + 1])
                g_new = nxt
                plan_total += time.perf_counter() - tp
                # a real prefetch hit only if no backend barrier (flush)
                # fired between dispatch(i) and the completed plan(i+1):
                # a substrate that regresses to synchronous staging (e.g.
                # the async_staging=False escape hatch, which flushes in
                # dispatch) scores 0 here — this is what the CI exact gate
                # pins at batches-1
                if self.backend.barrier_epoch == epoch0:
                    prefetch_hits += 1
            self._after_batch(sync_before_refresh=True)
        self.backend.flush()
        jax.block_until_ready(self.backend.sync_arrays())
        ss = StreamStats(stats, time.perf_counter() - t_start, plan_total,
                         prefetch_hits=prefetch_hits)
        if staging0 is not None:
            s1 = self.backend.staging_snapshot()
            ss.staged_bytes = s1.staged_bytes - staging0.staged_bytes
            ss.sync_wait_s = ((s1.wait_gather_s + s1.drain_wait_s)
                              - (staging0.wait_gather_s + staging0.drain_wait_s))
            ss.compute_s = s1.wait_device_s - staging0.wait_device_s
        if cache0 is not None:
            c1 = self.backend.cache_snapshot()
            ss.cache_hit_rows = c1.hit_rows - cache0.hit_rows
            ss.cache_miss_rows = c1.miss_rows - cache0.miss_rows
            ss.cache_evictions = c1.evictions - cache0.evictions
        if comms0 is not None:
            m1 = self.backend.comms_snapshot()
            ss.comms_halo_rows_sent = m1.halo_rows_sent - comms0.halo_rows_sent
            ss.comms_halo_bytes = m1.halo_bytes - comms0.halo_bytes
        return ss

    # ------------------------------------------------------------------ #
    # batch-window fusion (ISSUE 9): buffer up to fusion.window pending
    # batches, fuse the maximal independent prefix into ONE packed plan /
    # ONE device dispatch, fall back to serial on overlap.  Bitwise-equal
    # to the serial loop on every backend (the disjoint-footprint proof
    # lives on repro.core.affected.FusionWindow).
    # ------------------------------------------------------------------ #
    def _fusion_active(self) -> bool:
        """Fusion runs only when configured AND the policy allows it: a
        per-batch ``force_mode`` schedule is indexed by logical batch, so
        fusing under one would desynchronize the schedule — those streams
        take the serial loop unchanged."""
        if self.fusion is None:
            return False
        if self.policy is not None and self.policy.force_mode is not None \
                and not isinstance(self.policy.force_mode, str):
            return False
        return True

    def _refresh_limit(self) -> int:
        """Batches until the next refresh boundary (windows must not span
        one: refresh recomputes state, so constituents after the boundary
        would fuse against pre-refresh values)."""
        if not self.refresh_every:
            return 1 << 30
        return self.refresh_every - self._batches_seen % self.refresh_every

    def _plan_pending(self, g_old: CSRGraph, batch: UpdateBatch) -> _PendingPlan:
        g_new = g_old.apply_updates(
            batch.ins_src, batch.ins_dst, batch.del_src, batch.del_dst,
            batch.ins_weights, batch.ins_etypes)
        plan = build_plan(self.backend.model, g_old, g_new, batch,
                          self.backend.L)
        return _PendingPlan(batch=batch, g_old=g_old, g_new=g_new, plan=plan,
                            fp=FusionWindow.footprint(plan, batch))

    def _decide_window(self, merged_plan: BatchPlan):
        """Policy check for a fused window (None → no policy → fuse)."""
        if self.policy is None:
            return None, "incremental"
        decision = self.policy.decide_window(merged_plan)
        return decision, decision.mode

    def _fused_stats(self, group: List[_PendingPlan], dispatch_s: float,
                     decision) -> List[BatchStats]:
        """Per-constituent BatchStats of one fused dispatch: plan counters
        stay per *logical* batch (each constituent reports its own plan's
        edge/row work — the sums equal the merged plan's), the window's one
        dispatch time and policy estimate are charged to the first."""
        k = len(group)
        out = []
        for j, p in enumerate(group):
            out.append(BatchStats(
                inc_edges=p.plan.total_inc_edges(),
                full_edges=p.plan.total_full_edges(),
                out_vertices=p.plan.total_vertices(),
                plan_time_s=0.0,
                exec_time_s=dispatch_s if j == 0 else 0.0,
                graph_time_s=0.0,
                mode="incremental",
                est_edges=(decision.est_edges
                           if decision is not None and j == 0 else 0),
                est_cost=(decision.costs["incremental"]
                          if decision is not None and j == 0 else 0.0),
                fused_window=k,
            ))
        return out

    def _apply_stream_fused(self, batches: List[UpdateBatch]) -> StreamStats:
        """The fused variant of :meth:`apply_stream`: same overlap schedule
        (host planning of *future* batches runs behind the device execution
        of the dispatch just issued), but each dispatch covers the maximal
        independent prefix of the lookahead window."""
        fw = FusionWindow(self.fusion)
        t_start = time.perf_counter()
        stats: List[BatchStats] = []
        plan_total = 0.0
        prefetch_hits = 0
        fusion0 = (self.fusion_windows, self.fused_batches,
                   self.fusion_fallbacks)
        staging0 = self.backend.staging_snapshot()
        cache0 = self.backend.cache_snapshot()
        comms0 = self.backend.comms_snapshot()

        pending: List[_PendingPlan] = []
        nxt = 0  # next batch index to plan
        g_plan = self.graph  # graph snapshot after every *planned* batch

        def top_up() -> int:
            """Fill the lookahead window (host-only; overlaps execution)."""
            nonlocal nxt, g_plan, plan_total
            planned = 0
            while len(pending) < self.fusion.window and nxt < len(batches):
                tp = time.perf_counter()
                pending.append(self._plan_pending(g_plan, batches[nxt]))
                g_plan = pending[-1].g_new
                nxt += 1
                plan_total += time.perf_counter() - tp
                planned += 1
            return planned

        top_up()
        while pending:
            limit = min(len(pending), self._refresh_limit())
            k = fw.select_prefix([p.fp for p in pending[:limit]])
            decision, mode = None, "incremental"
            if k >= 2:
                tp = time.perf_counter()
                merged_plan, merged_batch = FusionWindow.merge(
                    [p.plan for p in pending[:k]],
                    [p.batch for p in pending[:k]])
                decision, mode = self._decide_window(merged_plan)
                if mode == "incremental":
                    prep = self.backend.plan(
                        pending[0].g_old, pending[k - 1].g_new, merged_batch,
                        base_plan=merged_plan)
                    plan_total += time.perf_counter() - tp
                    group = pending[:k]
                    del pending[:k]
                    epoch0 = self.backend.barrier_epoch
                    td = time.perf_counter()
                    self.backend.dispatch(prep)
                    dispatch_s = time.perf_counter() - td
                    self.graph = group[-1].g_new
                    self.fusion_windows += 1
                    self.fused_batches += k
                    stats.extend(self._fused_stats(group, dispatch_s,
                                                   decision))
                    if decision is not None:
                        self.policy.observe(decision, dispatch_s)
                    planned = top_up()  # overlapped with fused execution
                    if self.backend.barrier_epoch == epoch0:
                        prefetch_hits += planned
                    for _ in range(k):
                        self._after_batch(sync_before_refresh=True)
                    continue
                # the policy priced the fused unit off the incremental
                # path: break the window up, re-decide per batch below
                plan_total += time.perf_counter() - tp
                self.fusion_fallbacks += 1
            elif limit >= 2:
                self.fusion_fallbacks += 1  # head pair overlaps
            # serial dispatch of the window head (plan reused, not rebuilt)
            p = pending.pop(0)
            tp = time.perf_counter()
            mode, prep, decision = self._prepare(p.g_new, p.batch,
                                                 base=p.plan)
            plan_total += time.perf_counter() - tp
            epoch0 = self.backend.barrier_epoch
            td = time.perf_counter()
            self._dispatch_mode(mode, prep)
            dispatch_s = time.perf_counter() - td
            self.graph = p.g_new
            stats.append(BatchStats(
                inc_edges=prep.n_inc_edges,
                full_edges=prep.n_full_edges,
                out_vertices=prep.n_out_rows,
                plan_time_s=0.0,
                exec_time_s=dispatch_s,
                graph_time_s=0.0,
                mode=mode,
                est_edges=decision.est_edges if decision is not None else 0,
                est_cost=(decision.costs[mode]
                          if decision is not None else 0.0),
            ))
            if decision is not None:
                self.policy.observe(decision, dispatch_s)
            planned = top_up()
            if self.backend.barrier_epoch == epoch0:
                prefetch_hits += planned
            self._after_batch(sync_before_refresh=True)

        self.backend.flush()
        jax.block_until_ready(self.backend.sync_arrays())
        ss = StreamStats(stats, time.perf_counter() - t_start, plan_total,
                         prefetch_hits=prefetch_hits)
        ss.fusion_windows = self.fusion_windows - fusion0[0]
        ss.fused_batches = self.fused_batches - fusion0[1]
        ss.fusion_fallbacks = self.fusion_fallbacks - fusion0[2]
        if staging0 is not None:
            s1 = self.backend.staging_snapshot()
            ss.staged_bytes = s1.staged_bytes - staging0.staged_bytes
            ss.sync_wait_s = ((s1.wait_gather_s + s1.drain_wait_s)
                              - (staging0.wait_gather_s + staging0.drain_wait_s))
            ss.compute_s = s1.wait_device_s - staging0.wait_device_s
        if cache0 is not None:
            c1 = self.backend.cache_snapshot()
            ss.cache_hit_rows = c1.hit_rows - cache0.hit_rows
            ss.cache_miss_rows = c1.miss_rows - cache0.miss_rows
            ss.cache_evictions = c1.evictions - cache0.evictions
        if comms0 is not None:
            m1 = self.backend.comms_snapshot()
            ss.comms_halo_rows_sent = m1.halo_rows_sent - comms0.halo_rows_sent
            ss.comms_halo_bytes = m1.halo_bytes - comms0.halo_bytes
        return ss

    def apply_window(self, batches: Sequence[UpdateBatch],
                     on_plan=None) -> List[BatchStats]:
        """Blocking fused application of a *prefix* of ``batches``.

        The serving front-end's fused write path: plans batches one at a
        time from the current graph, stops at the first footprint overlap /
        window cap / refresh boundary, dispatches the accumulated prefix as
        one fused step (or one serial batch when the prefix is length 1),
        and blocks until the state reflects it.  Returns one
        :class:`BatchStats` per batch consumed (``len(result)`` tells the
        caller how far the stream advanced).

        ``on_plan`` runs once per *constituent* batch — in stream order,
        before dispatch, with the constituent's own :class:`BatchPlan` —
        while the substrate still holds the strictly pre-window state.
        Disjoint write sets make the pre-window values on batch j's write
        set identical to the post-batch-(j-1) values there, so the
        front-end's per-version pre-images stay exact (skipped for
        full-recompute fallbacks, matching :meth:`apply_batch`)."""
        batches = list(batches)
        if not batches:
            return []
        fw = FusionWindow(self.fusion) if self._fusion_active() \
            else FusionWindow(FusionConfig(window=1))
        limit = min(len(batches), fw.config.window, self._refresh_limit())
        t0 = time.perf_counter()
        group = [self._plan_pending(self.graph, batches[0])]
        while len(group) < limit:
            p = self._plan_pending(group[-1].g_new, batches[len(group)])
            if not all(fw.disjoint(p.fp, q.fp) for q in group):
                break  # one wasted (deterministic, value-independent) plan
            group.append(p)
        k = len(group)
        decision, mode = None, "incremental"
        if k >= 2:
            merged_plan, merged_batch = FusionWindow.merge(
                [p.plan for p in group], [p.batch for p in group])
            decision, mode = self._decide_window(merged_plan)
            if mode != "incremental":
                self.fusion_fallbacks += 1
                group, k = group[:1], 1
        elif limit >= 2 and len(batches) >= 2:
            self.fusion_fallbacks += 1
        t1 = time.perf_counter()
        if k >= 2:
            prep = self.backend.plan(group[0].g_old, group[-1].g_new,
                                     merged_batch, base_plan=merged_plan)
            if on_plan is not None:
                for p in group:
                    on_plan(p.plan)
            td = time.perf_counter()
            self.backend.dispatch(prep)
            self.backend.flush()
            jax.block_until_ready(self.backend.sync_arrays())
            dispatch_s = time.perf_counter() - td
            self.graph = group[-1].g_new
            self.fusion_windows += 1
            self.fused_batches += k
            out = self._fused_stats(group, dispatch_s, decision)
            out[0].plan_time_s = t1 - t0
            if decision is not None:
                self.policy.observe(decision, dispatch_s)
            for _ in range(k):
                self._after_batch(sync_before_refresh=True)
            return out
        p = group[0]
        mode, prep, decision = self._prepare(p.g_new, p.batch, base=p.plan)
        if on_plan is not None and mode != "full":
            on_plan(prep)
        td = time.perf_counter()
        self._dispatch_mode(mode, prep)
        self.backend.flush()
        jax.block_until_ready(self.backend.sync_arrays())
        dispatch_s = time.perf_counter() - td
        self.graph = p.g_new
        if decision is not None:
            self.policy.observe(decision, dispatch_s)
        self._after_batch(sync_before_refresh=True)
        return [BatchStats(
            inc_edges=prep.n_inc_edges,
            full_edges=prep.n_full_edges,
            out_vertices=prep.n_out_rows,
            plan_time_s=t1 - t0,
            exec_time_s=dispatch_s,
            graph_time_s=0.0,
            mode=mode,
            est_edges=decision.est_edges if decision is not None else 0,
            est_cost=decision.costs[mode] if decision is not None else 0.0,
        )]


# ====================================================================== #
# DeviceBackend — fused donated in-HBM state (the PR-2 pipelined path)
# ====================================================================== #
@dataclasses.dataclass
class _UnfusedPrep:
    """Per-layer seed execution path's prepared plan (equivalence reference)."""

    plan: BatchPlan
    batch: UpdateBatch

    @property
    def n_inc_edges(self) -> int:
        return self.plan.total_inc_edges()

    @property
    def n_full_edges(self) -> int:
        return self.plan.total_full_edges()

    @property
    def n_out_rows(self) -> int:
        return self.plan.total_vertices()


class DeviceBackend(StateBackend):
    """All state device-resident as scratch-extended ``[N+1, ·]`` arrays;
    each batch runs as one fused, donated L-layer step over a packed plan
    (:func:`repro.core.incremental.fused_stream_step`).  ``fused=False``
    preserves the seed per-layer dispatch as the unfused reference."""

    def __init__(
        self,
        model: GNNModel,
        params: Sequence[Params],
        graph: CSRGraph,
        x: jax.Array,
        store_h: bool = True,
        fused: bool = True,
        use_pallas_delta: bool = False,
    ):
        self.model = model
        self.params = list(params)
        self.L = len(self.params)
        self.store_h = store_h
        self.fused = fused
        self.use_pallas_delta = use_pallas_delta
        # high-water-mark capacity buckets: shrinking batches reuse the
        # previous PackedLayout instead of retracing the fused step
        self.hwm = BucketHysteresis()
        self._upd = jax.jit(model.update)
        self._init_state(graph, jnp.asarray(x))

    @property
    def overlap_capable(self) -> bool:
        return self.fused

    # ------------------------------------------------------------------ #
    # state: scratch-extended [N+1, ·] device arrays (index n = scratch)
    # ------------------------------------------------------------------ #
    def _init_state(self, graph: CSRGraph, x: Optional[jax.Array] = None) -> None:
        if x is None:
            x = self.x
        states = full_forward(self.model, self.params, x, graph)
        self._h: List[Optional[jax.Array]] = [with_scratch(x)] + [
            with_scratch(s.h) for s in states
        ]
        self._a: List[jax.Array] = [with_scratch(s.a) for s in states]
        self._nct: List[jax.Array] = [with_scratch(s.nct) for s in states]
        if not self.store_h:
            self._drop_h()

    def refresh(self, graph: CSRGraph) -> None:
        self._init_state(graph)

    def _drop_h(self) -> None:
        self._h = [self._h[0]] + [None] * self.L

    @property
    def x(self) -> jax.Array:
        return self._h[0][:-1]

    @property
    def h(self) -> List[Optional[jax.Array]]:
        """Seed-compatible view: per-layer embeddings without scratch rows."""
        return [None if v is None else v[:-1] for v in self._h]

    @h.setter
    def h(self, vals: Sequence[Optional[jax.Array]]) -> None:
        self._h = [None if v is None else with_scratch(v) for v in vals]

    @property
    def a(self) -> List[jax.Array]:
        return [v[:-1] for v in self._a]

    @a.setter
    def a(self, vals: Sequence[jax.Array]) -> None:
        self._a = [with_scratch(v) for v in vals]

    @property
    def nct(self) -> List[jax.Array]:
        return [v[:-1] for v in self._nct]

    @nct.setter
    def nct(self, vals: Sequence[jax.Array]) -> None:
        self._nct = [with_scratch(v) for v in vals]

    def reconstruct_h(self) -> List[jax.Array]:
        """Recomputation-based storage optimization (paper §V-B): rebuild
        h^l = update(h^{l-1}, a^l) from the cached aggregation states."""
        h = [self.x]
        for l in range(self.L):
            h.append(self._upd(self.params[l], h[l], self._a[l][:-1]))
        return h

    @property
    def embeddings(self) -> jax.Array:
        if self._h[-1] is None:
            return self.reconstruct_h()[-1]
        return self._h[-1][:-1]

    def state_bytes(self) -> int:
        def nb(arr: jax.Array) -> int:
            return (arr.shape[0] - 1) * int(np.prod(arr.shape[1:] or (1,))) * arr.dtype.itemsize

        total = sum(nb(a) for a in self._a) + sum(nb(c) for c in self._nct)
        if self.store_h:
            total += sum(nb(h) for h in self._h[1:] if h is not None)
        total += nb(self._h[0])
        return total

    def sync_arrays(self) -> list:
        return [v for v in (*self._h, *self._a, *self._nct) if v is not None]

    # ------------------------------------------------------------------ #
    # Serving API: O(len(rows)) device gather + D2H (never O(V))
    # ------------------------------------------------------------------ #
    def snapshot_rows(self, rows: np.ndarray) -> np.ndarray:
        idx = jnp.asarray(np.asarray(rows, np.int64), jnp.int32)
        h = self._h[-1]
        if h is None:  # store_h=False: rebuild from the cached a states
            return np.asarray(jnp.take(self.reconstruct_h()[-1], idx, axis=0))
        return np.asarray(jnp.take(h[:-1], idx, axis=0))

    def changed_rows(self, prep) -> np.ndarray:
        if isinstance(prep, _UnfusedPrep):
            from repro.core.affected import final_write_rows

            return final_write_rows(prep.plan)
        return prep.out_rows_final

    # ------------------------------------------------------------------ #
    # policy-execution primitives: scatters on the scratch-extended device
    # arrays (global rows < n, so the scratch row is never written)
    # ------------------------------------------------------------------ #
    def apply_feature_updates(self, rows: np.ndarray, vals: np.ndarray) -> None:
        idx = jnp.asarray(np.asarray(rows, np.int64), jnp.int32)
        self._h[0] = self._h[0].at[idx].set(
            jnp.asarray(vals, self._h[0].dtype))

    def layer_input_host(self, l: int) -> np.ndarray:
        h = self._h[l]
        if h is None:  # store_h=False: rebuild from the cached a states
            return np.asarray(self.reconstruct_h()[l])
        return np.asarray(h[:-1])

    def scatter_layer_rows(self, l: int, rows: np.ndarray, a_rows: np.ndarray,
                           nct_rows: np.ndarray, h_rows: np.ndarray) -> None:
        idx = jnp.asarray(np.asarray(rows, np.int64), jnp.int32)
        self._a[l] = self._a[l].at[idx].set(jnp.asarray(a_rows))
        self._nct[l] = self._nct[l].at[idx].set(jnp.asarray(nct_rows))
        if self._h[l + 1] is not None:  # store_h=False reconstructs instead
            self._h[l + 1] = self._h[l + 1].at[idx].set(jnp.asarray(h_rows))

    # ------------------------------------------------------------------ #
    def plan(self, g_old: CSRGraph, g_new: CSRGraph, batch: UpdateBatch,
             base_plan: Optional[BatchPlan] = None):
        if self.fused:
            if base_plan is not None:  # policy path: Alg. 4 already ran
                with obs.span("plan/pack"):
                    return pack_plan(base_plan, batch.feat_vertices,
                                     batch.feat_values,
                                     pallas=self.use_pallas_delta, hwm=self.hwm)
            return build_packed_plan(
                self.model, g_old, g_new, batch, self.L,
                pallas=self.use_pallas_delta, hwm=self.hwm,
            )
        plan = (base_plan if base_plan is not None
                else build_plan(self.model, g_old, g_new, batch, self.L))
        return _UnfusedPrep(plan, batch)

    def dispatch(self, prep) -> None:
        if isinstance(prep, _UnfusedPrep):
            self._execute_unfused(prep.plan, prep.batch)
        else:
            self._dispatch_packed(prep)

    # ------------------------------------------------------------------ #
    def _dispatch_packed(self, packed: PackedPlan) -> None:
        """One device_put for the whole plan, one fused-step dispatch."""
        if not self.store_h and self._h[1] is None:
            h = self.reconstruct_h()
            self._h = [self._h[0]] + [with_scratch(v) for v in h[1:]]
        with obs.span("device_put"):
            obs.count("h2d_bytes", packed_nbytes(packed))
            idx, flt, msk, feat_vals, pallas = jax.device_put(
                (packed.idx, packed.flt, packed.msk, packed.feat_vals,
                 packed.pallas))
        with obs.span("step"):
            hs, as_, ncts = fused_stream_step(
                self.model, packed.layout, tuple(self.params),
                tuple(self._h), tuple(self._a), tuple(self._nct),
                idx, flt, msk, feat_vals, pallas,
            )
        self._h = list(hs)
        self._a = list(as_)
        self._nct = list(ncts)
        if not self.store_h:
            self._drop_h()

    # ------------------------------------------------------------------ #
    # unfused seed path (per-layer dispatch) — equivalence reference
    # ------------------------------------------------------------------ #
    def _execute_unfused(self, plan: BatchPlan, batch: UpdateBatch) -> None:
        deg_old = jnp.asarray(plan.deg_old)
        deg_new = jnp.asarray(plan.deg_new)

        if not self.store_h:
            self.h = self.reconstruct_h()

        # layer-0 feature updates
        h0_old = self.h[0]
        if batch.feat_vertices is not None and batch.feat_vertices.size:
            h0_new = h0_old.at[jnp.asarray(batch.feat_vertices)].set(
                jnp.asarray(batch.feat_values, h0_old.dtype)
            )
        else:
            h0_new = h0_old

        h_old = [h0_old] + list(self.h[1:])
        h_new: List[jax.Array] = [h0_new]
        a_new: List[jax.Array] = []
        nct_new: List[jax.Array] = []

        for l, lp in enumerate(plan.layers):
            an, nn, hn = incremental_layer(
                self.model,
                self.params[l],
                with_scratch(h_old[l]),
                with_scratch(h_new[l]),
                deg_old,
                deg_new,
                self.a[l],
                self.nct[l],
                h_old[l + 1],
                jnp.asarray(lp.e_src),
                jnp.asarray(lp.e_dst),
                jnp.asarray(lp.e_rowidx),
                jnp.asarray(lp.e_sign),
                jnp.asarray(lp.e_use_new),
                jnp.asarray(lp.e_w),
                jnp.asarray(lp.e_t),
                jnp.asarray(lp.e_mask),
                jnp.asarray(lp.touch_rows),
                jnp.asarray(lp.touch_mask),
                jnp.asarray(lp.f_rows),
                jnp.asarray(lp.f_mask),
                jnp.asarray(lp.f_src),
                jnp.asarray(lp.f_rowidx),
                jnp.asarray(lp.f_w),
                jnp.asarray(lp.f_t),
                jnp.asarray(lp.f_emask),
                jnp.asarray(lp.out_rows),
                jnp.asarray(lp.out_mask),
            )
            a_new.append(an)
            nct_new.append(nn)
            h_new.append(hn)

        self.h = h_new
        self.a = a_new
        self.nct = nct_new
        if not self.store_h:
            self._drop_h()


# ====================================================================== #
# OffloadBackend — host-resident state, compact per-layer staging (§V-B)
# ====================================================================== #
@dataclasses.dataclass
class TransferStats:
    rows_up: int = 0
    rows_down: int = 0
    bytes_up: int = 0
    bytes_down: int = 0

    @property
    def total_rows(self) -> int:
        """H2D+D2H row volume — deterministic (no timing noise), so the CI
        perf gate can bound it tightly (benchmarks/check_regression.py)."""
        return self.rows_up + self.rows_down


_remap = remap_compact  # global vertex ids → compact positions (affected.py)


def _override_rows(dst_vals: np.ndarray, dst_rows: np.ndarray,
                   src_rows: np.ndarray, src_vals: np.ndarray) -> None:
    """dst_vals[i] ← src_vals[j] where dst_rows[i] == src_rows[j] (vectorized)."""
    if not src_rows.size or not dst_rows.size:
        return
    order = np.argsort(src_rows)
    pos = np.searchsorted(src_rows[order], dst_rows)
    pos = np.clip(pos, 0, src_rows.size - 1)
    hit = src_rows[order][pos] == dst_rows
    dst_vals[hit] = src_vals[order][pos[hit]]


@dataclasses.dataclass
class _CacheLayerOps:
    """Plan-time device hot-row-cache schedule for one layer (ISSUE 8).

    Built by the host-resident backends' ``_plan_cache`` next to the
    transfer tables (value-independent, so it keeps the plan/execute
    overlap contract) and consumed by their cached gather/exec paths at
    dispatch.  All ``*_pos`` arrays are positions in the layer's device
    workspace — ``[nh]``/``[ns]`` compact space for the flat offload,
    flat ``[S·cap]`` stacked space for the hybrid; ``h_miss_src``/
    ``s_miss_src`` are the global row ids the staging worker still
    gathers (the cold misses); ``patch_src`` / ``*_wb_pos`` index the
    previous / current layer's compact device outputs."""

    # h^{l-1} gather space ("h", l): hits read device slots, misses stage
    h_hit_pos: np.ndarray
    h_hit_slots: np.ndarray
    h_miss_pos: np.ndarray
    h_miss_src: np.ndarray
    h_admit_midx: np.ndarray  # miss-buffer rows to install into fresh slots
    h_admit_slots: np.ndarray
    # device-side new-view patch (previous layer's still-resident outputs)
    patch_pos: np.ndarray
    patch_src: np.ndarray
    # state gather space ("s", l): a/nct/h_cur rows
    s_hit_pos: np.ndarray
    s_hit_slots: np.ndarray
    s_miss_pos: np.ndarray
    s_miss_src: np.ndarray
    # in-place slot refresh from this layer's kernel outputs
    s_wb_pos: np.ndarray
    s_wb_slots: np.ndarray
    hnext_wb_pos: np.ndarray
    hnext_wb_slots: np.ndarray


def _patch_positions(dst_keys: np.ndarray, src_rows: np.ndarray):
    """Workspace positions (and source indices) of the new-view patch —
    the same match :func:`_override_rows` performs on the host path, so
    the cached device patch is position-for-position identical."""
    idx = np.full(dst_keys.shape[0], -1, np.int64)
    _override_rows(idx, np.asarray(dst_keys, np.int64), src_rows,
                   np.arange(src_rows.shape[0], dtype=np.int64))
    pos = np.flatnonzero(idx >= 0).astype(np.int64)
    return pos, idx[pos]


def _cache_assemble(n_rows: int, dim: int, miss_pos: np.ndarray, miss_vals,
                    hit_pos: np.ndarray, hit_vals):
    """Device workspace assembly: scatter the staged cold misses and the
    cached hot rows into a zeroed ``[n_rows, dim]`` array.  Hit and miss
    positions partition the live rows (dead stacked-hybrid positions stay
    0.0, matching the host gather's zeroing), so the result is bitwise
    identical to the staged workspace it replaces."""
    out = jnp.zeros((n_rows, dim), jnp.float32)
    if miss_pos.size:
        out = out.at[miss_pos].set(miss_vals)
    if hit_pos.size:
        out = out.at[hit_pos].set(hit_vals)
    return out


@dataclasses.dataclass
class _LayerTransfer:
    """Plan-time (value-independent) compact transfer tables for one layer."""

    need_h: np.ndarray  # global ids of h^{l-1} rows the device needs
    srows: np.ndarray  # global ids of state rows updated (= out_rows live)
    e_src: np.ndarray  # remapped into need_h space
    e_dst: np.ndarray
    f_src: np.ndarray
    touch_rows_s: np.ndarray  # remapped into srows space
    f_rows_s: np.ndarray
    out_rows_s: np.ndarray
    f_rows_h: np.ndarray  # remapped into need_h space
    out_rows_h: np.ndarray
    deg_old_rows: np.ndarray  # [nh+1] compact degree tables (scratch slot)
    deg_new_rows: np.ndarray


@dataclasses.dataclass
class _OffloadPrep:
    """Host-side output of the planning phase for one batch."""

    plan: BatchPlan
    batch: UpdateBatch
    transfers: List[_LayerTransfer]
    cache_ops: Optional[List[_CacheLayerOps]] = None

    @property
    def n_inc_edges(self) -> int:
        return self.plan.total_inc_edges()

    @property
    def n_full_edges(self) -> int:
        return self.plan.total_full_edges()

    @property
    def n_out_rows(self) -> int:
        return self.plan.total_vertices()


class _DeferredWritebackMixin:
    """Deferred final-layer write-back + staging barrier shared by the
    host-resident backends.  ``dispatch`` leaves the last layer's (device →
    host) write-back pending — a :class:`StagingTicket` in async-staging
    mode (the worker performs the D2H and the scatter), the raw payload in
    sync mode — and ``flush`` completes it and **drains the staging
    worker**, re-raising any worker exception on the caller thread.  The
    orchestrator's next plan (and, async, even the next batch's gathers,
    queued behind the write-back) runs while the device still executes the
    final layer."""

    _pending = None
    _staging: Optional[HostStagingPipeline] = None
    _cache: Optional[HotRowCache] = None

    def flush(self) -> None:
        self.barrier_epoch += 1
        pending, self._pending = self._pending, None
        if pending is not None:
            if isinstance(pending, StagingTicket):
                pending.wait()
            else:
                self._final_writeback(pending)
        if self._staging is not None:
            self._staging.drain()

    def staging_snapshot(self) -> Optional[StagingStats]:
        return self._staging.stats.snapshot()

    def cache_snapshot(self) -> Optional[CacheStats]:
        return None if self._cache is None else self._cache.stats.snapshot()

    @property
    def async_staging(self) -> bool:
        return self._staging.async_mode

    def _cache_layer_ops(self, l: int, n: int, rows_h: np.ndarray,
                         rows_s: np.ndarray, prev_rows: np.ndarray,
                         deg: np.ndarray):
        """Shared per-layer cache planning for the host-resident
        substrates: the read splits for the ``("h", l)`` / ``("s", l)``
        spaces and the write-back slot refresh for ``("s", l)`` and
        ``("h", l+1)``.  ``prev_rows`` (the rows the batch wrote earlier —
        layer l-1's scatter set, or the feature vertices for l=0) are
        excluded from hits *and* staged-value admission: their cached
        slots were just refreshed with post-write values, while layer l's
        old view needs the pristine pre-batch rows (see the coherence
        notes in repro.serve.hotcache)."""
        cache = self._cache
        h_split = cache.plan_reads(("h", l), n, rows_h, deg[rows_h],
                                   exclude_rows=prev_rows)
        s_split = cache.plan_reads(("s", l), n, rows_s, deg[rows_s],
                                   admit=False)
        s_wb = cache.plan_writeback(("s", l), n, rows_s, deg[rows_s])
        if l + 1 < self.L:
            hn_wb = cache.plan_writeback(("h", l + 1), n, rows_s, deg[rows_s])
        else:  # h^L is never re-read through the cache
            hn_wb = (np.zeros(0, np.int64), np.zeros(0, np.int32))
        return h_split, s_split, s_wb, hn_wb

    def _gather_state_rows(self, arr: np.ndarray, rows: np.ndarray) -> np.ndarray:
        """Gather global state rows (flat host arrays; the sharded hybrid
        overrides with its per-shard block gather)."""
        return arr[rows]

    def _prewarm_cache(self, graph: CSRGraph) -> None:
        """Seed every cache row space from the base graph's top-degree rows
        before batch 0 (``CacheConfig.prewarm_rows``, ISSUE 9).

        Runs at construction time, after the initial full forward: the
        gathered values are the pristine base state, so the coherence
        invariant holds trivially.  Degree ties admit the smallest row id
        (stable argsort), keeping the seeded slot table — and every
        downstream hit/miss/eviction counter — deterministic."""
        cache = self._cache
        if cache is None or not cache.config.prewarm_rows:
            return
        k = min(int(cache.config.prewarm_rows), graph.n)
        deg = graph.in_degree().astype(np.int64)
        top = np.argsort(-deg, kind="stable")[:k].astype(np.int64)
        degs = deg[top].astype(np.float32)
        for l in range(self.L):
            cache.prewarm(("h", l), graph.n, top, degs,
                          {"h": self._gather_state_rows(self.h[l], top)})
            cache.prewarm(("s", l), graph.n, top, degs, {
                "a": self._gather_state_rows(self.a[l], top),
                "nct": self._gather_state_rows(self.nct[l], top),
                "h": self._gather_state_rows(self.h[l + 1], top),
            })

    def _cache_invalidate_feats(self, batch: UpdateBatch) -> np.ndarray:
        """Plan-time, value-independent invalidation for a batch's feature
        scatter (it rewrites h[0] rows outside the kernel write-back path);
        returns the feature rows as layer 0's exclusion set."""
        if batch.feat_vertices is not None and np.asarray(batch.feat_vertices).size:
            rows = np.asarray(batch.feat_vertices, np.int64)
            self._cache.invalidate(("h", 0), rows)
            return rows
        return np.zeros(0, np.int64)

    def _defer_final(self, payload) -> None:
        """Queue the final layer's write-back: on the worker (async) or as
        a raw pending payload completed inline at ``flush`` (sync)."""
        pipe = self._staging
        nb = (0 if payload is None or payload[-1] is None
              else sum(int(o.nbytes) for o in payload[-1]))
        if pipe.async_mode:
            self._pending = pipe.submit_writeback(
                partial(self._final_writeback, payload), nbytes=nb, tag="final")
        else:
            pipe.stats.staged_bytes += nb
            self._pending = payload


class OffloadBackend(_DeferredWritebackMixin, StateBackend):
    """NeutronRT-style out-of-memory embedding management (paper §V-B).

    The per-layer state (h, a, nct) lives as **host numpy**; per batch only
    the compact row sets the plan touches transfer to the device, the same
    `incremental_layer` kernel runs over compact arrays (the kernel is
    index-based, so a compact view with remapped indices is exactly
    equivalent), and all write-backs are grouped.  Host staging runs
    through a :class:`~repro.serve.staging.HostStagingPipeline`: pristine
    per-layer gathers prefetch on a background worker while the device
    computes the previous layer, write-back scatters retire there too, and
    the final layer's write-back (D2H included) is deferred entirely to
    the worker (``flush`` is the barrier) so batch-t+1 planning — and its
    gathers — overlap the device's execution of batch t's last layer.
    ``async_staging=False`` runs the identical staging jobs inline
    (bitwise-identical output; tests/test_staging.py)."""

    def __init__(self, model: GNNModel, params: Sequence[Params],
                 graph: CSRGraph, x: np.ndarray, async_staging: bool = True,
                 cache: Optional[HotRowCache] = None, staging_depth: int = 2):
        self.model = model
        self.params = list(params)
        self.L = len(self.params)
        self.x = np.asarray(x, np.float32)
        self.transfers = TransferStats()
        self._cache = cache
        self._staging = HostStagingPipeline(self.L, depth=staging_depth,
                                            async_mode=async_staging,
                                            name="offload")
        states = full_forward(model, params, jnp.asarray(self.x), graph)
        self.h: List[np.ndarray] = [self.x.copy()] + [np.array(s.h) for s in states]
        self.a: List[np.ndarray] = [np.array(s.a) for s in states]
        self.nct: List[np.ndarray] = [np.array(s.nct) for s in states]
        self._prewarm_cache(graph)

    @property
    def embeddings(self) -> np.ndarray:
        self.flush()
        return self.h[-1]

    def state_bytes(self) -> int:
        return (sum(a.nbytes for a in self.a) + sum(c.nbytes for c in self.nct)
                + sum(h.nbytes for h in self.h))

    def sync_arrays(self) -> list:
        return []  # flush() is the real barrier; state is host numpy

    def refresh(self, graph: CSRGraph) -> None:
        self.flush()
        states = full_forward(self.model, self.params, jnp.asarray(self.h[0]),
                              graph)
        self.h = [self.h[0]] + [np.array(s.h) for s in states]
        self.a = [np.array(s.a) for s in states]
        self.nct = [np.array(s.nct) for s in states]
        if self._cache is not None:  # every cached row may now be stale
            self._cache.invalidate_all()

    # ------------------------------------------------------------------ #
    # Serving API: host-numpy gather; flush() first so a deferred final
    # write-back can never be missed (a no-op at a version boundary — the
    # staging worker's queue is already drained, so reads never block it)
    # ------------------------------------------------------------------ #
    def snapshot_rows(self, rows: np.ndarray) -> np.ndarray:
        self.flush()
        return self.h[-1][np.asarray(rows, np.int64)]

    def changed_rows(self, prep: "_OffloadPrep") -> np.ndarray:
        return np.unique(prep.transfers[-1].srows)

    # ------------------------------------------------------------------ #
    # policy-execution primitives: direct host-numpy scatters (the
    # orchestrator flushes first, so no deferred write-back is in flight)
    # ------------------------------------------------------------------ #
    def apply_feature_updates(self, rows: np.ndarray, vals: np.ndarray) -> None:
        rows = np.asarray(rows, np.int64)
        self.h[0][rows] = np.asarray(vals, np.float32)
        if self._cache is not None:
            self._cache.invalidate(("h", 0), rows)

    def layer_input_host(self, l: int) -> np.ndarray:
        return self.h[l]

    def scatter_layer_rows(self, l: int, rows: np.ndarray, a_rows: np.ndarray,
                           nct_rows: np.ndarray, h_rows: np.ndarray) -> None:
        self.a[l][rows] = a_rows
        self.nct[l][rows] = nct_rows
        self.h[l + 1][rows] = h_rows
        if self._cache is not None:  # value-independent: keyed by rows only
            self._cache.invalidate(("s", l), rows)
            self._cache.invalidate(("h", l + 1), rows)

    # ------------------------------------------------------------------ #
    # planning phase (host only, value-independent)
    # ------------------------------------------------------------------ #
    def plan(self, g_old: CSRGraph, g_new: CSRGraph, batch: UpdateBatch,
             base_plan: Optional[BatchPlan] = None) -> _OffloadPrep:
        plan = (base_plan if base_plan is not None
                else build_plan(self.model, g_old, g_new, batch, self.L))
        n = g_old.n
        prev_rows = (
            np.asarray(batch.feat_vertices, np.int64)
            if batch.feat_vertices is not None and batch.feat_vertices.size
            else np.zeros(0, np.int64)
        )
        transfers: List[_LayerTransfer] = []
        for lp in plan.layers:
            need_h = np.unique(np.concatenate([
                lp.e_src[lp.e_mask].astype(np.int64),
                lp.e_dst[lp.e_mask].astype(np.int64),
                lp.f_src[lp.f_emask].astype(np.int64),
                lp.f_rows[lp.f_mask].astype(np.int64),
                lp.out_rows[lp.out_mask].astype(np.int64),
                prev_rows,
            ]))
            srows = lp.out_rows[lp.out_mask].astype(np.int64)
            nh, ns = need_h.shape[0], srows.shape[0]
            transfers.append(_LayerTransfer(
                need_h=need_h,
                srows=srows,
                e_src=_remap(lp.e_src, need_h, nh, n),
                e_dst=_remap(lp.e_dst, need_h, nh, n),
                f_src=_remap(lp.f_src, need_h, nh, n),
                touch_rows_s=_remap(lp.touch_rows, srows, ns, n),
                f_rows_s=_remap(lp.f_rows, srows, ns, n),
                out_rows_s=_remap(lp.out_rows, srows, ns, n),
                f_rows_h=_remap(lp.f_rows, need_h, nh, n),
                out_rows_h=_remap(lp.out_rows, need_h, nh, n),
                deg_old_rows=np.concatenate(
                    [plan.deg_old[need_h], [0.0]]).astype(np.float32),
                deg_new_rows=np.concatenate(
                    [plan.deg_new[need_h], [0.0]]).astype(np.float32),
            ))
            prev_rows = srows
        cache_ops = (self._plan_cache(plan, batch, transfers)
                     if self._cache is not None else None)
        return _OffloadPrep(plan=plan, batch=batch, transfers=transfers,
                            cache_ops=cache_ops)

    def _plan_cache(self, plan: BatchPlan, batch: UpdateBatch,
                    transfers: List[_LayerTransfer]) -> List[_CacheLayerOps]:
        """Plan-time residency split for every layer (host only,
        value-independent — it touches slot metadata and degree tables,
        never row values).  Runs after dispatch(t-1) returned, so all of
        batch t-1's cache-store updates are already recorded."""
        cache = self._cache
        n = plan.deg_old.shape[0] - 1  # deg tables carry a scratch slot
        deg = plan.deg_new
        cache.decay_tick()
        prev_rows = self._cache_invalidate_feats(batch)
        ops: List[_CacheLayerOps] = []
        for l, tr in enumerate(transfers):
            h_split, s_split, s_wb, hn_wb = self._cache_layer_ops(
                l, n, tr.need_h, tr.srows, prev_rows, deg)
            patch_pos, patch_src = _patch_positions(tr.need_h, prev_rows)
            ops.append(_CacheLayerOps(
                h_hit_pos=h_split.hit_pos, h_hit_slots=h_split.hit_slots,
                h_miss_pos=h_split.miss_pos, h_miss_src=h_split.miss_rows,
                h_admit_midx=h_split.admit_midx,
                h_admit_slots=h_split.admit_slots,
                patch_pos=patch_pos, patch_src=patch_src,
                s_hit_pos=s_split.hit_pos, s_hit_slots=s_split.hit_slots,
                s_miss_pos=s_split.miss_pos, s_miss_src=s_split.miss_rows,
                s_wb_pos=s_wb[0], s_wb_slots=s_wb[1],
                hnext_wb_pos=hn_wb[0], hnext_wb_slots=hn_wb[1]))
            prev_rows = tr.srows
        return ops

    # ------------------------------------------------------------------ #
    def dispatch(self, prep: _OffloadPrep) -> None:
        """Run all layers through the staging pipeline (see
        :mod:`repro.serve.staging` for the schedule).  Pristine gathers for
        every layer enqueue up front — the in-order worker runs them after
        any still-in-flight write-back of the previous batch and before
        this batch's own write-backs, so each layer's staged ``h_old`` view
        is exactly the pre-batch state and the ``h_new`` view is the same
        rows patched with the previous layer's freshly computed outputs.
        While the device computes layer *l*, the worker gathers layer *l+1*
        and retires layer *l-1*'s scatter; the final layer's grouped
        write-back (the paper's "group all updated embeddings and write
        them back in parallel") defers entirely to the worker so the
        orchestrator's next plan overlaps the device's last-layer
        execution."""
        pipe = self._staging
        if not pipe.async_mode:
            self.flush()  # inline staging jobs read host state directly
        pipe.begin_batch()
        batch = prep.batch

        # layer-0 "previous layer outputs" = the batch's feature updates
        if batch.feat_vertices is not None and batch.feat_vertices.size:
            prev_rows = np.asarray(batch.feat_vertices, np.int64)
            prev_new = np.asarray(batch.feat_values, np.float32)
        else:
            prev_rows = np.zeros(0, np.int64)
            prev_new = np.zeros((0, self.h[0].shape[1]), np.float32)

        ops = prep.cache_ops
        tickets = [
            pipe.submit_gather(partial(self._gather_layer, l, tr,
                                       pipe.buffers(l),
                                       None if ops is None else ops[l]),
                               tag=l)
            for l, tr in enumerate(prep.transfers)
        ]
        if prev_rows.size:
            # persist the feature update into h[0]; the in-order queue puts
            # it after gather(0)'s pristine read and before the next batch
            pipe.submit_writeback(
                partial(self._scatter_feats, prev_rows, prev_new),
                nbytes=int(prev_new.nbytes), tag="feat")

        # cached path: the previous layer's outputs stay device-resident so
        # the new-view patch happens on device instead of via staged h_new
        prev_dev = jnp.asarray(prev_new) if prev_rows.size else None
        final = None
        for l, (lp, tr) in enumerate(zip(prep.plan.layers, prep.transfers)):
            staged = pipe.wait_gather(tickets[l])
            if ops is None:
                outs = self._layer_exec(l, lp, tr, staged, prev_rows, prev_new)
            else:
                outs = self._layer_exec_cached(l, lp, tr, staged, ops[l],
                                               prev_dev)
                prev_dev = None if outs is None else outs[2]
            if l + 1 < self.L:
                if outs is None:  # empty layer: nothing written back
                    prev_rows = tr.srows
                    prev_new = np.zeros((0, self.h[l + 1].shape[1]), np.float32)
                else:
                    a_np, nct_np, h_np = pipe.wait_device(outs)
                    pipe.submit_writeback(
                        partial(self._writeback_host, l, tr.srows,
                                a_np, nct_np, h_np),
                        nbytes=int(a_np.nbytes + nct_np.nbytes + h_np.nbytes),
                        tag=l)
                    prev_rows, prev_new = tr.srows, h_np
            else:
                final = (l, tr.srows, outs)
        self._defer_final(final)

    def _scatter_feats(self, rows: np.ndarray, vals: np.ndarray) -> None:
        self.h[0][rows] = vals

    def _gather_layer(self, l: int, tr: _LayerTransfer, bufs,
                      cops: Optional[_CacheLayerOps] = None):
        """Staging-worker job: pristine gather of layer ``l``'s compact
        rows into the double-buffered staging set (``h_new`` starts as a
        copy of ``h_old``; the caller patches it before H2D).  With the
        hot-row cache enabled, only the plan's cold misses stage — hits
        are served from device slots at exec and no ``h_new`` view stages
        at all (the new-view patch happens on device)."""
        need_h, srows = tr.need_h, tr.srows
        nh, ns = need_h.shape[0], srows.shape[0]
        if nh == 0 and ns == 0:
            return None
        if cops is not None:
            nh_m, ns_m = cops.h_miss_src.shape[0], cops.s_miss_src.shape[0]
            h_old = bufs.take("h_old", nh_m, self.h[l].shape[1:])
            np.take(self.h[l], cops.h_miss_src, axis=0, out=h_old)
            a_rows = bufs.take("a", ns_m, self.a[l].shape[1:])
            np.take(self.a[l], cops.s_miss_src, axis=0, out=a_rows)
            nct_rows = bufs.take("nct", ns_m, self.nct[l].shape[1:])
            np.take(self.nct[l], cops.s_miss_src, axis=0, out=nct_rows)
            h_cur = bufs.take("h_cur", ns_m, self.h[l + 1].shape[1:])
            np.take(self.h[l + 1], cops.s_miss_src, axis=0, out=h_cur)
            return {"h_old": h_old, "a": a_rows, "nct": nct_rows,
                    "h_cur": h_cur}
        h_old = bufs.take("h_old", nh, self.h[l].shape[1:])
        np.take(self.h[l], need_h, axis=0, out=h_old)
        h_new = bufs.take("h_new", nh, self.h[l].shape[1:])
        np.copyto(h_new, h_old)
        a_rows = bufs.take("a", ns, self.a[l].shape[1:])
        np.take(self.a[l], srows, axis=0, out=a_rows)
        nct_rows = bufs.take("nct", ns, self.nct[l].shape[1:])
        np.take(self.nct[l], srows, axis=0, out=nct_rows)
        h_cur = bufs.take("h_cur", ns, self.h[l + 1].shape[1:])
        np.take(self.h[l + 1], srows, axis=0, out=h_cur)
        return {"h_old": h_old, "h_new": h_new, "a": a_rows,
                "nct": nct_rows, "h_cur": h_cur}

    def _layer_exec(self, l: int, lp: LayerPlan, tr: _LayerTransfer, staged,
                    prev_rows: np.ndarray, prev_new: np.ndarray):
        """Patch the staged new-view rows with the previous layer's fresh
        outputs, ship the layer in ONE device_put, dispatch the kernel."""
        if staged is None:
            return None
        need_h, srows = tr.need_h, tr.srows
        nh, ns = need_h.shape[0], srows.shape[0]
        h_old_rows, h_new_rows = staged["h_old"], staged["h_new"]
        _override_rows(h_new_rows, need_h, prev_rows, prev_new)
        a_rows, nct_rows, h_cur_rows = staged["a"], staged["nct"], staged["h_cur"]

        self.transfers.rows_up += 2 * nh + 3 * ns
        self.transfers.bytes_up += (2 * h_new_rows.nbytes + a_rows.nbytes
                                    + nct_rows.nbytes + h_cur_rows.nbytes)

        # one batched H2D transfer for the whole layer (packed-plan analogue)
        dev = jax.device_put((
            h_old_rows, h_new_rows, tr.deg_old_rows, tr.deg_new_rows,
            a_rows, nct_rows, h_cur_rows,
            tr.e_src, tr.e_dst, lp.e_rowidx, lp.e_sign, lp.e_use_new,
            lp.e_w, lp.e_t, lp.e_mask,
            tr.touch_rows_s, lp.touch_mask,
            tr.f_rows_s, lp.f_mask, tr.f_src, lp.f_rowidx, lp.f_w,
            lp.f_t, lp.f_emask,
            tr.out_rows_s, lp.out_mask, tr.f_rows_h, tr.out_rows_h,
        ))
        (h_old_d, h_new_d, deg_old_d, deg_new_d, a_d, nct_d, h_cur_d,
         e_src, e_dst, e_rowidx, e_sign, e_use_new, e_w, e_t, e_mask,
         touch_rows_s, touch_mask, f_rows_s, f_mask, f_src, f_rowidx, f_w,
         f_t, f_emask, out_rows_s, out_mask, f_rows_h, out_rows_h) = dev

        return incremental_layer(
            self.model, self.params[l],
            with_scratch(h_old_d), with_scratch(h_new_d),
            deg_old_d, deg_new_d, a_d, nct_d, h_cur_d,
            e_src, e_dst, e_rowidx, e_sign, e_use_new, e_w, e_t, e_mask,
            touch_rows_s, touch_mask,
            f_rows_s, f_mask, f_src, f_rowidx, f_w, f_t, f_emask,
            out_rows_s, out_mask,
            f_rows_h=f_rows_h, out_rows_h=out_rows_h,
        )

    def _layer_exec_cached(self, l: int, lp: LayerPlan, tr: _LayerTransfer,
                           staged, cops: _CacheLayerOps, prev_dev):
        """Cached variant of :meth:`_layer_exec`: assemble the device
        workspaces from staged cold misses + cached hot slots, patch the
        new view on device from the previous layer's still-resident
        outputs, run the identical kernel, then refresh written slots in
        place from the kernel outputs (bitwise-equal to the uncached path
        — hits/misses partition the rows, and the float32 D2H→H2D
        round-trip the uncached patch takes is value-preserving)."""
        if staged is None:
            return None
        cache = self._cache
        nh, ns = tr.need_h.shape[0], tr.srows.shape[0]
        h_old_m, a_m, nct_m, h_cur_m = (staged["h_old"], staged["a"],
                                        staged["nct"], staged["h_cur"])
        self.transfers.rows_up += h_old_m.shape[0] + 3 * a_m.shape[0]
        self.transfers.bytes_up += (h_old_m.nbytes + a_m.nbytes
                                    + nct_m.nbytes + h_cur_m.nbytes)

        dev = jax.device_put((
            h_old_m, a_m, nct_m, h_cur_m,
            tr.deg_old_rows, tr.deg_new_rows,
            tr.e_src, tr.e_dst, lp.e_rowidx, lp.e_sign, lp.e_use_new,
            lp.e_w, lp.e_t, lp.e_mask,
            tr.touch_rows_s, lp.touch_mask,
            tr.f_rows_s, lp.f_mask, tr.f_src, lp.f_rowidx, lp.f_w,
            lp.f_t, lp.f_emask,
            tr.out_rows_s, lp.out_mask, tr.f_rows_h, tr.out_rows_h,
        ))
        (h_old_md, a_md, nct_md, h_cur_md, deg_old_d, deg_new_d,
         e_src, e_dst, e_rowidx, e_sign, e_use_new, e_w, e_t, e_mask,
         touch_rows_s, touch_mask, f_rows_s, f_mask, f_src, f_rowidx, f_w,
         f_t, f_emask, out_rows_s, out_mask, f_rows_h, out_rows_h) = dev

        d_in = self.h[l].shape[1]
        h_old_d = _cache_assemble(
            nh, d_in, cops.h_miss_pos, h_old_md, cops.h_hit_pos,
            cache.store(("h", l), "h", (d_in,))[cops.h_hit_slots]
            if cops.h_hit_pos.size else None)
        # install freshly admitted rows from the staged pristine values
        if cops.h_admit_midx.size:
            cache.update_store(("h", l), "h", cops.h_admit_slots,
                               h_old_md[cops.h_admit_midx])
        if cops.patch_pos.size:
            h_new_d = h_old_d.at[cops.patch_pos].set(prev_dev[cops.patch_src])
        else:
            h_new_d = h_old_d

        da, dn, dc = (self.a[l].shape[1], self.nct[l].shape[1],
                      self.h[l + 1].shape[1])
        s_key = ("s", l)
        a_d = _cache_assemble(
            ns, da, cops.s_miss_pos, a_md, cops.s_hit_pos,
            cache.store(s_key, "a", (da,))[cops.s_hit_slots]
            if cops.s_hit_pos.size else None)
        nct_d = _cache_assemble(
            ns, dn, cops.s_miss_pos, nct_md, cops.s_hit_pos,
            cache.store(s_key, "nct", (dn,))[cops.s_hit_slots]
            if cops.s_hit_pos.size else None)
        h_cur_d = _cache_assemble(
            ns, dc, cops.s_miss_pos, h_cur_md, cops.s_hit_pos,
            cache.store(s_key, "h", (dc,))[cops.s_hit_slots]
            if cops.s_hit_pos.size else None)

        outs = incremental_layer(
            self.model, self.params[l],
            with_scratch(h_old_d), with_scratch(h_new_d),
            deg_old_d, deg_new_d, a_d, nct_d, h_cur_d,
            e_src, e_dst, e_rowidx, e_sign, e_use_new, e_w, e_t, e_mask,
            touch_rows_s, touch_mask,
            f_rows_s, f_mask, f_src, f_rowidx, f_w, f_t, f_emask,
            out_rows_s, out_mask,
            f_rows_h=f_rows_h, out_rows_h=out_rows_h,
        )
        # in-place slot refresh from the kernel outputs: hot written rows
        # skip the D2H→host→H2D re-staging round-trip on the next batch
        if cops.s_wb_pos.size:
            cache.update_store(s_key, "a", cops.s_wb_slots,
                               outs[0][cops.s_wb_pos])
            cache.update_store(s_key, "nct", cops.s_wb_slots,
                               outs[1][cops.s_wb_pos])
            cache.update_store(s_key, "h", cops.s_wb_slots,
                               outs[2][cops.s_wb_pos])
        if cops.hnext_wb_pos.size:
            cache.update_store(("h", l + 1), "h", cops.hnext_wb_slots,
                               outs[2][cops.hnext_wb_pos])
        return outs

    def _writeback_host(self, l: int, srows: np.ndarray, a_new: np.ndarray,
                        nct_new: np.ndarray, h_new: np.ndarray) -> None:
        """Grouped host scatter of one layer's written-back rows (runs on
        the staging worker in async mode)."""
        self.a[l][srows] = a_new
        self.nct[l][srows] = nct_new
        self.h[l + 1][srows] = h_new
        self.transfers.rows_down += 3 * srows.shape[0]
        self.transfers.bytes_down += int(a_new.nbytes + nct_new.nbytes + h_new.nbytes)

    def _final_writeback(self, payload) -> None:
        """Final layer's D2H + scatter — runs on the staging worker (async)
        or at ``flush`` (sync escape hatch)."""
        if payload is None:
            return
        l, srows, outs = payload
        if outs is None:
            return
        a_new, nct_new, h_new = (np.asarray(o) for o in outs)
        self._writeback_host(l, srows, a_new, nct_new, h_new)


# ====================================================================== #
# ShardBackend — row-sharded device state over the repro.dist mesh
# ====================================================================== #
class _StreamMeshMixin:
    """Shared 1-D stream-mesh setup for the two row-sharded backends:
    resolves (mesh, axis, S, rows_per) and the state/plan/replicated
    NamedShardings from one ``ShardingConfig``."""

    def _init_stream_mesh(self, graph: CSRGraph, mesh, num_shards, shcfg) -> None:
        from repro.dist.sharding import ShardingConfig, stream_mesh, stream_state_specs

        self.shcfg = shcfg or ShardingConfig()
        self.mesh = mesh if mesh is not None else stream_mesh(num_shards, self.shcfg)
        self.axis = tuple(self.mesh.axis_names)[0]
        self.S = int(self.mesh.shape[self.axis])
        self.rows_per = shard_rows(graph.n, self.S)
        specs = stream_state_specs(self.mesh, self.shcfg)
        self._state_sh = specs["state"]
        self._plan_sh = specs["plan"]
        self._rep_sh = specs["replicated"]


class ShardBackend(_StreamMeshMixin, StateBackend):
    """Scratch-extended per-layer state block row-partitioned over a 1-D
    ``repro.dist`` mesh as stacked ``[S, rows_per+1, ·]`` arrays; each
    batch's plan is partitioned per shard at plan time
    (:func:`repro.core.affected.shard_plan`) and runs as one donated,
    shard_map'd L-layer step (:func:`repro.core.incremental.sharded_step_fn`).

    The per-layer halo exchange is governed by
    :class:`~repro.dist.sharding.CommsConfig` (ISSUE 10): ``halo="psum"``
    broadcasts the global frontier (per-device bytes scale with the global
    frontier); ``"ppermute"`` — the ``"auto"`` default on any multi-shard
    mesh — runs the plan-time per-consumer rotation schedules, so each
    shard's traffic scales with its own halo.  Both modes are bitwise-equal
    (pinned by tests/test_comms.py)."""

    def __init__(
        self,
        model: GNNModel,
        params: Sequence[Params],
        graph: CSRGraph,
        x: np.ndarray,
        mesh=None,
        num_shards: Optional[int] = None,
        shcfg=None,
        comms=None,
        use_pallas_delta: Optional[bool] = None,
    ):
        self.model = model
        self.L = len(list(params))
        self.n = graph.n
        self.comms = _resolve_backend_comms(comms, use_pallas_delta,
                                            "ShardBackend")
        self.use_pallas_delta = self.comms.use_pallas_delta
        self._init_stream_mesh(graph, mesh, num_shards, shcfg)
        # "auto" collapses once per backend: the resolved mode is a static
        # trace key, so it must not flip batch to batch
        self.halo_mode = self.comms.resolve_halo(self.S)
        self._params_host = list(params)
        # step inputs must all live on the mesh: replicate params once
        self.params = jax.device_put(tuple(params), self._rep_sh)
        self._step = sharded_step_fn(model, self.mesh, self.axis)
        self.hwm = BucketHysteresis()
        self.halo_rows_total = 0
        self._comms_rows_sent = 0
        self._comms_bytes = 0
        self._x_host = np.asarray(x, np.float32)
        self._init_state(graph)

    # ------------------------------------------------------------------ #
    # state: stacked [S, rows_per+1, ·] blocks (last local row = scratch)
    # ------------------------------------------------------------------ #
    def _to_blocks(self, arr) -> jax.Array:
        flat = np.asarray(arr, np.float32)
        out = np.zeros((self.S, self.rows_per + 1) + flat.shape[1:], np.float32)
        for s in range(self.S):
            lo = s * self.rows_per
            hi = min(self.n, lo + self.rows_per)
            if hi > lo:
                out[s, : hi - lo] = flat[lo:hi]
        return jax.device_put(out, self._state_sh)

    def _from_blocks(self, blocks: jax.Array) -> np.ndarray:
        arr = np.asarray(blocks)[:, : self.rows_per]
        return arr.reshape(self.S * self.rows_per, *arr.shape[2:])[: self.n]

    def _init_state(self, graph: CSRGraph, x: Optional[np.ndarray] = None) -> None:
        if x is None:
            x = self._x_host
        states = full_forward(self.model, self._params_host,
                              jnp.asarray(x), graph)
        self._h: List[jax.Array] = [self._to_blocks(x)] + [
            self._to_blocks(s.h) for s in states
        ]
        self._a: List[jax.Array] = [self._to_blocks(s.a) for s in states]
        self._nct: List[jax.Array] = [self._to_blocks(s.nct) for s in states]

    def refresh(self, graph: CSRGraph) -> None:
        """Full recomputation (drift reset) over the current snapshot and the
        *current* features — layer-0 feature updates applied during the
        stream live in the h[0] blocks, not in the construction-time x."""
        self._init_state(graph, self._from_blocks(self._h[0]))

    @property
    def embeddings(self) -> np.ndarray:
        return self._from_blocks(self._h[-1])

    @property
    def h(self) -> List[np.ndarray]:
        return [self._from_blocks(v) for v in self._h]

    @property
    def a(self) -> List[np.ndarray]:
        return [self._from_blocks(v) for v in self._a]

    @property
    def nct(self) -> List[np.ndarray]:
        return [self._from_blocks(v) for v in self._nct]

    def state_bytes(self) -> int:
        return sum(int(np.prod(v.shape)) * 4 for v in (*self._h, *self._a, *self._nct))

    def sync_arrays(self) -> list:
        return [*self._h, *self._a, *self._nct]

    # ------------------------------------------------------------------ #
    # Serving API: one device gather over the stacked blocks — row g lives
    # at block [g // rows_per, g % rows_per] (scratch row is never read)
    # ------------------------------------------------------------------ #
    def snapshot_rows(self, rows: np.ndarray) -> np.ndarray:
        r = np.asarray(rows, np.int64)
        return np.asarray(self._h[-1][r // self.rows_per, r % self.rows_per])

    def changed_rows(self, prep: ShardedPlan) -> np.ndarray:
        return prep.out_rows_final

    # ------------------------------------------------------------------ #
    # policy-execution primitives: scatters round-trip through the host
    # (blocks → numpy → device_put with the state sharding) — a policy
    # batch is already a synchronous full/chunked pass, so the O(V) copy
    # is dominated by the recompute it accompanies
    # ------------------------------------------------------------------ #
    @property
    def host_params(self) -> List[Params]:
        return self._params_host  # .params is device-replicated on the mesh

    def _scatter_blocks(self, blocks: jax.Array, rows: np.ndarray,
                        vals: np.ndarray) -> jax.Array:
        host = np.array(blocks)  # np.asarray of a device array is read-only
        host[rows // self.rows_per, rows % self.rows_per] = vals
        return jax.device_put(host, self._state_sh)

    def apply_feature_updates(self, rows: np.ndarray, vals: np.ndarray) -> None:
        self._h[0] = self._scatter_blocks(
            self._h[0], np.asarray(rows, np.int64), np.asarray(vals, np.float32))

    def layer_input_host(self, l: int) -> np.ndarray:
        return self._from_blocks(self._h[l])

    def scatter_layer_rows(self, l: int, rows: np.ndarray, a_rows: np.ndarray,
                           nct_rows: np.ndarray, h_rows: np.ndarray) -> None:
        r = np.asarray(rows, np.int64)
        self._a[l] = self._scatter_blocks(self._a[l], r, a_rows)
        self._nct[l] = self._scatter_blocks(self._nct[l], r, nct_rows)
        self._h[l + 1] = self._scatter_blocks(self._h[l + 1], r, h_rows)

    # ------------------------------------------------------------------ #
    def plan(self, g_old: CSRGraph, g_new: CSRGraph, batch: UpdateBatch,
             base_plan: Optional[BatchPlan] = None) -> ShardedPlan:
        plan = (base_plan if base_plan is not None
                else build_plan(self.model, g_old, g_new, batch, self.L))
        return shard_plan(plan, self.S, batch.feat_vertices, batch.feat_values,
                          hwm=self.hwm, pallas=self.use_pallas_delta,
                          halo_mode=self.halo_mode,
                          pair_hysteresis=self.comms.pair_capacity_hysteresis)

    def comms_snapshot(self) -> CommsStats:
        return CommsStats(halo_rows_sent=self._comms_rows_sent,
                          halo_bytes=self._comms_bytes)

    def dispatch(self, sp: ShardedPlan) -> None:
        """One sharded device_put (each device gets only its plan slice),
        one shard_map'd fused-step dispatch."""
        idx_sh, flt_sh, msk_sh, pallas_sh, comms_sh = jax.device_put(
            (sp.idx_sh, sp.flt_sh, sp.msk_sh, sp.pallas_sh or (),
             sp.comms_sh or ()), self._plan_sh
        )
        fv = sp.feat_vals if sp.feat_vals is not None else np.zeros(
            (0, self._x_host.shape[1]), np.float32
        )
        idx_rep, msk_rep, feat_vals = jax.device_put(
            (sp.idx_rep, sp.msk_rep, fv), self._rep_sh
        )
        # plan-derived halo traffic: each delivered row carries its
        # old+new previous-layer views (the concatenated halo payload)
        for l, rows_l in enumerate(sp.comms_rows or ()):
            self._comms_rows_sent += rows_l
            self._comms_bytes += rows_l * 2 * int(self._h[l].shape[-1]) * 4
        hs, as_, ncts = self._step(
            sp.layout, self.params,
            tuple(self._h), tuple(self._a), tuple(self._nct),
            idx_sh, flt_sh, msk_sh, idx_rep, msk_rep, feat_vals, pallas_sh,
            comms_sh,
        )
        self._h = list(hs)
        self._a = list(as_)
        self._nct = list(ncts)
        self.halo_rows_total += sp.n_halo_rows


# ====================================================================== #
# ShardedOffloadBackend — the sharded offload hybrid (§V-B at mesh scale)
# ====================================================================== #
@dataclasses.dataclass
class _HybridPrep:
    """Host-side output of hybrid planning for one batch."""

    plan: BatchPlan
    batch: UpdateBatch
    layers: List[HybridLayerPlan]
    cache_ops: Optional[List[_CacheLayerOps]] = None

    @property
    def n_inc_edges(self) -> int:
        return self.plan.total_inc_edges()

    @property
    def n_full_edges(self) -> int:
        return self.plan.total_full_edges()

    @property
    def n_out_rows(self) -> int:
        return self.plan.total_vertices()


class ShardedOffloadBackend(_StreamMeshMixin, _DeferredWritebackMixin, StateBackend):
    """Row sharding × host-resident state: the full NeutronRT GPU-CPU
    co-processing story at mesh scale (ROADMAP "Sharded offload hybrid").

    Every shard keeps **only its own row block** of the per-layer state
    host-resident (stacked ``[S, rows_per, ·]`` numpy).  Per batch and
    layer, the plan is partitioned by destination-row owner (scatters stay
    owner-local) and each shard stages a compact ``[halo | local]``
    workspace to its device: the rows it needs but does not own (the halo)
    are gathered from the other shards' *host* blocks — the host is the
    exchange medium, so no device collective runs — together with its own
    affected rows.  Device residency is therefore O(per-shard affected
    subgraph), never O(V): the persistent state never touches HBM.

    Under ``CommsConfig(halo="ppermute")`` (the ``"auto"`` default on any
    multi-shard mesh) the uncached path additionally takes the
    **device-served fast path** (ISSUE 10): the rows of each layer's
    gather set that the previous layer just wrote are split out at plan
    time (``HybridLayerPlan.patch_pos``/``patch_src``) and patched on
    device from its still-resident outputs, so the staged ``h_new``
    buffer — a host-derived copy of ``h_old`` outside those rows — never
    stages at all.  Bitwise-equal to the staged path (the pristine-gather
    contract holds because halo rows are never written by the previous
    layer's owner-local scatter); pinned by tests/test_comms.py.

    The device step is one shard_map'd compact layer over the stacked
    staging buffers (:func:`repro.core.incremental.hybrid_layer_step_fn`),
    L dispatches per batch.  Host staging (the per-shard gathers and the
    write-back scatters — the dominant host cost at mesh scale) runs
    through the same :class:`~repro.serve.staging.HostStagingPipeline` as
    the flat offload backend: layer *l+1*'s gathers and layer *l-1*'s
    scatters overlap the device's compute of layer *l*, and the final
    layer's grouped write-back (D2H included) defers to the worker
    (``flush`` barrier) for plan/execute overlap.  ``async_staging=False``
    runs the identical jobs inline (bitwise-identical output)."""

    def __init__(
        self,
        model: GNNModel,
        params: Sequence[Params],
        graph: CSRGraph,
        x: np.ndarray,
        mesh=None,
        num_shards: Optional[int] = None,
        shcfg=None,
        async_staging: bool = True,
        cache: Optional[HotRowCache] = None,
        staging_depth: int = 2,
        comms=None,
    ):
        self.model = model
        self.params = list(params)
        self.L = len(self.params)
        self.n = graph.n
        self.comms = _resolve_backend_comms(comms, None,
                                            "ShardedOffloadBackend")
        self._init_stream_mesh(graph, mesh, num_shards, shcfg)
        self.halo_mode = self.comms.resolve_halo(self.S)
        self._params_dev = jax.device_put(tuple(params), self._rep_sh)
        self._step = hybrid_layer_step_fn(model, self.mesh, self.axis)
        self.hwm = BucketHysteresis()
        self.transfers = TransferStats()
        self._cache = cache
        self._staging = HostStagingPipeline(self.L, depth=staging_depth,
                                            async_mode=async_staging,
                                            name="hybrid")
        # caller (rows_up) and staging worker (rows_down) both touch the
        # per-shard accumulators — serialize the read-modify-write updates
        self._acc_lock = threading.Lock()
        # per-shard H2D+D2H row volume (the hybrid's scaling metric: each
        # shard's traffic is bounded by its own affected subgraph)
        self.per_shard_rows = np.zeros(self.S, np.int64)
        # peak bytes simultaneously staged on the mesh for one layer step —
        # the backend's entire HBM footprint (state is host-resident)
        self.peak_device_bytes = 0
        # plan-derived halo traffic (ISSUE 10): rows a shard gathers but
        # does not own, crossing through the exchange medium
        self._comms_rows_sent = 0
        self._comms_bytes = 0
        self._init_state(graph, np.asarray(x, np.float32))
        self._prewarm_cache(graph)

    # ------------------------------------------------------------------ #
    # state: host-resident per-shard row blocks [S, rows_per, ·]
    # ------------------------------------------------------------------ #
    def _gather_state_rows(self, arr: np.ndarray, rows: np.ndarray) -> np.ndarray:
        return self._gather_rows(arr, rows)

    def _to_blocks(self, arr: np.ndarray) -> np.ndarray:
        flat = np.asarray(arr, np.float32)
        out = np.zeros((self.S, self.rows_per) + flat.shape[1:], np.float32)
        for s in range(self.S):
            lo = s * self.rows_per
            hi = min(self.n, lo + self.rows_per)
            if hi > lo:
                out[s, : hi - lo] = flat[lo:hi]
        return out

    def _from_blocks(self, blocks: np.ndarray) -> np.ndarray:
        return blocks.reshape(self.S * self.rows_per, *blocks.shape[2:])[: self.n]

    def _gather_rows(self, blocks: np.ndarray, rows: np.ndarray) -> np.ndarray:
        """Gather global rows out of the per-shard host blocks."""
        return blocks[rows // self.rows_per, rows % self.rows_per]

    def _scatter_rows(self, blocks: np.ndarray, rows: np.ndarray,
                      vals: np.ndarray) -> None:
        blocks[rows // self.rows_per, rows % self.rows_per] = vals

    def _init_state(self, graph: CSRGraph, x: Optional[np.ndarray] = None) -> None:
        if x is None:
            x = self._from_blocks(self.h[0])
        states = full_forward(self.model, self.params, jnp.asarray(x), graph)
        self.h: List[np.ndarray] = [self._to_blocks(x)] + [
            self._to_blocks(np.asarray(s.h)) for s in states
        ]
        self.a: List[np.ndarray] = [self._to_blocks(np.asarray(s.a)) for s in states]
        self.nct: List[np.ndarray] = [self._to_blocks(np.asarray(s.nct)) for s in states]

    def refresh(self, graph: CSRGraph) -> None:
        self.flush()
        self._init_state(graph)
        if self._cache is not None:  # every cached row may now be stale
            self._cache.invalidate_all()

    @property
    def embeddings(self) -> np.ndarray:
        self.flush()
        return self._from_blocks(self.h[-1])

    def state_bytes(self) -> int:
        return sum(v.nbytes for v in (*self.h, *self.a, *self.nct))

    def sync_arrays(self) -> list:
        return []  # flush() is the real barrier; state is host numpy

    # ------------------------------------------------------------------ #
    # Serving API: flush() first so the worker's deferred final write-back
    # can never be missed (a no-op at a version boundary), then gather from
    # the per-shard host blocks
    # ------------------------------------------------------------------ #
    def snapshot_rows(self, rows: np.ndarray) -> np.ndarray:
        self.flush()
        return self._gather_rows(self.h[-1], np.asarray(rows, np.int64))

    def changed_rows(self, prep: _HybridPrep) -> np.ndarray:
        tr = prep.layers[-1]
        return np.unique(tr.srows[tr.srows_mask].astype(np.int64))

    # ------------------------------------------------------------------ #
    # policy-execution primitives: scatters into the per-shard host blocks
    # (the orchestrator flushes first, so the staging worker is drained)
    # ------------------------------------------------------------------ #
    def apply_feature_updates(self, rows: np.ndarray, vals: np.ndarray) -> None:
        rows = np.asarray(rows, np.int64)
        self._scatter_rows(self.h[0], rows, np.asarray(vals, np.float32))
        if self._cache is not None:
            self._cache.invalidate(("h", 0), rows)

    def layer_input_host(self, l: int) -> np.ndarray:
        return self._from_blocks(self.h[l])

    def scatter_layer_rows(self, l: int, rows: np.ndarray, a_rows: np.ndarray,
                           nct_rows: np.ndarray, h_rows: np.ndarray) -> None:
        r = np.asarray(rows, np.int64)
        self._scatter_rows(self.a[l], r, a_rows)
        self._scatter_rows(self.nct[l], r, nct_rows)
        self._scatter_rows(self.h[l + 1], r, h_rows)
        if self._cache is not None:  # value-independent: keyed by rows only
            self._cache.invalidate(("s", l), r)
            self._cache.invalidate(("h", l + 1), r)

    # ------------------------------------------------------------------ #
    # planning phase (host only, value-independent)
    # ------------------------------------------------------------------ #
    def plan(self, g_old: CSRGraph, g_new: CSRGraph, batch: UpdateBatch,
             base_plan: Optional[BatchPlan] = None) -> _HybridPrep:
        plan = (base_plan if base_plan is not None
                else build_plan(self.model, g_old, g_new, batch, self.L))
        hp = hybrid_plan(plan, self.S, hwm=self.hwm,
                         feat_vertices=batch.feat_vertices,
                         halo_mode=self.halo_mode)
        cache_ops = (self._plan_cache(plan, batch, hp.layers)
                     if self._cache is not None else None)
        return _HybridPrep(plan=plan, batch=batch, layers=hp.layers,
                           cache_ops=cache_ops)

    def comms_snapshot(self) -> CommsStats:
        return CommsStats(halo_rows_sent=self._comms_rows_sent,
                          halo_bytes=self._comms_bytes)

    def _plan_cache(self, plan: BatchPlan, batch: UpdateBatch,
                    layers: List[HybridLayerPlan]) -> List[_CacheLayerOps]:
        """Plan-time residency split over the stacked ``[S, cap]`` hybrid
        workspaces.  Cache keys are global row ids (a hot halo row is
        cached once, served to every shard that stages it); all positions
        are flattened ``[S·cap]`` indices so the cached exec scatters
        straight into the flat workspace view."""
        cache = self._cache
        n = plan.deg_old.shape[0] - 1  # deg tables carry a scratch slot
        deg = plan.deg_new
        cache.decay_tick()
        prev_rows = self._cache_invalidate_feats(batch)
        prev_live_pos: Optional[np.ndarray] = None
        ops: List[_CacheLayerOps] = []
        for l, tr in enumerate(layers):
            live_pos_h = np.flatnonzero(tr.need_mask.reshape(-1)).astype(np.int64)
            rows_h = tr.need_h.reshape(-1)[live_pos_h].astype(np.int64)
            live_pos_s = np.flatnonzero(tr.srows_mask.reshape(-1)).astype(np.int64)
            rows_s = tr.srows.reshape(-1)[live_pos_s].astype(np.int64)
            h_split, s_split, s_wb, hn_wb = self._cache_layer_ops(
                l, n, rows_h, rows_s, prev_rows, deg)
            dst_keys = np.where(tr.need_mask, tr.need_h, -1).reshape(-1)
            patch_pos, patch_src = _patch_positions(dst_keys, prev_rows)
            if l > 0:  # compose: index into srows_flat → flat ws position
                patch_src = prev_live_pos[patch_src]
            ops.append(_CacheLayerOps(
                h_hit_pos=live_pos_h[h_split.hit_pos],
                h_hit_slots=h_split.hit_slots,
                h_miss_pos=live_pos_h[h_split.miss_pos],
                h_miss_src=h_split.miss_rows,
                h_admit_midx=h_split.admit_midx,
                h_admit_slots=h_split.admit_slots,
                patch_pos=patch_pos, patch_src=patch_src,
                s_hit_pos=live_pos_s[s_split.hit_pos],
                s_hit_slots=s_split.hit_slots,
                s_miss_pos=live_pos_s[s_split.miss_pos],
                s_miss_src=s_split.miss_rows,
                s_wb_pos=live_pos_s[s_wb[0]], s_wb_slots=s_wb[1],
                hnext_wb_pos=live_pos_s[hn_wb[0]], hnext_wb_slots=hn_wb[1]))
            prev_rows, prev_live_pos = rows_s, live_pos_s
        return ops

    # ------------------------------------------------------------------ #
    def dispatch(self, prep: _HybridPrep) -> None:
        """Same staging schedule as :meth:`OffloadBackend.dispatch`, over
        per-shard stacked buffers: pristine gathers for all layers enqueue
        up front, each layer's new-view rows are patched with the previous
        layer's fresh outputs, and the write-back scatters (host blocks are
        the halo-exchange medium between layers) retire on the worker while
        the device computes the next layer."""
        pipe = self._staging
        if not pipe.async_mode:
            self.flush()  # inline staging jobs read host state directly
        pipe.begin_batch()
        batch = prep.batch

        if batch.feat_vertices is not None and batch.feat_vertices.size:
            prev_rows = np.asarray(batch.feat_vertices, np.int64)
            prev_new = np.asarray(batch.feat_values, np.float32)
        else:
            prev_rows = np.zeros(0, np.int64)
            prev_new = np.zeros((0, self.h[0].shape[2]), np.float32)

        ops = prep.cache_ops
        tickets = [
            pipe.submit_gather(partial(self._gather_layer, l, tr,
                                       pipe.buffers(l),
                                       None if ops is None else ops[l]),
                               tag=l)
            for l, tr in enumerate(prep.layers)
        ]
        if prev_rows.size:
            pipe.submit_writeback(
                partial(self._scatter_feats, prev_rows, prev_new),
                nbytes=int(prev_new.nbytes), tag="feat")

        # plan-derived halo traffic: every live need row with a remote
        # owner crosses the exchange medium once (legacy mode twice — the
        # staged h_new copy ships the same remote rows again)
        h_new_copies = 1 if self.halo_mode == "ppermute" else 2
        for l, tr in enumerate(prep.layers):
            self._comms_rows_sent += tr.n_halo_remote * h_new_copies
            self._comms_bytes += (tr.n_halo_remote
                                  * int(self.h[l].shape[2]) * 4 * h_new_copies)

        # cached / device-served paths: the previous layer's stacked
        # outputs stay resident so the new-view patch happens on device
        # (flat [S·cap] positions)
        prev_dev = jnp.asarray(prev_new) if prev_rows.size else None
        final = None
        for l, tr in enumerate(prep.layers):
            staged = pipe.wait_gather(tickets[l])
            if ops is None:
                outs = self._layer_exec(l, tr, staged, prev_rows, prev_new,
                                        prev_dev)
                if self.halo_mode == "ppermute":
                    prev_dev = outs[2].reshape(self.S * tr.ns_cap, -1)
            else:
                outs = self._layer_exec_cached(l, tr, staged, ops[l], prev_dev)
                prev_dev = outs[2].reshape(self.S * tr.ns_cap, -1)
            srows_flat = tr.srows[tr.srows_mask]
            if l + 1 < self.L:
                a_np, nct_np, h_np = pipe.wait_device(outs)
                pipe.submit_writeback(
                    partial(self._writeback_host, l, tr, srows_flat,
                            a_np, nct_np, h_np),
                    nbytes=int(a_np.nbytes + nct_np.nbytes + h_np.nbytes),
                    tag=l)
                prev_rows, prev_new = srows_flat, h_np[tr.srows_mask]
            else:
                final = (l, tr, srows_flat, outs)
        self._defer_final(final)

    def _scatter_feats(self, rows: np.ndarray, vals: np.ndarray) -> None:
        self._scatter_rows(self.h[0], rows, vals)

    def _gather_layer(self, l: int, tr: HybridLayerPlan, bufs,
                      cops: Optional[_CacheLayerOps] = None):
        """Staging-worker job: pristine per-shard gather of layer ``l``'s
        stacked ``[S, cap, ·]`` workspace rows.  Block-contiguous row
        ownership makes the flat view's index the global row id, so the
        gathers fill the double-buffered staging sets with one ``np.take``
        each.  With the hot-row cache enabled only the plan's cold misses
        stage (flat row lists; every miss is a live position, and the
        assembled workspace's dead positions are zero by construction).

        In device-served halo mode (``halo_mode != "psum"``) the host
        ``h_new`` copy is skipped entirely: the previous layer's stacked
        outputs stay device-resident and :meth:`_layer_exec` patches the
        new view from them, so the staging pipeline never ships the same
        bytes twice.  In legacy psum mode the copy is still staged, but
        keyed ``"_h_new"`` so the staging accountant counts only bytes
        actually read from host state — the copy derives byte-for-byte
        from the ``h_old`` gather in the same job (the old double-count
        inflated ``staged_bytes`` whenever a halo row was needed by two
        consecutive layers)."""
        if cops is not None:
            d_in = self.h[l].shape[2]
            nh_m, ns_m = cops.h_miss_src.shape[0], cops.s_miss_src.shape[0]
            h_old = bufs.take("h_old", nh_m, (d_in,))
            np.take(self.h[l].reshape(self.S * self.rows_per, d_in),
                    cops.h_miss_src, axis=0, out=h_old)

            def gather_miss(name, blocks):
                d = blocks.shape[2]
                rows = bufs.take(name, ns_m, (d,))
                np.take(blocks.reshape(self.S * self.rows_per, d),
                        cops.s_miss_src, axis=0, out=rows)
                return rows

            return {"h_old": h_old, "a": gather_miss("a", self.a[l]),
                    "nct": gather_miss("nct", self.nct[l]),
                    "h_cur": gather_miss("h_cur", self.h[l + 1])}
        S, nh_cap, ns_cap = self.S, tr.nh_cap, tr.ns_cap
        live_h, live_s = tr.need_mask, tr.srows_mask
        d_in = self.h[l].shape[2]

        h_old = bufs.take("h_old", S * nh_cap, (d_in,))
        np.take(self.h[l].reshape(S * self.rows_per, d_in),
                tr.need_h.reshape(-1), axis=0, out=h_old)
        h_old = h_old.reshape(S, nh_cap, d_in)
        h_old[~live_h] = 0.0
        h_new = None
        if self.halo_mode == "psum":
            h_new = bufs.take("h_new", S * nh_cap,
                              (d_in,)).reshape(S, nh_cap, d_in)
            np.copyto(h_new, h_old)

        def gather_state(name, blocks):
            d = blocks.shape[2]
            rows = bufs.take(name, S * ns_cap, (d,))
            np.take(blocks.reshape(S * self.rows_per, d),
                    tr.srows.reshape(-1), axis=0, out=rows)
            rows = rows.reshape(S, ns_cap, d)
            rows[~live_s] = 0.0
            return rows

        out = {"h_old": h_old,
               "a": gather_state("a", self.a[l]),
               "nct": gather_state("nct", self.nct[l]),
               "h_cur": gather_state("h_cur", self.h[l + 1])}
        if h_new is not None:
            out["_h_new"] = h_new
        return out

    def _layer_exec(self, l: int, tr: HybridLayerPlan, staged,
                    prev_rows: np.ndarray, prev_new: np.ndarray,
                    prev_dev=None):
        """Patch the new-view rows, ship one sharded device_put (each
        device receives only its slice), one shard_map'd compact layer
        step.

        Device-served fast path (``halo_mode != "psum"``): the staged
        dict carries no ``_h_new`` buffer.  The old view is shipped once
        and the new view is built on device by scattering the previous
        layer's resident stacked outputs into the plan-time
        ``patch_pos``/``patch_src`` positions — halo rows are pristine
        by the gather contract (the previous layer's local scatter never
        writes remote-owned rows), so the unpatched positions already
        hold the correct old=new values."""
        S, nh_cap = self.S, tr.nh_cap
        live_h, live_s = tr.need_mask, tr.srows_mask
        h_old_rows = staged["h_old"]
        a_rows, nct_rows, h_cur_rows = staged["a"], staged["nct"], staged["h_cur"]
        nh_live = live_h.sum(axis=1)
        ns_live = live_s.sum(axis=1)

        if self.halo_mode != "psum":
            with self._acc_lock:
                self.transfers.rows_up += int(nh_live.sum() + 3 * ns_live.sum())
                self.transfers.bytes_up += (h_old_rows.nbytes + a_rows.nbytes
                                            + nct_rows.nbytes + h_cur_rows.nbytes)
                self.per_shard_rows += nh_live + 3 * ns_live
            dev = jax.device_put(
                (h_old_rows, a_rows, nct_rows, h_cur_rows,
                 tr.idx_sh, tr.flt_sh, tr.msk_sh),
                self._plan_sh,
            )
            (h_old_d, a_d, nct_d, h_cur_d, idx_d, flt_d, msk_d) = dev
            d_in = h_old_rows.shape[2]
            h_old_flat = h_old_d.reshape(S * nh_cap, d_in)
            if tr.patch_pos is not None and tr.patch_pos.size and prev_dev is not None:
                h_new_flat = h_old_flat.at[tr.patch_pos].set(
                    prev_dev[tr.patch_src])
            else:
                h_new_flat = h_old_flat
            h_new_d = jax.device_put(h_new_flat.reshape(S, nh_cap, d_in),
                                     self._plan_sh)
            self.peak_device_bytes = max(
                self.peak_device_bytes,
                sum(int(d.nbytes) for d in dev) + int(h_new_d.nbytes),
            )
            return self._step(tr.layout, self._params_dev[l],
                              h_old_d, h_new_d, a_d, nct_d, h_cur_d,
                              idx_d, flt_d, msk_d)

        h_new_rows = staged["_h_new"]
        flat_new = h_new_rows.reshape(S * nh_cap, -1)
        _override_rows(flat_new, np.where(live_h, tr.need_h, -1).reshape(-1),
                       prev_rows, prev_new)
        h_new_rows = flat_new.reshape(S, nh_cap, -1)

        with self._acc_lock:
            self.transfers.rows_up += int(2 * nh_live.sum() + 3 * ns_live.sum())
            self.transfers.bytes_up += (2 * h_new_rows.nbytes + a_rows.nbytes
                                        + nct_rows.nbytes + h_cur_rows.nbytes)
            self.per_shard_rows += 2 * nh_live + 3 * ns_live

        # one sharded H2D transfer: each device receives only its slice
        dev = jax.device_put(
            (h_old_rows, h_new_rows, a_rows, nct_rows, h_cur_rows,
             tr.idx_sh, tr.flt_sh, tr.msk_sh),
            self._plan_sh,
        )
        self.peak_device_bytes = max(
            self.peak_device_bytes, sum(int(d.nbytes) for d in dev)
        )
        (h_old_d, h_new_d, a_d, nct_d, h_cur_d, idx_d, flt_d, msk_d) = dev
        return self._step(tr.layout, self._params_dev[l],
                          h_old_d, h_new_d, a_d, nct_d, h_cur_d,
                          idx_d, flt_d, msk_d)

    def _layer_exec_cached(self, l: int, tr: HybridLayerPlan, staged,
                           cops: _CacheLayerOps, prev_dev):
        """Cached variant of :meth:`_layer_exec`: assemble the flat
        ``[S·cap, ·]`` workspaces from staged cold misses + cached hot
        slots (dead positions stay 0.0, matching the host gather's
        zeroing), patch the new view on device, reshard to the stacked
        per-shard layout, run the identical step, then refresh written
        slots in place from the stacked outputs."""
        cache = self._cache
        S, nh_cap, ns_cap = self.S, tr.nh_cap, tr.ns_cap
        h_old_m, a_m, nct_m, h_cur_m = (staged["h_old"], staged["a"],
                                        staged["nct"], staged["h_cur"])
        h_miss_sh = np.bincount(cops.h_miss_pos // nh_cap, minlength=S)
        s_miss_sh = np.bincount(cops.s_miss_pos // ns_cap, minlength=S)
        with self._acc_lock:
            self.transfers.rows_up += int(h_miss_sh.sum() + 3 * s_miss_sh.sum())
            self.transfers.bytes_up += (h_old_m.nbytes + a_m.nbytes
                                        + nct_m.nbytes + h_cur_m.nbytes)
            self.per_shard_rows += h_miss_sh + 3 * s_miss_sh

        h_old_md, a_md, nct_md, h_cur_md = jax.device_put(
            (h_old_m, a_m, nct_m, h_cur_m))
        d_in = self.h[l].shape[2]
        h_old_flat = _cache_assemble(
            S * nh_cap, d_in, cops.h_miss_pos, h_old_md, cops.h_hit_pos,
            cache.store(("h", l), "h", (d_in,))[cops.h_hit_slots]
            if cops.h_hit_pos.size else None)
        if cops.h_admit_midx.size:
            cache.update_store(("h", l), "h", cops.h_admit_slots,
                               h_old_md[cops.h_admit_midx])
        if cops.patch_pos.size:
            h_new_flat = h_old_flat.at[cops.patch_pos].set(
                prev_dev[cops.patch_src])
        else:
            h_new_flat = h_old_flat

        da, dn, dc = (self.a[l].shape[2], self.nct[l].shape[2],
                      self.h[l + 1].shape[2])
        s_key = ("s", l)
        a_flat = _cache_assemble(
            S * ns_cap, da, cops.s_miss_pos, a_md, cops.s_hit_pos,
            cache.store(s_key, "a", (da,))[cops.s_hit_slots]
            if cops.s_hit_pos.size else None)
        nct_flat = _cache_assemble(
            S * ns_cap, dn, cops.s_miss_pos, nct_md, cops.s_hit_pos,
            cache.store(s_key, "nct", (dn,))[cops.s_hit_slots]
            if cops.s_hit_pos.size else None)
        h_cur_flat = _cache_assemble(
            S * ns_cap, dc, cops.s_miss_pos, h_cur_md, cops.s_hit_pos,
            cache.store(s_key, "h", (dc,))[cops.s_hit_slots]
            if cops.s_hit_pos.size else None)

        # explicit reshard to the stacked per-shard layout for shard_map
        dev = jax.device_put(
            (h_old_flat.reshape(S, nh_cap, d_in),
             h_new_flat.reshape(S, nh_cap, d_in),
             a_flat.reshape(S, ns_cap, da), nct_flat.reshape(S, ns_cap, dn),
             h_cur_flat.reshape(S, ns_cap, dc),
             tr.idx_sh, tr.flt_sh, tr.msk_sh),
            self._plan_sh,
        )
        self.peak_device_bytes = max(
            self.peak_device_bytes, sum(int(d.nbytes) for d in dev)
        )
        (h_old_d, h_new_d, a_d, nct_d, h_cur_d, idx_d, flt_d, msk_d) = dev
        outs = self._step(tr.layout, self._params_dev[l],
                          h_old_d, h_new_d, a_d, nct_d, h_cur_d,
                          idx_d, flt_d, msk_d)
        if cops.s_wb_pos.size:
            a_o = outs[0].reshape(S * ns_cap, -1)
            nct_o = outs[1].reshape(S * ns_cap, -1)
            h_o = outs[2].reshape(S * ns_cap, -1)
            cache.update_store(s_key, "a", cops.s_wb_slots,
                               a_o[cops.s_wb_pos])
            cache.update_store(s_key, "nct", cops.s_wb_slots,
                               nct_o[cops.s_wb_pos])
            cache.update_store(s_key, "h", cops.s_wb_slots,
                               h_o[cops.s_wb_pos])
        if cops.hnext_wb_pos.size:
            cache.update_store(
                ("h", l + 1), "h", cops.hnext_wb_slots,
                outs[2].reshape(S * ns_cap, -1)[cops.hnext_wb_pos])
        return outs

    def _writeback_host(self, l: int, tr: HybridLayerPlan,
                        srows_flat: np.ndarray, a_new: np.ndarray,
                        nct_new: np.ndarray, h_new: np.ndarray) -> None:
        """Grouped per-shard host scatter of one layer's written-back rows
        (runs on the staging worker in async mode) — the host blocks are
        the halo-exchange medium between layers."""
        live = tr.srows_mask
        self._scatter_rows(self.a[l], srows_flat, a_new[live])
        self._scatter_rows(self.nct[l], srows_flat, nct_new[live])
        self._scatter_rows(self.h[l + 1], srows_flat, h_new[live])
        with self._acc_lock:
            self.transfers.rows_down += 3 * int(srows_flat.shape[0])
            self.transfers.bytes_down += int(a_new[live].nbytes
                                             + nct_new[live].nbytes
                                             + h_new[live].nbytes)
            self.per_shard_rows += 3 * live.sum(axis=1)

    def _final_writeback(self, payload) -> None:
        if payload is None:
            return
        l, tr, srows_flat, outs = payload
        a_new, nct_new, h_new = (np.asarray(o) for o in outs)
        self._writeback_host(l, tr, srows_flat, a_new, nct_new, h_new)


# ====================================================================== #
# ChunkedBackend — host-resident state, chunked full-recompute execution
# ====================================================================== #
@dataclasses.dataclass
class _ChunkedPrep:
    """Prepared plan for the chunked substrate: the Alg.-4 affected sets
    plus the post-batch graph (the chunk scheduler re-reads CSR edges at
    execution time instead of baking transfer tables at plan time)."""

    plan: BatchPlan
    batch: UpdateBatch
    g_new: CSRGraph
    rows_per_layer: List[np.ndarray]  # live out_rows per layer (global ids)

    @property
    def n_inc_edges(self) -> int:
        return self.plan.total_inc_edges()

    @property
    def n_full_edges(self) -> int:
        return self.plan.total_full_edges()

    @property
    def n_out_rows(self) -> int:
        return self.plan.total_vertices()


class ChunkedBackend(StateBackend):
    """Host-resident state executed through the §V-C chunked scheduler.

    The per-layer state lives as host numpy (like :class:`OffloadBackend`)
    but each batch executes by *constrained re-computation*: per layer, the
    planner's live ``out_rows`` (⊇ touch ∪ full rows, i.e. every row whose
    a/nct/h may change) are recomputed from the post-batch graph through
    :class:`repro.serve.scheduler.ChunkedLayerScheduler` —
    destination-vertex chunks with inter-chunk shard-embedding reuse, so
    device residency is bounded by ``chunk_size`` regardless of how large a
    batch's affected subgraph grows.  This is the fallback substrate for
    affected sets too big to stage at once; output matches the incremental
    substrates to numerical tolerance (recompute vs. signed incremental
    accumulation), not bitwise — the cross-backend matrix covers it
    (tests/test_backends.py).

    Serving API: state is plain host numpy with no deferred write-back, so
    ``snapshot_rows`` is a direct gather and ``changed_rows`` is the final
    layer's planned recompute set."""

    def __init__(self, model: GNNModel, params: Sequence[Params],
                 graph: CSRGraph, x: np.ndarray, chunk_size: int = 8192,
                 chunk_reuse: bool = True):
        # deferred import: repro.serve.scheduler pulls repro.core.full
        # while this module is itself mid-import under repro.core.__init__
        from repro.serve.scheduler import ChunkedLayerScheduler

        self.model = model
        self.params = list(params)
        self.L = len(self.params)
        self.x = np.asarray(x, np.float32)
        self.scheduler = ChunkedLayerScheduler(model, chunk_size=chunk_size,
                                               reuse=chunk_reuse)
        states = full_forward(model, params, jnp.asarray(self.x), graph)
        self.h: List[np.ndarray] = [self.x.copy()] + [np.array(s.h) for s in states]
        self.a: List[np.ndarray] = [np.array(s.a) for s in states]
        self.nct: List[np.ndarray] = [np.array(s.nct) for s in states]

    @property
    def embeddings(self) -> np.ndarray:
        return self.h[-1]

    def state_bytes(self) -> int:
        return (sum(a.nbytes for a in self.a) + sum(c.nbytes for c in self.nct)
                + sum(h.nbytes for h in self.h))

    def sync_arrays(self) -> list:
        return []  # dispatch is synchronous; state is host numpy

    def refresh(self, graph: CSRGraph) -> None:
        states = full_forward(self.model, self.params, jnp.asarray(self.h[0]),
                              graph)
        self.h = [self.h[0]] + [np.array(s.h) for s in states]
        self.a = [np.array(s.a) for s in states]
        self.nct = [np.array(s.nct) for s in states]

    # ------------------------------------------------------------------ #
    # Serving API
    # ------------------------------------------------------------------ #
    def snapshot_rows(self, rows: np.ndarray) -> np.ndarray:
        return self.h[-1][np.asarray(rows, np.int64)]

    def changed_rows(self, prep: "_ChunkedPrep") -> np.ndarray:
        return prep.rows_per_layer[-1]

    # ------------------------------------------------------------------ #
    # policy-execution primitives: this substrate's native dispatch *is*
    # the chunked mode — the policy path shares its scheduler (and its
    # reuse/transfer counters), making policy-chosen chunked batches
    # bitwise-identical to native ones
    # ------------------------------------------------------------------ #
    def chunk_scheduler(self):
        return self.scheduler

    def apply_feature_updates(self, rows: np.ndarray, vals: np.ndarray) -> None:
        self.h[0][np.asarray(rows, np.int64)] = np.asarray(vals, np.float32)

    def layer_input_host(self, l: int) -> np.ndarray:
        return self.h[l]

    def scatter_layer_rows(self, l: int, rows: np.ndarray, a_rows: np.ndarray,
                           nct_rows: np.ndarray, h_rows: np.ndarray) -> None:
        self.a[l][rows] = a_rows
        self.nct[l][rows] = nct_rows
        self.h[l + 1][rows] = h_rows

    # ------------------------------------------------------------------ #
    def plan(self, g_old: CSRGraph, g_new: CSRGraph, batch: UpdateBatch,
             base_plan: Optional[BatchPlan] = None) -> _ChunkedPrep:
        plan = (base_plan if base_plan is not None
                else build_plan(self.model, g_old, g_new, batch, self.L))
        rows = [np.unique(lp.out_rows[lp.out_mask].astype(np.int64))
                for lp in plan.layers]
        return _ChunkedPrep(plan=plan, batch=batch, g_new=g_new,
                            rows_per_layer=rows)

    def dispatch(self, prep: _ChunkedPrep) -> None:
        """Layer-by-layer chunked recompute of the affected rows.  Layer
        ``l`` reads ``h[l]`` *after* the previous layer's write-back (and
        the batch's feature scatter for layer 0), so the recompute sees
        exactly the incremental substrates' layer inputs."""
        batch = prep.batch
        if batch.feat_vertices is not None and batch.feat_vertices.size:
            self.h[0][np.asarray(batch.feat_vertices, np.int64)] = np.asarray(
                batch.feat_values, np.float32)
        deg = prep.plan.deg_new[:-1]  # [n] new-graph degrees (drop scratch)
        for l in range(self.L):
            rows = prep.rows_per_layer[l]
            if not rows.size:
                continue
            a_r, nct_r, h_r = self.scheduler.run_layer(
                self.params[l], prep.g_new, self.h[l], rows, deg)
            self.a[l][rows] = a_r
            self.nct[l][rows] = nct_r
            self.h[l + 1][rows] = h_r
