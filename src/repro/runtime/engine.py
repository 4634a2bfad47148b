"""Continuous-batching TD-VMM serving engine.

The paper's system discipline — fixed conversion circuitry, time-multiplexed
inputs — maps onto serving as: keep exactly TWO jit-compiled step functions
(one fixed-shape chunked-prefill step, one fixed-shape batched-decode step)
and multiplex a ragged request stream through them.  Ragged traffic is
absorbed by:

  * a fixed pool of B decode **slots** (the decode step's batch dimension),
    admitted FIFO by arrival (``runtime/scheduler.py``);
  * a **paged** KV cache: attention KV lives in fixed-size pages owned per
    request via block tables (``runtime/paged_cache.py``), so short requests
    stop paying ``max_len`` memory and finished requests' pages recycle;
    a model's window layers keep theirs in a fixed ring of pages per slot
    (``paged_cache.ring_pages``: the window plus one chunk);
  * **chunked prefill**: prompts are absorbed ``chunk`` tokens per step
    through the single compiled prefill shape, interleaved with decode.

Calibration enters the compiled steps as **runtime-operand windows**
(``core.calibration.runtime_windows``): the pinned ``CalibrationState``
threads through the two jits as a site -> f32 array dict argument, NOT as
baked jit-static constants — bit-identical to the baked path (the kernels
already pin windows behind optimization barriers), and hot-swappable: a
recaptured state replaces the dict values between steps with zero
recompilation, keeping ``compiled_steps == 2`` under online recalibration.

Request lifecycle::

    pending --admit(slot+pages)--> prefilling --last chunk--> decoding
       |                                                         |
       +--> evicted (prompt exceeds page budget)                 +--> eos
       +--> rejected (SLA admission: deadline- or                +--> max_tokens
            joule-infeasible, before any compute)                +--> evicted
                                                                 +--> failed
                                                                 +--> over_budget
                                                   (evicted: page budget
                                                    exhausted — finished
                                                    BEFORE the overflowing
                                                    write; failed: a
                                                    persistently failing
                                                    compiled step, blamed
                                                    on one request so the
                                                    engine keeps serving;
                                                    over_budget: joule
                                                    budget crossed
                                                    mid-stream under an
                                                    SLA policy)

Fault tolerance (``FaultConfig``): a ``fault.PreemptionGuard`` (or an
injected ``faultinject.PreemptAt``) unwinds the run between steps to a
**snapshot** — the full in-flight state (scheduler queue, slots, block
tables, page-pool free list, paged KV pools, emitted tokens, energy
accounting, runtime windows) as one checkpointable pytree — with the hard
contract that ``restore`` + ``resume`` replays the remaining trace
bit-identically to an uninterrupted run.  ``fault.retry_step`` wraps both
compiled steps (transient failures recover invisibly; persistent ones
degrade to a single ``failed`` request with neighbors bit-equal), and
``StragglerMonitor`` / ``Heartbeat`` feed the report.

Energy: every processed token is priced by the resolved plan's analog-tile
geometry (``core.energy.serving_energy_model``) into per-request Op counts
and joules — the fJ/Op currency of the paper, measured at request level.

Telemetry & SLA (``runtime/telemetry.py`` / ``runtime/sla.py``): pass
``sink=`` to stream per-tick metrics (step latency, queue depth, page
pressure, fJ/Op, retries, drift) through a ``MetricsSink`` with online
spike/regression alerts, and ``sla=`` to schedule with priority-aging
admission, deadline/joule admission control, and mid-stream ``over_budget``
enforcement.  Both are host-side bookkeeping between the two compiled
steps (``compiled_steps == 2`` holds), both ride in ``snapshot()``, and
with both disabled every existing trace replays bit-identically.

Tracing & per-site attribution (``runtime/trace.py`` / PR 10): pass
``tracer=`` to record the whole request lifecycle as Chrome-trace spans
(requests as threads, engine ticks as slices, counter tracks) stamped on a
cumulative engine clock that rides ``snapshot()`` (meta v4) — a killed,
restored engine continues the SAME trace file seamlessly.  Every report
carries ``site_attribution``: the run's priced tokens broken down by plan
site from ``core.energy.site_attribution``, whose per-site table sums
bit-exactly to the aggregate ``analog_ops``/``analog_energy_j``/``fj_per_op``
columns, with chained sites' skipped I/O conversions shown explicitly.
With ``DriftConfig.observe_every`` and a sink, per-site readout clip rates
stream as live ``clip_rate.<site>`` series for ``AlertRule`` wiring.  All
of it is host-side, between the two compiled steps: traced runs are
bit-identical to untraced and ``compiled_steps == 2`` holds.

Mesh-sharded serving: pass ``mesh=`` (axes ``data`` x ``model``) and the two
compiled steps run tensor/expert/data-parallel — params take the training
``launch/sharding._rules`` TP layout (DP replicated: no ZeRO gathers at
inference), paged pools shard their head dims over ``model``
(``sharding.paged_specs``) while the page dim stays replicated, and the DP
axes multiply the slot pool: ``total_slots = dp * ecfg.slots`` with slot id
``dp_rank * ecfg.slots + local_slot`` and one page region per rank
(``PagePool(ranks=dp)``).  The scheduler stays host-side and deterministic;
admission walks free slots in ``slot_order`` and draws pages from the slot's
rank region.  A (1, 1) mesh is bit-identical to no mesh; snapshots are
device_get on save and re-sharded on restore, so the kill-at-any-step
bit-identity contract survives under a mesh.
"""
from __future__ import annotations

import dataclasses
import json
import math
import time
from typing import Any, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.core import energy as energy_model
from repro.core.calibration import (CalibrationState, apply_calibration,
                                    clip_rate_metrics)
from repro.kernels.tdvmm import ops as tdvmm_ops
from repro.launch import meshctx
from repro.launch import sharding as shardlib
from repro.launch.mesh import axis_info
from repro.models import attention, model, transformer
from repro.runtime import fault
from repro.runtime import sla as sla_policy
from repro.runtime import trace
from repro.runtime.paged_cache import PagePool, pages_for, ring_pages
from repro.runtime.scheduler import (Request, RequestRecord, Slot,
                                     SlotScheduler, static_baseline)

__all__ = ["Engine", "EngineConfig", "EngineReport", "FaultConfig",
           "DriftConfig", "Request", "static_baseline"]


@dataclasses.dataclass(frozen=True)
class EngineConfig:
    """Engine shape/capacity knobs (all jit-static: they pin the two
    compiled step shapes)."""
    slots: int = 4                # B — decode batch width
    page_size: int = 16           # tokens per KV page
    num_pages: int = 64           # shared pool size (excludes the trash page)
    max_pages_per_slot: int = 0   # per-request page budget; 0 = num_pages
    chunk: int = 32               # C — prefill tokens absorbed per step
    eos_id: Optional[int] = None  # greedy decode stops on this token
    tile_n: int = 256             # analog tile edge for energy accounting
    slot_order: str = "fifo"      # free-slot pick order (determinism test)
    max_steps: int = 100_000      # runaway guard

    @property
    def resolved_max_pages(self) -> int:
        p = self.max_pages_per_slot or self.num_pages
        return min(p, self.num_pages)


@dataclasses.dataclass
class DriftConfig:
    """Online drift detection + recalibration policy.

    Every ``check_every`` engine steps the engine runs an *eager* probe pass
    (``models.model.drift_probe`` — the same capture as ``model.calibrate``,
    never a third compiled program) on ``probe_batch`` and compares the
    fresh windows and per-site readout clip rates against the pinned ones.
    Drift is declared when any site clips more than ``clip_threshold`` of
    its |z| mass against its pinned window, or any window moved by more than
    ``window_tol`` in |log ratio|; with ``recalibrate`` the fresh
    ``CalibrationState`` is hot-swapped in between steps (no recompile).

    ``observe_every`` > 0 additionally streams per-site readout clip rates
    into the engine's ``MetricsSink`` as ``clip_rate.<site>`` series every
    that many steps (same eager probe, never a third compiled program) —
    typically much more often than ``check_every``, so an ``AlertRule`` on
    a single site's clip rate fires minutes before the full drift check
    would recalibrate."""
    probe_batch: dict
    check_every: int = 16
    clip_threshold: float = 0.01
    window_tol: float = 0.25
    max_len: int = 0
    recalibrate: bool = True
    observe_every: int = 0


@dataclasses.dataclass
class FaultConfig:
    """Fault wiring for one ``Engine.run`` / ``resume``.

    ``guard`` polls for preemption (install it for real SIGTERM handling;
    injected preemptions use the run's internal guard); ``snapshot_dir``
    makes a preemption exit through ``checkpoint.save_engine_snapshot``.
    ``retries``/``backoff_s``/``backoff_cap_s``/``jitter`` parameterize
    ``fault.retry_step`` around both compiled steps.  ``injector`` is a
    ``faultinject.FaultInjector`` schedule; ``drift`` a ``DriftConfig``."""
    guard: Optional[fault.PreemptionGuard] = None
    snapshot_dir: Optional[str] = None
    snapshot_keep: int = 3
    retries: int = 2
    backoff_s: float = 0.01
    backoff_cap_s: float = 1.0
    jitter: float = 0.1
    heartbeat: Optional[fault.Heartbeat] = None
    monitor: Optional[fault.StragglerMonitor] = None
    injector: Optional[Any] = None
    drift: Optional[DriftConfig] = None


@dataclasses.dataclass
class EngineReport:
    """Aggregate run stats + per-request records (rid order)."""
    requests: list[dict]
    steps: int
    prefill_steps: int
    decode_steps: int
    idle_steps: int
    wall_s: float
    prompt_tokens: int
    generated_tokens: int
    utilization: float
    evictions: int
    nan_logit_steps: int
    page_high_water: int
    page_bytes: int
    kv_high_water_bytes: int
    analog_ops: float
    analog_energy_j: float
    fj_per_op: float
    tokens_per_joule: float
    compiled_steps: int
    # --- fault tolerance & drift (defaults keep old constructors valid) ---
    preempted: bool = False
    snapshot_path: Optional[str] = None
    failed: int = 0
    step_retries: int = 0
    stragglers: int = 0
    straggler_ewma_s: float = 0.0
    heartbeats: int = 0
    recalibrations: int = 0
    drift_events: list = dataclasses.field(default_factory=list)
    # --- SLA & telemetry (PR 8) -------------------------------------------
    rejected: int = 0
    over_budget: int = 0
    deadline_hits: int = 0
    deadline_misses: int = 0
    alerts: int = 0
    telemetry: Optional[dict] = None
    # --- mesh-sharded serving (PR 9) --------------------------------------
    devices: int = 1              # mesh size (1 = meshless engine)
    total_slots: int = 0          # dp_size * ecfg.slots aggregate decode width
    # --- tracing & per-site attribution (PR 10) ---------------------------
    tokens_priced: int = 0        # exact token count behind the energy totals
    site_attribution: Optional[dict] = None   # energy.site_attribution table
    trace_summary: Optional[dict] = None      # Tracer.summary() when tracing
    autotune: Optional[dict] = None           # kernels.tdvmm autotune report

    def to_json(self) -> dict:
        return dataclasses.asdict(self)


@dataclasses.dataclass
class RunState:
    """Everything one serving run mutates — the snapshot/restore unit
    (device caches + host bookkeeping + cumulative counters)."""
    requests: list[Request]
    records: dict[int, RequestRecord]
    sched: SlotScheduler
    pool: PagePool
    caches: Any
    steps: int = 0
    prefill_steps: int = 0
    decode_steps: int = 0
    idle_steps: int = 0
    prompt_tokens: int = 0
    generated_tokens: int = 0
    evictions: int = 0
    nan_steps: int = 0
    failed: int = 0
    rejected: int = 0
    over_budget: int = 0
    analog_ops: float = 0.0       # running totals (order-exact for the
    analog_energy_j: float = 0.0  # fj_per_op telemetry stream)
    tokens_priced: int = 0        # exact count of tokens through _account
    step_retries: int = 0
    recalibrations: int = 0
    last_drift_check: int = 0
    last_clip_obs: int = 0
    wall_s: float = 0.0
    util_samples: list = dataclasses.field(default_factory=list)
    kv_pages_read: int = 0        # pages the steps' KV reads touched (one
    #                               layer of each pool kind: full, window ring)
    kv_pages_live: int = 0        # of those, pages holding a written position
    kv_window_pages_read: int = 0  # of kv_pages_read, window-ring pages
    moe_rows_routed: int = 0      # (row, expert) pairs routed to the experts
    #                               held here, every MoE layer, as of the last
    #                               readback (a device count read with it)
    moe_rows_computed: int = 0    # expert buffer rows the expert launches
    #                               computed, every MoE layer
    drift_events: list = dataclasses.field(default_factory=list)
    preempted: bool = False
    snapshot_path: Optional[str] = None


class Engine:
    """Continuous-batching serving engine over ONE model + calibration.

    ``calib`` pins every enabled digital-boundary site's readout window.
    The engine *requires* pinned windows on enabled sites (or
    ``output_calibration=False``): a data-calibrated per-call window is a
    max over the whole batch, which would couple slots together and break
    the per-request bit-identity contract.  The pinned windows thread into
    the two compiled steps as runtime operands (see module docstring), so
    ``set_calibration`` can hot-swap them between steps without recompiling.
    """

    def __init__(self, cfg: ModelConfig, params,
                 engine_cfg: EngineConfig = EngineConfig(),
                 calib: Optional[CalibrationState] = None,
                 sla: Optional[sla_policy.SlaConfig] = None,
                 sink: Optional[Any] = None,
                 mesh: Optional[Any] = None,
                 tracer: Optional[Any] = None):
        if cfg.family not in ("dense", "moe", "vlm", "audio"):
            raise NotImplementedError(
                f"engine supports attention families, not {cfg.family!r} "
                "(use launch.serve --static for SSM/hybrid)")
        if cfg.input_mode != "tokens":
            raise NotImplementedError("engine serves token-input models")
        sliding = transformer.segment_sliding(cfg)
        if any(sliding) and mesh is not None:
            raise NotImplementedError("window rings under a mesh")
        self.cfg = cfg
        self.ecfg = engine_cfg
        self.calib = calib
        self.sla = sla
        self.sink = sink
        self.tracer = tracer

        # --- mesh: TP shards each step's math, DP multiplies the slot pool.
        # The scheduler stays host-side and meshless — slot id =
        # dp_rank * ecfg.slots + local_slot, and every rank's page region
        # mirrors the single-device layout, so a (1,1) mesh is bit-identical
        # to no mesh at all.
        self.mesh = mesh
        if mesh is not None:
            info = axis_info(mesh)
            self._dp_axes = info["dp_axes"]
            self._tp_axis = info["tp_axis"]
            self.dp = shardlib._dp_size(mesh, self._dp_axes)
        else:
            self._dp_axes, self._tp_axis, self.dp = (), None, 1
        self.total_slots = self.dp * engine_cfg.slots
        # Window layers keep their KV in rings of ring_pages pages a slot
        # (slot s: ring pages [s * R, (s + 1) * R)); full layers in the pool.
        self._full_layers = not all(sliding)
        self.ring_pages = ring_pages(cfg.swa_window, engine_cfg.chunk,
                                     engine_cfg.page_size) if any(sliding) else 0
        self._ring_rows = np.arange(self.total_slots * self.ring_pages,
                                    dtype=np.int32).reshape(self.total_slots, -1)
        self._ring_tables = jnp.asarray(self._ring_rows) if self.ring_pages else None
        self._moe_rows_per_row = 0 if cfg.moe is None else (
            cfg.moe.resolved_held * (cfg.n_layers - cfg.moe.first_k_dense))

        self.cfg_serving = apply_calibration(cfg, calib)
        self._check_pinned_windows()
        self.energy = energy_model.serving_energy_model(
            self.cfg_serving, engine_cfg.tile_n,
            n_devices=(mesh.size if mesh is not None else 1))

        # Params: TP layout from the training _rules (heads / ffn-hidden /
        # vocab over 'model'); dp_axes=() replicates over DP — serving never
        # wants ZeRO-3 gathers in the step — while expert banks still shard
        # over DP under moe.impl='ep'.
        if mesh is not None:
            p_specs = shardlib.param_specs(
                params, cfg, mesh, dp_axes=(), ep_axes=self._dp_axes)
            params = jax.device_put(params, shardlib.to_named(p_specs, mesh))
        self.params = params

        # Windows as runtime operands: the jits trace over the window dict
        # (same sites + shapes -> same executable), never bake the values.
        self._windows = self._place_windows(
            calib.as_arrays() if calib is not None else {})

        # Per-page HBM bytes across all layers (for the high-water stat) and
        # the paged-pool shardings the two steps are pinned to.
        shapes = jax.eval_shape(self._init_caches)
        total = sum(np.prod(leaf.shape) * leaf.dtype.itemsize
                    for i, window in enumerate(sliding) if not window
                    for leaf in jax.tree.leaves(shapes[f"seg{i}"]))
        self.page_bytes = int(
            total // (self.dp * (engine_cfg.num_pages + 1)))
        self._decode_in_place = attention.reads_in_place(shapes["seg0"], mesh)
        self._cache_sh = None
        self._batch_sh = {}
        jit_kw: dict[str, Any] = {}
        if mesh is not None:
            self._cache_sh = shardlib.to_named(
                shardlib.paged_specs(shapes, cfg, mesh), mesh)
            self._batch_sh = {
                kind: shardlib.to_named(shardlib.slot_specs(mesh, kind), mesh)
                for kind in ("prefill", "decode")}
            # Pinning the cache output sharding to the input sharding is what
            # keeps compiled_steps == 2: a drifting output layout would make
            # the next call's donated input a new signature.
            jit_kw["out_shardings"] = (None, self._cache_sh)
        # Named so that a profile's program line reads jit_engine_prefill /
        # jit_engine_decode.
        def engine_prefill(p, b, c, w):
            return model.prefill_chunk(p, b, c, cfg, windows=w)

        def engine_decode(p, b, c, w):
            return model.decode_slots(p, b, c, cfg, windows=w)

        self._prefill = jax.jit(engine_prefill, donate_argnums=(2,), **jit_kw)
        self._decode = jax.jit(engine_decode, donate_argnums=(2,), **jit_kw)

        self._st: Optional[RunState] = None
        self._fault: Optional[FaultConfig] = None
        self._guard: Optional[fault.PreemptionGuard] = None

    def _init_caches(self):
        """Fresh page pools (and window rings) of this engine's shape."""
        e = self.ecfg
        return model.init_paged_caches(
            self.cfg, e.num_pages, e.page_size, ranks=self.dp,
            ring_pages=self.total_slots * self.ring_pages)

    def _read_routed(self, caches) -> None:
        """The device count of routed expert rows, read beside a step's
        readback (the step is done by then: no sync of its own).  The
        device's int32 sum wraps; the host total stays equal to it modulo
        2**32, so what was added since the last read is their difference
        modulo 2**32."""
        if "moe_routed" in caches:
            st = self._st
            st.moe_rows_routed += (int(caches["moe_routed"])
                                   - st.moe_rows_routed) % (1 << 32)

    def _place_windows(self, windows: dict) -> dict:
        """Replicate the window operands across the mesh (meshless: as-is).
        Expert-parallel (E,) slicing happens inside the MoE shard_map, which
        takes these as explicit operands — see models/moe.py."""
        if self.mesh is None or not windows:
            return dict(windows)
        from jax.sharding import NamedSharding, PartitionSpec as P
        rep = NamedSharding(self.mesh, P())
        return {site: jax.device_put(jnp.asarray(v), rep)
                for site, v in windows.items()}

    def _check_pinned_windows(self):
        for site, sc in self.cfg_serving.resolved_tdvmm_plan.sites:
            if (sc.enabled and sc.io_quantize and sc.output_calibration
                    and sc.out_scale is None):
                raise ValueError(
                    f"engine requires a pinned readout window on enabled "
                    f"site {site!r}: per-call data calibration is a max over "
                    f"the whole batch and couples requests together.  Run "
                    f"models.model.calibrate(...) and pass calib=, or set "
                    f"out_scale/output_calibration=False in the plan.")

    def compiled_steps(self) -> int:
        """How many distinct step executables exist (the invariant: 2)."""
        sizes = []
        for fn in (self._prefill, self._decode):
            get = getattr(fn, "_cache_size", None)
            sizes.append(int(get()) if get is not None else -1)
        return sum(sizes) if all(s >= 0 for s in sizes) else -1

    # ------------------------------------------------------------------
    # Calibration hot-swap
    # ------------------------------------------------------------------
    def set_calibration(self, calib: CalibrationState) -> None:
        """Swap the pinned windows between steps — values only, never
        structure, so the two compiled step executables are reused as-is
        (``compiled_steps`` stays 2)."""
        new = calib.as_arrays()
        if set(new) != set(self._windows):
            raise ValueError(
                f"hot-swap calibration covers sites {sorted(new)} but the "
                f"engine serves {sorted(self._windows)} — site structure is "
                "jit-static; rebuild the engine for a different plan")
        for site, arr in new.items():
            if arr.shape != self._windows[site].shape:
                raise ValueError(
                    f"hot-swap window for site {site!r} has shape "
                    f"{arr.shape}, pinned is {self._windows[site].shape}")
        self._windows = self._place_windows(new)
        self.calib = calib

    def pinned_calibration(self) -> CalibrationState:
        """The currently pinned windows as a ``CalibrationState``."""
        return CalibrationState(windows=dict(self._windows))

    # ------------------------------------------------------------------
    # Preemption
    # ------------------------------------------------------------------
    def request_preemption(self) -> None:
        """Flag the active run for snapshot-and-exit before its next step
        (what a SIGTERM handler — or an injected preemption — calls)."""
        if self._guard is None:
            self._guard = fault.PreemptionGuard()
        self._guard.requested = True

    # ------------------------------------------------------------------
    # Run lifecycle
    # ------------------------------------------------------------------
    def _make_sched(self) -> SlotScheduler:
        ecfg = self.ecfg
        if self.sla is not None:
            return sla_policy.SlaScheduler(self.total_slots, ecfg.slot_order,
                                           self.sla)
        return SlotScheduler(self.total_slots, ecfg.slot_order)

    def start(self, requests: list[Request]) -> None:
        """Initialize a fresh run over a trace (allocates pools/caches)."""
        rids = [r.rid for r in requests]
        if len(set(rids)) != len(rids):
            raise ValueError("duplicate request ids in trace")
        ecfg = self.ecfg
        sched = self._make_sched()
        sched.add(requests)
        caches = self._init_caches()
        if self._cache_sh is not None:
            caches = jax.device_put(caches, self._cache_sh)
        if self.tracer is not None:
            self.tracer.attach(requests)
        self._st = RunState(
            requests=list(requests),
            records={r.rid: RequestRecord(r) for r in requests},
            sched=sched,
            pool=PagePool(ecfg.num_pages, ecfg.page_size, ranks=self.dp),
            caches=caches,
        )

    def run(self, requests: list[Request],
            fault_cfg: Optional[FaultConfig] = None) -> EngineReport:
        """Serve a trace to completion (or preemption); returns the report
        (token streams, finish reasons, energy, utilization, memory
        high-water, fault/drift accounting)."""
        self.start(requests)
        return self._drive(fault_cfg)

    def resume(self,
               fault_cfg: Optional[FaultConfig] = None) -> EngineReport:
        """Continue a run restored by ``restore`` (or a run that exited
        preempted in-process) to completion."""
        if self._st is None:
            raise RuntimeError("no run state: call run() or restore() first")
        self._st.preempted = False
        self._st.snapshot_path = None
        return self._drive(fault_cfg)

    def _drive(self, fault_cfg: Optional[FaultConfig]) -> EngineReport:
        st = self._st
        fc = self._fault = fault_cfg
        guard = (fc.guard if fc is not None else None) \
            or fault.PreemptionGuard()
        self._guard = guard
        t0 = time.time()
        try:
            while True:
                if fc is not None and fc.injector is not None:
                    fc.injector.on_tick(self, st.steps)
                if guard.requested:
                    raise fault.Preempted(f"preempted at step {st.steps}")
                t1 = time.time()
                alive = self.tick()
                dt = time.time() - t1
                if self.tracer is not None:
                    self.tracer.tick_done(st.steps, dt, {
                        "queue_depth": len(st.sched.pending),
                        "active_slots": len(st.sched.occupied()),
                        "pages_in_use": st.pool.in_use,
                        "fj_per_op": (st.analog_energy_j / st.analog_ops
                                      * 1e15) if st.analog_ops else 0.0,
                    })
                if self.sink is not None:
                    self._observe_tick(dt)
                if fc is not None:
                    if fc.monitor is not None:
                        fc.monitor.record(st.steps, dt)
                    if fc.heartbeat is not None:
                        fc.heartbeat.beat(st.steps)
                    if (fc.drift is not None and fc.drift.observe_every
                            and self.sink is not None and st.steps -
                            st.last_clip_obs >= fc.drift.observe_every):
                        st.last_clip_obs = st.steps
                        self._observe_clips(fc.drift)
                    if (fc.drift is not None and st.steps -
                            st.last_drift_check >= fc.drift.check_every):
                        st.last_drift_check = st.steps
                        self._drift_check(fc.drift)
                if not alive:
                    break
        except fault.Preempted:
            st.preempted = True
            st.wall_s += time.time() - t0
            if self.sink is not None:
                self.sink.flush()        # metrics land before the snapshot
            if fc is not None and fc.snapshot_dir is not None:
                from repro.checkpoint import checkpoint as ckpt
                path = ckpt.save_engine_snapshot(
                    self.snapshot(), fc.snapshot_dir, step=st.steps,
                    keep=fc.snapshot_keep)
                st.snapshot_path = str(path)
            return self.report()
        st.wall_s += time.time() - t0
        return self.report()

    def _observe_tick(self, dt: float) -> None:
        """Feed the metrics sink after one tick — pure host-side floats, no
        device sync beyond what ``tick`` already did, so telemetry never
        perturbs the compiled-step story (``compiled_steps == 2``)."""
        st = self._st
        step = st.steps          # the tick just executed landed us here
        sink = self.sink
        sink.observe("step_latency_s", dt, step)
        sink.observe("queue_depth", len(st.sched.pending), step)
        sink.observe("active_slots", len(st.sched.occupied()), step)
        sink.observe("page_in_use", st.pool.in_use, step)
        sink.observe("page_high_water", st.pool.high_water, step)
        sink.observe("generated_tokens", st.generated_tokens, step)
        sink.observe("step_retries", st.step_retries, step)
        if st.analog_ops > 0.0:
            sink.observe("fj_per_op",
                         st.analog_energy_j / st.analog_ops * 1e15, step)

    # ------------------------------------------------------------------
    # One scheduling tick
    # ------------------------------------------------------------------
    def tick(self) -> bool:
        """One engine iteration: admit, then run one prefill chunk OR one
        batched decode step OR fast-forward to the next arrival.  Returns
        False when the trace is fully served.  Engine state is always
        consistent between ticks — snapshots happen exactly here."""
        st = self._st
        ecfg = self.ecfg
        if st.steps > ecfg.max_steps:
            raise RuntimeError(f"engine exceeded max_steps={ecfg.max_steps}")
        if self.tracer is not None:
            for req in st.sched.pending:     # open `queued` spans (idempotent)
                if req.arrival_step <= st.steps:
                    self.tracer.note_arrival(req.rid, st.steps)
        with trace.span("engine.admit"):
            self._admit()
        occupied = st.sched.occupied()
        prefilling = [s for s in occupied if s.prefilling]
        decoding = [s for s in occupied if not s.prefilling]
        if prefilling:
            self._prefill_tick(prefilling[0])
            return True
        if decoding:
            self._decode_tick(decoding)
            return True
        if st.sched.has_pending():
            nxt = st.sched.next_arrival()
            if nxt is None or nxt <= st.steps:
                raise RuntimeError(
                    "scheduler stall: pending request cannot be admitted "
                    "into an empty engine (page budget inconsistency)")
            if self.tracer is not None:
                self.tracer.mark_idle(st.steps, nxt)
            st.idle_steps += nxt - st.steps
            st.steps = nxt
            return True
        return False

    def _admit(self) -> None:
        """Admission (FIFO, or SLA priority-with-aging when ``sla=`` is
        set); head-of-line blocks on pool pressure.  SLA infeasibility is
        checked FIRST — a rejected request never occupies a slot, never
        allocates a page, and never reaches a compiled step."""
        st = self._st
        ecfg = self.ecfg
        cap_pages = ecfg.resolved_max_pages
        while True:
            req = st.sched.head(st.steps)
            if req is None:
                break
            if self.sla is not None:
                verdict = sla_policy.admission_verdict(
                    req, st.steps, ecfg.chunk, self.energy, self.sla)
                if verdict is not None:
                    st.sched.pop_head()
                    rec = st.records[req.rid]
                    rec.admitted_step = rec.finished_step = st.steps
                    rec.finish_reason = "rejected"
                    rec.reject_reason = verdict
                    st.rejected += 1
                    if self.tracer is not None:
                        self.tracer.finished(req.rid, st.steps, "rejected")
                    continue
            need = pages_for(len(req.prompt), ecfg.page_size)
            if need > cap_pages:
                # can never fit: reject without occupying a slot
                st.sched.pop_head()
                rec = st.records[req.rid]
                rec.admitted_step = rec.finished_step = st.steps
                rec.finish_reason = "evicted"
                st.evictions += 1
                if self.tracer is not None:
                    self.tracer.finished(req.rid, st.steps, "evicted")
                continue
            # Walk free slots in slot_order; a slot's DP rank decides which
            # page region serves it (slot id = dp_rank * slots + local), so
            # admission tries each rank's pool until one fits.  With dp=1
            # this is exactly the legacy free_slot_id + alloc sequence.
            sid = pages = None
            for cand in st.sched.free_slot_ids():
                got = st.pool.alloc(need, rank=cand // self.ecfg.slots)
                if got is not None:
                    sid, pages = cand, got
                    break
            if sid is None:
                break
            st.sched.pop_head()
            rec = st.records[req.rid]
            rec.admitted_step = st.steps
            st.sched.place(sid, rec, pages)
            if self.tracer is not None:
                self.tracer.admitted(req.rid, st.steps, sid,
                                     sid // self.ecfg.slots, len(pages))

    def _finish(self, slot: Slot, reason: str) -> None:
        st = self._st
        slot.record.finish_reason = reason
        slot.record.finished_step = st.steps
        if self.tracer is not None:
            self.tracer.finished(slot.record.request.rid, st.steps, reason)
        if reason == "evicted":
            st.evictions += 1
        elif reason == "failed":
            st.failed += 1
        elif reason == "over_budget":
            st.over_budget += 1
        st.pool.free(slot.pages)
        st.sched.release(slot)

    def _emit(self, slot: Slot, tok: int) -> None:
        """Stream one generated token; finish on eos/budget.

        Under an SLA policy a request whose accumulated joules crossed its
        ``joule_budget`` is finished ``over_budget`` — the token it just
        produced still streams (the work was done and priced), the slot and
        pages recycle, and neighbor streams are untouched (the same
        row-isolation argument as the ``failed`` path)."""
        rec = slot.record
        rec.tokens.append(tok)
        if rec.first_token_step < 0:
            rec.first_token_step = self._st.steps
        if self.ecfg.eos_id is not None and tok == self.ecfg.eos_id:
            self._finish(slot, "eos")
        elif (self.sla is not None and rec.request.joule_budget is not None
                and rec.analog_energy_j > rec.request.joule_budget):
            self._finish(slot, "over_budget")
        elif len(rec.tokens) >= rec.request.max_new_tokens:
            self._finish(slot, "max_tokens")
        else:
            slot.cur_token = tok

    def _account(self, rec: RequestRecord, n: int) -> None:
        st = self._st
        ops, e_j = energy_model.token_cost(self.energy, n)
        rec.analog_ops += ops
        rec.analog_energy_j += e_j
        st.analog_ops += ops
        st.analog_energy_j += e_j
        st.tokens_priced += n         # exact int behind site_attribution

    def _run_compiled(self, kind: str, fn, *args):
        """The retry boundary around one compiled step.  Injected faults
        raise before ``fn`` is invoked, so the donated cache buffers of a
        failed attempt were never consumed."""
        fc = self._fault
        st = self._st

        def call():
            if fc is not None and fc.injector is not None:
                fc.injector.check(kind, st.steps)
            if self.mesh is not None:
                # Model code reads the mesh context at trace time (shard_map
                # in moe/common); only the first call per step kind traces,
                # later ones hit the executable cache.
                with meshctx.use_mesh(self.mesh, self._dp_axes,
                                      self._tp_axis):
                    return fn(*args)
            return fn(*args)

        if fc is None:
            return call()

        def on_retry(attempt, e):
            st.step_retries += 1

        return fault.retry_step(
            call, retries=fc.retries, backoff_s=fc.backoff_s,
            backoff_cap_s=fc.backoff_cap_s, jitter=fc.jitter,
            on_retry=on_retry, guard=self._guard)

    def _prefill_tick(self, slot: Slot) -> None:
        """One prefill chunk (oldest admission first)."""
        st = self._st
        ecfg = self.ecfg
        vocab = self.cfg.vocab_size
        prompt = slot.record.request.prompt
        start = slot.prefill_done
        n = min(ecfg.chunk, len(prompt) - start)
        last = start + n == len(prompt)
        with trace.span("engine.assemble"):
            tokens = np.zeros((1, ecfg.chunk), np.int32)
            tokens[0, :n] = prompt[start:start + n]
            row = np.full((ecfg.resolved_max_pages,), st.pool.trash_page,
                          np.int32)
            row[:len(slot.pages)] = slot.pages
            batch = {"inputs": jnp.asarray(tokens),
                     "block_row": jnp.asarray(row),
                     "offset": jnp.int32(start), "valid": jnp.int32(n)}
            if self.ring_pages:
                batch["ring_row"] = jnp.asarray(self._ring_rows[slot.sid])
            if self.mesh is not None:
                batch = jax.device_put(batch, self._batch_sh["prefill"])
        try:
            with trace.span("engine.dispatch"):
                logits, caches = self._run_compiled(
                    "prefill", self._prefill, self.params, batch, st.caches,
                    self._windows)
        except RuntimeError as e:
            # Persistent step failure: this slot IS the step's work — finish
            # it as failed (graceful degradation) and re-plan next tick.
            del e
            self._finish(slot, "failed")
            return
        st.caches = caches
        if last:
            with trace.span("engine.readback"):
                row_logits = logits[0, 0]
                tok = int(jnp.argmax(row_logits[:vocab]))
                nan = int(bool(jnp.isnan(row_logits).any()))
                self._read_routed(caches)
        with trace.span("engine.emit"):
            st.prefill_steps += 1
            slot.prefill_done += n
            slot.pos += n
            st.prompt_tokens += n
            self._count_pages(1, [slot])
            st.moe_rows_computed += self._moe_rows_per_row * ecfg.chunk
            self._account(slot.record, n)
            if self.tracer is not None:
                self.tracer.mark_chunk(
                    slot.record.request.rid, start // ecfg.chunk, n,
                    done=last, step=st.steps)
            if last:
                st.nan_steps += nan
                st.generated_tokens += 1
                self._account(slot.record, 1)
                self._emit(slot, tok)
        st.steps += 1

    def _decode_tick(self, decoding: list[Slot]) -> None:
        """One batched decode step over all decoding slots."""
        st = self._st
        ecfg = self.ecfg
        ps, cap_pages = ecfg.page_size, ecfg.resolved_max_pages
        vocab = self.cfg.vocab_size
        with trace.span("engine.assemble"):
            # --- evict-before-poison: secure every slot's write page ------
            runnable = []
            for slot in decoding:
                if slot.pos >= len(slot.pages) * ps:
                    if len(slot.pages) >= cap_pages or \
                            (new := st.pool.alloc(
                                1, rank=slot.sid // ecfg.slots)) is None:
                        self._finish(slot, "evicted")
                        continue
                    slot.pages.extend(new)
                runnable.append(slot)
            if not runnable:
                return            # state changed (evictions); re-plan
            b = self.total_slots
            tokens = np.zeros((b, 1), np.int32)
            pos = np.zeros((b,), np.int32)
            tables = np.full((b, cap_pages), st.pool.trash_page, np.int32)
            active = np.zeros((b,), bool)
            for slot in runnable:
                tokens[slot.sid, 0] = slot.cur_token
                pos[slot.sid] = slot.pos
                tables[slot.sid, :len(slot.pages)] = slot.pages
                active[slot.sid] = True
            batch = {"inputs": jnp.asarray(tokens),
                     "block_tables": jnp.asarray(tables),
                     "pos": jnp.asarray(pos),
                     "active": jnp.asarray(active)}
            if self.ring_pages:
                batch["ring_tables"] = self._ring_tables
            if self.mesh is not None:
                batch = jax.device_put(batch, self._batch_sh["decode"])
        try:
            with trace.span("engine.dispatch"):
                logits, caches = self._run_compiled(
                    "decode", self._decode, self.params, batch, st.caches,
                    self._windows)
        except RuntimeError as e:
            # Persistent step failure: blame the attributed request (or the
            # oldest runnable slot), finish it failed, re-plan next tick.
            # Decode rows are independent (row-wise math + trash-page
            # isolation), so the survivors' streams are bit-unchanged.
            rid = getattr(e, "rid", None)
            culprit = next(
                (s for s in runnable if s.record.request.rid == rid), None)
            if culprit is None:
                culprit = min(runnable, key=lambda s: s.seq)
            self._finish(culprit, "failed")
            return
        st.caches = caches
        with trace.span("engine.readback"):
            toks = np.asarray(jnp.argmax(logits[:, 0, :vocab], axis=-1))
            nans = np.asarray(jnp.isnan(logits[:, 0]).any(axis=-1))
            self._read_routed(caches)
        with trace.span("engine.emit"):
            st.decode_steps += 1
            if self.tracer is not None:
                self.tracer.mark_decode(
                    [s.record.request.rid for s in runnable], st.steps)
            st.util_samples.append(len(runnable) / b)
            st.moe_rows_computed += self._moe_rows_per_row * b
            for slot in runnable:              # admission order
                st.nan_steps += int(nans[slot.sid])
                slot.pos += 1
                st.generated_tokens += 1
                self._account(slot.record, 1)
                self._emit(slot, int(toks[slot.sid]))
            if self._decode_in_place:
                self._count_pages_in_place([s.pos - 1 for s in runnable])
            else:
                self._count_pages(b, runnable)
        st.steps += 1

    def _count_pages(self, rows: int, slots: list[Slot]) -> None:
        """Pages a step's gathers touched, for ``rows`` slots' rows, and of
        those the pages holding a written position of ``slots``: the full
        pool's block rows and the window rings, one layer of each."""
        st, ps = self._st, self.ecfg.page_size
        if self._full_layers:
            st.kv_pages_read += rows * self.ecfg.resolved_max_pages
            st.kv_pages_live += sum(pages_for(s.pos, ps) for s in slots)
        if self.ring_pages:
            st.kv_pages_read += rows * self.ring_pages
            st.kv_window_pages_read += rows * self.ring_pages
            st.kv_pages_live += sum(min(pages_for(s.pos, ps), self.ring_pages)
                                    for s in slots)

    def _count_pages_in_place(self, positions: list[int]) -> None:
        """Pages a decode step that reads in place reads for slots at
        ``positions`` (before the step), one layer of each pool kind: every
        page below a slot's position, and of its window ring those that
        hold the window (``attention._paged_read_in_place``), so every page
        read is live."""
        st, ps, window = self._st, self.ecfg.page_size, self.cfg.swa_window
        for pos in positions:
            below = -(-pos // ps)
            read = below if self._full_layers else 0
            if self.ring_pages:
                ring = below - max(pos - window + 1, 0) // ps
                st.kv_window_pages_read += ring
                read += ring
            st.kv_pages_read += read
            st.kv_pages_live += read

    # ------------------------------------------------------------------
    # Drift detection + online recalibration
    # ------------------------------------------------------------------
    def _observe_clips(self, dc: DriftConfig) -> None:
        """Stream per-site readout clip rates into the sink as live
        ``clip_rate.<site>`` series (``DriftConfig.observe_every``).  Same
        eager ``drift_probe`` capture as the full drift check — host-side,
        never a third compiled program — but run far more often and with
        no recalibration decision attached, so a per-site ``AlertRule``
        sees a rising clip rate well before ``check_every`` comes due."""
        st = self._st
        _, clips = model.drift_probe(
            self.params, dc.probe_batch, self.cfg,
            self.pinned_calibration(), dc.max_len)
        for name, v in clip_rate_metrics(clips).items():
            self.sink.observe(name, v, st.steps)

    def _drift_check(self, dc: DriftConfig) -> None:
        st = self._st
        pinned = self.pinned_calibration()
        fresh, clips = model.drift_probe(
            self.params, dc.probe_batch, self.cfg, pinned, dc.max_len)
        ratios = pinned.drift_ratios(fresh)
        max_clip = max(clips.values(), default=0.0)
        max_dev = max((abs(math.log(max(r, 1e-12)))
                       for r in ratios.values()), default=0.0)
        if self.sink is not None:
            self.sink.observe("drift_max_clip_rate", float(max_clip),
                              st.steps)
            self.sink.observe("drift_max_log_ratio", float(max_dev),
                              st.steps)
            for name, v in clip_rate_metrics(clips).items():
                self.sink.observe(name, v, st.steps)
        drifted = max_clip > dc.clip_threshold or max_dev > dc.window_tol
        if not drifted:
            return
        event = {"step": st.steps, "max_clip_rate": float(max_clip),
                 "max_log_ratio": float(max_dev),
                 "clip_rates": {k: float(v) for k, v in clips.items()},
                 "ratios": {k: float(v) for k, v in ratios.items()},
                 "recalibrated": bool(dc.recalibrate)}
        st.drift_events.append(event)
        if dc.recalibrate:
            self.set_calibration(fresh)
            st.recalibrations += 1

    # ------------------------------------------------------------------
    # Snapshot / restore
    # ------------------------------------------------------------------
    def snapshot(self) -> dict:
        """The full in-flight state as ONE checkpointable pytree.

        Leaves: ``caches/...`` (paged KV pools, host-copied), ``windows/<site>``
        (the currently pinned — possibly recalibrated — readout windows), and
        ``meta`` (a uint8-encoded JSON blob of every host-side structure:
        requests, records, scheduler queue + slots + block tables, page-pool
        free list, cumulative counters).  ``params``/model weights are NOT
        included — weight provenance belongs to the model checkpoint; the
        restoring process constructs the Engine with the same params.

        Only valid between ticks (where the engine always is when a
        preemption unwinds it)."""
        st = self._st
        if st is None:
            raise RuntimeError("no run state to snapshot")
        meta = {
            "version": 4,
            "dp": self.dp,
            "ecfg": dataclasses.asdict(self.ecfg),
            "model": {"vocab_size": self.cfg.vocab_size,
                      "n_layers": self.cfg.n_layers,
                      "d_model": self.cfg.d_model,
                      "family": self.cfg.family},
            "sla": (dataclasses.asdict(self.sla)
                    if self.sla is not None else None),
            "telemetry": (self.sink.snapshot()
                          if self.sink is not None else None),
            "trace": (self.tracer.snapshot()
                      if self.tracer is not None else None),
            "requests": [
                {"rid": r.rid, "prompt": list(r.prompt),
                 "max_new_tokens": r.max_new_tokens,
                 "arrival_step": r.arrival_step,
                 "priority": r.priority,
                 "deadline_steps": r.deadline_steps,
                 "joule_budget": r.joule_budget} for r in st.requests],
            "records": {
                str(rid): {
                    "tokens": list(rec.tokens),
                    "finish_reason": rec.finish_reason,
                    "admitted_step": rec.admitted_step,
                    "first_token_step": rec.first_token_step,
                    "finished_step": rec.finished_step,
                    "analog_ops": rec.analog_ops,
                    "analog_energy_j": rec.analog_energy_j,
                    "reject_reason": rec.reject_reason,
                } for rid, rec in st.records.items()},
            "sched": {
                "pending": [r.rid for r in st.sched.pending],
                "seq": st.sched._seq,
                "slots": [
                    None if s is None else {
                        "sid": s.sid, "seq": s.seq,
                        "rid": s.record.request.rid,
                        "pages": list(s.pages), "pos": s.pos,
                        "prefill_done": s.prefill_done,
                        "cur_token": s.cur_token,
                    } for s in st.sched.slots]},
            "pool": {"free": st.pool.free_lists(),
                     "high_water": st.pool.high_water},
            "counters": {
                "steps": st.steps, "prefill_steps": st.prefill_steps,
                "decode_steps": st.decode_steps,
                "idle_steps": st.idle_steps,
                "prompt_tokens": st.prompt_tokens,
                "generated_tokens": st.generated_tokens,
                "evictions": st.evictions, "nan_steps": st.nan_steps,
                "failed": st.failed, "rejected": st.rejected,
                "over_budget": st.over_budget,
                "analog_ops": st.analog_ops,
                "analog_energy_j": st.analog_energy_j,
                "tokens_priced": st.tokens_priced,
                "step_retries": st.step_retries,
                "recalibrations": st.recalibrations,
                "last_drift_check": st.last_drift_check,
                "last_clip_obs": st.last_clip_obs,
                "wall_s": st.wall_s,
                "util_samples": [float(u) for u in st.util_samples],
                "kv_pages_read": st.kv_pages_read,
                "kv_pages_live": st.kv_pages_live,
                "kv_window_pages_read": st.kv_window_pages_read,
                "moe_rows_routed": st.moe_rows_routed,
                "moe_rows_computed": st.moe_rows_computed,
                "drift_events": st.drift_events,
            },
        }
        blob = np.frombuffer(json.dumps(meta).encode("utf-8"), np.uint8)
        return {
            "caches": jax.tree.map(np.asarray, st.caches),
            "windows": {site: np.asarray(v)
                        for site, v in self._windows.items()},
            "meta": blob,
        }

    def restore(self, snap) -> None:
        """Rebuild in-flight state from ``snapshot()`` output — the nested
        pytree itself or the flat name -> array dict
        ``checkpoint.load_engine_snapshot`` returns.  Validates the engine
        shape (EngineConfig + model identity + window structure) against the
        snapshot; ``resume`` then continues the trace bit-identically."""
        from repro.checkpoint import checkpoint as ckpt
        flat = dict(ckpt.leaf_paths(snap))
        if "meta" not in flat:
            raise ValueError("engine snapshot missing 'meta' leaf")
        meta = json.loads(np.asarray(flat["meta"], np.uint8)
                          .tobytes().decode("utf-8"))
        mine = dataclasses.asdict(self.ecfg)
        if meta["ecfg"] != mine:
            raise ValueError(
                f"engine snapshot was taken with EngineConfig "
                f"{meta['ecfg']}, this engine has {mine} — the config pins "
                "the compiled step shapes and cannot change across resume")
        snap_dp = meta.get("dp", 1)
        if snap_dp != self.dp:
            raise ValueError(
                f"engine snapshot was taken with data-parallel size "
                f"{snap_dp}, this engine has {self.dp} — the DP slot-pool "
                "dimension pins the decode batch and page-pool layout")
        model_id = {"vocab_size": self.cfg.vocab_size,
                    "n_layers": self.cfg.n_layers,
                    "d_model": self.cfg.d_model, "family": self.cfg.family}
        if meta["model"] != model_id:
            raise ValueError(
                f"engine snapshot model {meta['model']} != {model_id}")
        snap_sla = meta.get("sla")
        mine_sla = (dataclasses.asdict(self.sla)
                    if self.sla is not None else None)
        if snap_sla != mine_sla:
            raise ValueError(
                f"engine snapshot was taken under SLA policy {snap_sla}, "
                f"this engine has {mine_sla} — the policy drives admission "
                "order and must match for a bit-identical resume")
        snap_telemetry = meta.get("telemetry")
        if snap_telemetry is not None:
            if self.sink is None:
                raise ValueError(
                    "engine snapshot carries telemetry state but this "
                    "engine has no sink — construct it with sink= to "
                    "resume the metric series and alert history")
            self.sink.restore(snap_telemetry)
        snap_trace = meta.get("trace")
        if snap_trace is not None:
            if self.tracer is None:
                raise ValueError(
                    "engine snapshot carries trace state but this engine "
                    "has no tracer — construct it with tracer= to resume "
                    "the span stream as one continuous trace")
            self.tracer.restore(snap_trace)

        # --- windows (the pinned state at snapshot time, which may be a
        # recalibrated one — restoring it is what keeps resume bit-exact) ---
        win_names = {k[len("windows/"):] for k in flat
                     if k.startswith("windows/")}
        if win_names != set(self._windows):
            raise ValueError(
                f"snapshot windows {sorted(win_names)} != engine sites "
                f"{sorted(self._windows)}")
        restored = {}
        for site in win_names:
            arr = np.asarray(flat[f"windows/{site}"], np.float32)
            if arr.shape != self._windows[site].shape:
                raise ValueError(
                    f"snapshot window {site!r} shape {arr.shape} != "
                    f"{self._windows[site].shape}")
            restored[site] = jnp.asarray(arr)
        self._windows = self._place_windows(restored)
        self.calib = CalibrationState(windows=dict(restored))

        # --- device caches (re-sharded onto the mesh when one is set) -----
        ecfg = self.ecfg
        shapes = jax.eval_shape(self._init_caches)
        sh_flat = dict(ckpt.leaf_paths(self._cache_sh)) \
            if self._cache_sh is not None else {}
        leaves = []
        for name, sh in ckpt.leaf_paths(shapes):
            arr = flat.get(f"caches/{name}")
            if arr is None:
                raise KeyError(f"engine snapshot missing cache leaf {name}")
            if tuple(arr.shape) != tuple(sh.shape) or \
                    str(arr.dtype) != str(sh.dtype):
                raise ValueError(
                    f"cache leaf {name}: snapshot {arr.shape}/{arr.dtype} "
                    f"!= expected {sh.shape}/{sh.dtype}")
            if name in sh_flat:
                leaves.append(jax.device_put(np.asarray(arr), sh_flat[name]))
            else:
                leaves.append(jnp.asarray(arr))
        caches = jax.tree_util.tree_unflatten(
            jax.tree_util.tree_structure(shapes), leaves)

        # --- host bookkeeping --------------------------------------------
        requests = [Request(rid=r["rid"], prompt=tuple(r["prompt"]),
                            max_new_tokens=r["max_new_tokens"],
                            arrival_step=r["arrival_step"],
                            priority=r.get("priority", 0),
                            deadline_steps=r.get("deadline_steps"),
                            joule_budget=r.get("joule_budget"))
                    for r in meta["requests"]]
        by_rid = {r.rid: r for r in requests}
        records = {}
        for rid_s, rd in meta["records"].items():
            rid = int(rid_s)
            rec = RequestRecord(by_rid[rid])
            rec.tokens = list(rd["tokens"])
            rec.finish_reason = rd["finish_reason"]
            rec.admitted_step = rd["admitted_step"]
            rec.first_token_step = rd["first_token_step"]
            rec.finished_step = rd["finished_step"]
            rec.analog_ops = rd["analog_ops"]
            rec.analog_energy_j = rd["analog_energy_j"]
            rec.reject_reason = rd.get("reject_reason")
            records[rid] = rec
        sched = self._make_sched()
        sched.pending = [by_rid[rid] for rid in meta["sched"]["pending"]]
        sched._seq = meta["sched"]["seq"]
        for sd in meta["sched"]["slots"]:
            if sd is None:
                continue
            slot = Slot(sid=sd["sid"], seq=sd["seq"],
                        record=records[sd["rid"]], pages=list(sd["pages"]),
                        pos=sd["pos"], prefill_done=sd["prefill_done"],
                        cur_token=sd["cur_token"])
            sched.slots[sd["sid"]] = slot
        pool = PagePool(ecfg.num_pages, ecfg.page_size, ranks=self.dp)
        free = meta["pool"]["free"]
        if meta["version"] < 3:       # v2: one flat free list (dp == 1)
            free = [free]
        pool.restore_free(free)
        pool.high_water = meta["pool"]["high_water"]

        c = meta["counters"]
        # tokens_priced landed in meta v4; older snapshots reconstruct it
        # exactly from the (integer-valued) op totals.
        opt = self.energy["ops_per_token"]
        tokens_priced = c.get("tokens_priced")
        if tokens_priced is None:
            ops_total = c.get("analog_ops",
                              sum(r.analog_ops for r in records.values()))
            tokens_priced = int(round(ops_total / opt)) if opt else 0
        self._st = RunState(
            requests=requests, records=records, sched=sched, pool=pool,
            caches=caches, steps=c["steps"],
            prefill_steps=c["prefill_steps"],
            decode_steps=c["decode_steps"], idle_steps=c["idle_steps"],
            prompt_tokens=c["prompt_tokens"],
            generated_tokens=c["generated_tokens"],
            evictions=c["evictions"], nan_steps=c["nan_steps"],
            failed=c["failed"], rejected=c.get("rejected", 0),
            over_budget=c.get("over_budget", 0),
            analog_ops=c.get("analog_ops",
                             sum(r.analog_ops for r in records.values())),
            analog_energy_j=c.get(
                "analog_energy_j",
                sum(r.analog_energy_j for r in records.values())),
            tokens_priced=tokens_priced,
            step_retries=c["step_retries"],
            recalibrations=c["recalibrations"],
            last_drift_check=c["last_drift_check"],
            last_clip_obs=c.get("last_clip_obs", 0), wall_s=c["wall_s"],
            util_samples=list(c["util_samples"]),
            kv_pages_read=c.get("kv_pages_read", 0),
            kv_pages_live=c.get("kv_pages_live", 0),
            kv_window_pages_read=c.get("kv_window_pages_read", 0),
            moe_rows_routed=c.get("moe_rows_routed", 0),
            moe_rows_computed=c.get("moe_rows_computed", 0),
            drift_events=list(c["drift_events"]),
        )

    # ------------------------------------------------------------------
    def report(self) -> EngineReport:
        """The report for the current (finished, preempted, or in-flight)
        run state."""
        st = self._st
        if st is None:
            raise RuntimeError("no run state to report")
        fc = self._fault
        records, requests = st.records, st.requests
        if self.sink is not None:
            self.sink.flush()     # buffered emitters reach disk with report
        # Aggregates are DERIVED from the per-site attribution table (the
        # same exact tokens_priced count expanded per site), so the site
        # table sums bit-exactly to analog_ops/analog_energy_j/fj_per_op.
        attr = energy_model.site_attribution(self.energy, st.tokens_priced)
        tot_ops = attr["ops"]
        tot_e = attr["energy_j"]
        # Deadline outcomes over ADMITTED finished requests: a rejection is
        # admission control working (counted in `rejected`), not a miss.
        hits = [r.deadline_hit for r in records.values()
                if r.done and r.finish_reason != "rejected"
                and r.deadline_hit is not None]
        return EngineReport(
            requests=[records[r.rid].summary() for r in requests],
            steps=st.steps,
            prefill_steps=st.prefill_steps,
            decode_steps=st.decode_steps,
            idle_steps=st.idle_steps,
            wall_s=st.wall_s,
            prompt_tokens=st.prompt_tokens,
            generated_tokens=st.generated_tokens,
            utilization=(float(np.mean(st.util_samples))
                         if st.util_samples else 0.0),
            evictions=st.evictions,
            nan_logit_steps=st.nan_steps,
            page_high_water=st.pool.high_water,
            page_bytes=self.page_bytes,
            kv_high_water_bytes=(st.pool.high_water + 1) * self.page_bytes,
            analog_ops=tot_ops,
            analog_energy_j=tot_e,
            fj_per_op=(tot_e / tot_ops * 1e15) if tot_ops else 0.0,
            tokens_per_joule=(st.generated_tokens / tot_e) if tot_e else 0.0,
            compiled_steps=self.compiled_steps(),
            preempted=st.preempted,
            snapshot_path=st.snapshot_path,
            failed=st.failed,
            step_retries=st.step_retries,
            stragglers=(fc.monitor.stragglers
                        if fc is not None and fc.monitor is not None else 0),
            straggler_ewma_s=(fc.monitor.ewma
                              if fc is not None and fc.monitor is not None
                              else 0.0),
            heartbeats=(fc.heartbeat.beats
                        if fc is not None and fc.heartbeat is not None
                        else 0),
            recalibrations=st.recalibrations,
            drift_events=list(st.drift_events),
            rejected=st.rejected,
            over_budget=st.over_budget,
            deadline_hits=sum(1 for h in hits if h),
            deadline_misses=sum(1 for h in hits if not h),
            alerts=(len(self.sink.alerts) if self.sink is not None else 0),
            telemetry=(self.sink.summary()
                       if self.sink is not None else None),
            devices=(self.mesh.size if self.mesh is not None else 1),
            total_slots=self.total_slots,
            tokens_priced=st.tokens_priced,
            site_attribution=attr,
            trace_summary=(self.tracer.summary()
                           if self.tracer is not None else None),
            autotune=tdvmm_ops.autotune_report(),
        )
