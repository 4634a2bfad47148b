"""Model-wide TD-VMM calibration state.

The §3.1 output-window calibration ("slope ... controlled by appropriate
scaling of VMM weights") is **model state**, not a frozen config field: each
site's readout window is captured once on a representative batch and then
pinned for serving, where it (a) skips the per-call max|z| reduction and
(b) unlocks the Pallas fused-epilogue kernel (a fixed window is tile-local).

``CalibrationState`` is a pytree — per-site scalar windows, per-expert
``(E,)`` vector windows for expert-batched sites, and per-member ``(G,)``
vector windows for grouped sites (``attn.qkv``, ``ssm.in_proj``: the G
same-input projections of one shared-input launch each calibrate their own
tile window, captured in one record instead of G max-merged scalars) — so it
checkpoints through ``repro.checkpoint.checkpoint`` like any other state and
threads through ``models.model.prefill_step`` / ``decode_step``.

Capture protocol: ``collect()`` installs a process-wide collector;
``core.layers.td_matmul`` / ``td_expert_matmul`` then record each site's
latch-normalized max|z| via ``jax.debug.callback`` (values produced inside
``lax.scan``-ed layer stacks are tracers — the callback is the supported
escape hatch, and max-merging is order-independent).  The model-wide pass
lives in ``models.model.calibrate``.

Two serving-time mechanisms ride the same per-site channel:

  * **Runtime windows** (``runtime_windows`` / ``runtime_window``): a
    trace-time context mapping site -> f32 *array* window.  When a site
    resolves its readout window here, the window enters the compiled program
    as a runtime operand instead of a baked jit-static constant — so a
    restored or freshly recaptured ``CalibrationState`` can be hot-swapped
    between engine steps without recompiling (the two-compiled-step rule).
    Bitwise contract: the runtime-operand program evaluates the exact same
    barrier-pinned expression as the static path (``ops._epilogue``), so a
    window passed as an operand reproduces the baked-constant outputs bit
    for bit.
  * **Clip tracking** (``collect(pinned=...)``): a capture pass given the
    currently pinned windows additionally records, per site, how much of the
    latch-normalized |z| mass exceeds its pinned window — the readout
    *saturation/clip rate* that drift detection
    (``models.model.drift_probe`` -> ``runtime.engine.DriftConfig``)
    thresholds to decide when the §3.1 windows have gone stale.
"""
from __future__ import annotations

import contextlib
import dataclasses
import threading
from typing import Iterator, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig, TDVMMPlan, tdvmm_rule


@dataclasses.dataclass
class CalibrationState:
    """Per-site calibrated readout windows.

    windows: site name -> f32 window; shape ``()`` for plain sites, ``(E,)``
    for expert-batched sites (one window per expert's analog tile).
    """
    windows: dict[str, jax.Array] = dataclasses.field(default_factory=dict)

    def sites(self) -> tuple[str, ...]:
        return tuple(sorted(self.windows))

    @classmethod
    def from_collected(cls, collected: dict[str, np.ndarray],
                       floor: float = 1e-9) -> "CalibrationState":
        """Windows from a capture's max|z| records.  A tile of a per-tile
        window that the capture gave no charge at all (an expert that no
        calibration row was routed to) takes the largest window of its
        site."""
        def window(v):
            v = np.asarray(v, np.float32)
            if v.ndim and v.size:
                v = np.where(v == 0.0, v.max(), v)
            return jnp.asarray(np.maximum(v, floor))

        return cls(windows={site: window(v)
                            for site, v in sorted(collected.items())})

    def as_arrays(self) -> dict[str, jax.Array]:
        """Site -> f32 window *array* (the runtime-operand form consumed by
        ``runtime_windows`` and the serving engine's hot-swap path)."""
        return {site: jnp.asarray(v, jnp.float32)
                for site, v in sorted(self.windows.items())}

    def drift_ratios(self, fresh: "CalibrationState") -> dict[str, float]:
        """Per-site max over the window elements of fresh/pinned — the drift
        magnitude a recalibration decision thresholds.  > 1 means the live
        max|z| outgrew the pinned window (readout clips); < 1 means the
        window is now oversized (resolution loss)."""
        out = {}
        for site, pinned in self.windows.items():
            if site not in fresh.windows:
                continue
            p = np.maximum(np.asarray(pinned, np.float64), 1e-12)
            f = np.asarray(fresh.windows[site], np.float64)
            if p.shape != f.shape:
                raise ValueError(
                    f"site {site!r}: pinned window shape {p.shape} vs "
                    f"recaptured {f.shape} — calibration structure changed")
            r = f / p
            # report the element that drifted FURTHEST from 1, either way
            out[site] = float(r.flat[np.argmax(np.abs(np.log(
                np.maximum(r, 1e-12))))])
        return out


jax.tree_util.register_dataclass(
    CalibrationState, data_fields=["windows"], meta_fields=[])


# ---------------------------------------------------------------------------
# Collector (capture-time side channel)
# ---------------------------------------------------------------------------
class _Collector(threading.local):
    def __init__(self):
        self.store: Optional[dict[str, np.ndarray]] = None
        self.pinned: Optional[dict[str, np.ndarray]] = None
        self.clips: Optional[dict[str, np.ndarray]] = None


_COLLECTOR = _Collector()


def active() -> bool:
    """True while a ``collect()`` context is installed (trace-time check —
    the serving fast path pays nothing when no calibration is running)."""
    return _COLLECTOR.store is not None


def clip_reference(site: str) -> Optional[np.ndarray]:
    """The pinned window the active collector tracks clip rates against for
    ``site`` (None when no clip tracking is requested) — concrete host
    values, so layers can fold the comparison into the capture pass."""
    pinned = _COLLECTOR.pinned
    if pinned is None or not site:
        return None
    return pinned.get(site)


def record(site: str, z_max: jax.Array) -> None:
    """Max-merge one site's latch-normalized |z| maximum (scalar or (E,))
    into the active collector.  No-op without a collector."""
    store = _COLLECTOR.store
    if store is None or not site:
        return

    def _merge(value):
        # Closes over the dict itself: debug callbacks run on a runtime
        # thread where the installing thread's local slot is not visible.
        value = np.asarray(value, np.float32)
        prev = store.get(site)
        store[site] = value if prev is None else np.maximum(prev, value)

    jax.debug.callback(_merge, z_max)


def record_clip(site: str, exceed: jax.Array, total: int) -> None:
    """Accumulate one site's (clipped-element count, element count) pair —
    the readout-saturation tally against the collector's pinned windows.
    No-op unless ``collect(pinned=...)`` installed clip tracking."""
    clips = _COLLECTOR.clips
    if clips is None or not site:
        return

    def _merge(exceed_v):
        delta = np.asarray([float(exceed_v), float(total)], np.float64)
        prev = clips.get(site)
        clips[site] = delta if prev is None else prev + delta

    jax.debug.callback(_merge, exceed)


def clip_rates(clips: dict[str, np.ndarray]) -> dict[str, float]:
    """(exceed, total) tallies -> per-site clip fraction in [0, 1]."""
    return {site: float(v[0] / max(v[1], 1.0)) for site, v in clips.items()}


def clip_rate_metrics(rates: dict[str, float]) -> dict[str, float]:
    """Per-site clip rates as ``MetricsSink`` series names
    (``clip_rate.<site>``), in sorted site order so the observation
    sequence is deterministic — the naming contract between the engine's
    live clip observation, ``AlertRule(metric="clip_rate.ffn.out", ...)``
    wiring, and ``launch/serve.py --alert-on``."""
    return {f"clip_rate.{site}": float(v)
            for site, v in sorted(rates.items())}


@contextlib.contextmanager
def collect(pinned: Optional[dict[str, np.ndarray]] = None,
            ) -> Iterator[dict[str, np.ndarray]]:
    """Install a collector; yields the (mutating) site -> max|z| dict.

    With ``pinned`` (site -> concrete window values), the pass additionally
    tallies per-site clip counts against those windows; read them from
    ``last_clips()`` after the context exits (or use
    ``models.model.drift_probe``, which packages both).

    The barrier on exit flushes outstanding debug callbacks so every
    recorded site is present before the caller reads the dict."""
    if _COLLECTOR.store is not None:
        raise RuntimeError("nested calibration collect() is not supported")
    _COLLECTOR.store = {}
    _COLLECTOR.clips = {} if pinned is not None else None
    _COLLECTOR.pinned = None if pinned is None else {
        site: np.asarray(v, np.float32) for site, v in pinned.items()}
    try:
        yield _COLLECTOR.store
        jax.effects_barrier()
    finally:
        _LAST_CLIPS[0] = _COLLECTOR.clips
        _COLLECTOR.store = None
        _COLLECTOR.pinned = None
        _COLLECTOR.clips = None


_LAST_CLIPS: list = [None]


def last_clips() -> Optional[dict[str, np.ndarray]]:
    """(exceed, total) tallies from the most recent ``collect(pinned=...)``
    pass (None when the last pass did not track clips)."""
    return _LAST_CLIPS[0]


# ---------------------------------------------------------------------------
# Runtime windows (hot-swappable serving calibration)
# ---------------------------------------------------------------------------
class _RuntimeWindows(threading.local):
    def __init__(self):
        self.map: Optional[dict[str, jax.Array]] = None


_RUNTIME = _RuntimeWindows()


@contextlib.contextmanager
def runtime_windows(windows: Optional[dict[str, jax.Array]]):
    """Install site -> f32 window *arrays* for the duration of a trace.

    Inside the context every TD-VMM site whose name appears in the map takes
    its readout window from the array (a runtime operand — typically a jit
    argument of the caller) instead of the plan's static ``out_scale``.
    This is what lets the serving engine swap a recaptured
    ``CalibrationState`` between steps without recompiling: same structure,
    same shapes, new values -> same compiled executable.

    Nesting installs the inner map (restored on exit); ``None``/empty maps
    are a no-op context.
    """
    prev = _RUNTIME.map
    _RUNTIME.map = dict(windows) if windows else prev
    try:
        yield
    finally:
        _RUNTIME.map = prev


def runtime_window_map() -> Optional[dict[str, jax.Array]]:
    """The full site -> window map currently installed (None outside a
    ``runtime_windows`` context).  Used by shard_map call sites that must
    re-install the map *inside* the per-shard body — closures over the
    outer-trace arrays would capture full ``(E,)`` windows where an
    expert-parallel shard only owns its ``(E_loc,)`` slice."""
    return _RUNTIME.map


def runtime_window(site: str) -> Optional[jax.Array]:
    """The runtime window array installed for ``site`` (trace-time lookup;
    None outside a ``runtime_windows`` context or for uncovered sites)."""
    m = _RUNTIME.map
    if m is None or not site:
        return None
    return m.get(site)


# ---------------------------------------------------------------------------
# Applying captured state to a model config
# ---------------------------------------------------------------------------
def _host_window(value) -> float | tuple[float, ...]:
    arr = np.asarray(value)
    if arr.ndim == 0:
        return float(arr)
    if arr.ndim == 1:
        return tuple(float(v) for v in arr)
    raise ValueError(f"calibration window must be scalar or (E,), "
                     f"got shape {arr.shape}")


def apply_calibration(cfg: ModelConfig,
                      calib: Optional[CalibrationState]) -> ModelConfig:
    """Bake a CalibrationState into the model's plan.

    Each captured window becomes an appended exact-site rule setting
    ``out_scale`` — later rules win, so calibration overrides any statically
    configured window while every other site setting is untouched.  Windows
    are converted to host floats here (out_scale is a jit-static kernel
    argument), which requires concrete values: apply before/at trace time,
    not on traced state.
    """
    if calib is None or not calib.windows:
        return cfg
    from repro.configs.plan import group_members
    plan = cfg.tdvmm_plan if cfg.tdvmm_plan is not None else TDVMMPlan()
    rules = []
    for site in sorted(calib.windows):
        window = _host_window(calib.windows[site])
        members = group_members(cfg, site)
        if members and isinstance(window, tuple) and len(window) != len(members):
            raise ValueError(
                f"grouped site {site!r}: calibration captured "
                f"{len(window)} windows for the {len(members)}-member "
                f"launch {members}")
        rules.append(tdvmm_rule(site, out_scale=window))
    return cfg.replace(tdvmm_plan=plan.with_rules(*rules))
