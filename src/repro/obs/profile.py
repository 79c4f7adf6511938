"""Static per-executable cost models from post-optimization HLO.

The attribution layer's *price list*: for each (program, plan
fingerprint, batch bucket) the engine can dispatch, lower the exact
executable once via ``Engine.lower_hlo`` and run the roofline analyzer
(``repro.roofline.hlo_parse``) over the optimized HLO with
``trip_clamp=1`` — yielding **per-sweep** costs (one superstep /
local-iteration body) that are scaled at sample time by the measured
number of sweeps actually run.  The result is a frozen ``CostModel``
(flops, HBM bytes, collective bytes, arithmetic intensity) memoized in a
module-level LRU keyed by everything that changes the lowered
executable: program name, the plan's static aux (k, n_vertices, v_max,
e_max, epoch, e_slots), sharded-or-not, the serve bucket, and the
shape/dtype signature of ctx and batched arguments.  ``max_supersteps``
and warm-start state are deliberately NOT part of the key — they change
trip counts and initial values, never the per-sweep cost.

Profiling must never break serving: every failure mode (lowering error,
analyzer error, malformed HLO) degrades to an *error model* with zero
costs and the exception recorded in ``CostModel.error``; ``cost_model``
never raises.  Cache hits/misses/errors are a registered obs provider
(``snapshot()["cost_models"]``), and each fresh compile records a
``profile.compile`` event when the recorder is enabled.

This module must not import ``repro.engine`` (the engine imports
``repro.obs``); it duck-types the engine instance through its ``plan``,
``mesh`` and ``lower_hlo`` attributes.
"""
from __future__ import annotations

import threading
from collections import OrderedDict
from dataclasses import dataclass
from typing import Any

import jax

from ..roofline.hlo_parse import analyze_hlo
from . import recorder as _rec

# Published per-chip peaks, keyed by ``jax.Device.device_kind``:
# (peak FLOP/s, HBM bytes/s).  TPU v5e: 197 TFLOP/s bf16 and 819 GB/s HBM
# (Google Cloud documentation, "TPU v5e").  A device kind that is not in
# the table has no roofline: ``attainable_s`` — and the utilization the
# serving ledger prices from it — is ``None`` ("not available"), never a
# guessed number.
DEVICE_PEAKS: dict[str, tuple[float, float]] = {
    "TPU v5 lite": (197e12, 819e9),
}

_CACHE_CAP = 256


@dataclass(frozen=True)
class CostModel:
    """Per-sweep static cost of one compiled executable.

    ``flops_per_sweep`` / ``hbm_bytes_per_sweep`` / ``coll_bytes_per_sweep``
    are the analyzer's totals with every loop clamped to one trip; multiply
    by the measured sweep count (``cost()``) to price a dispatch.  An
    ``error`` model (all costs zero, ``error`` set) is what a failed
    lowering degrades to — samples priced by it carry device time but no
    flop/byte attribution.
    """

    program: str
    plan_key: tuple
    bucket: int | None
    sharded: bool
    flops_per_sweep: float
    hbm_bytes_per_sweep: float
    coll_bytes_per_sweep: float
    unmodeled_ops: int = 0
    hlo_chars: int = 0
    compile_s: float = 0.0
    error: str | None = None
    device_kind: str | None = None

    @property
    def arithmetic_intensity(self) -> float:
        return self.flops_per_sweep / max(self.hbm_bytes_per_sweep, 1.0)

    def cost(self, sweeps: int) -> tuple[float, float, float]:
        """(flops, hbm_bytes, coll_bytes) for a dispatch that ran
        ``sweeps`` superstep/local-iteration bodies."""
        s = max(int(sweeps), 1)
        return (self.flops_per_sweep * s, self.hbm_bytes_per_sweep * s,
                self.coll_bytes_per_sweep * s)

    def attainable_s(self, sweeps: int) -> float | None:
        """Roofline lower bound on device time for ``sweeps`` sweeps: the
        slower of the compute and memory ceilings (collective bytes ride
        the HBM term — a deliberate single-node simplification).  ``None``
        on a device kind without published peaks."""
        peaks = DEVICE_PEAKS.get(self.device_kind)
        if peaks is None:
            return None
        fl, by, _ = self.cost(sweeps)
        return max(fl / peaks[0], by / peaks[1])


_LOCK = threading.Lock()
_MODELS: OrderedDict[tuple, CostModel] = OrderedDict()
_STATS = {"hits": 0, "misses": 0, "errors": 0}


def _shape_sig(kw: dict | None) -> tuple:
    if not kw:
        return ()
    out = []
    for k in sorted(kw):
        v = kw[k]
        shape = tuple(getattr(v, "shape", ()))
        dtype = str(getattr(v, "dtype", type(v).__name__))
        out.append((k, shape, dtype))
    return tuple(out)


def _plan_key(plan: Any) -> tuple:
    return (plan.k, plan.n_vertices, plan.v_max, plan.e_max, plan.epoch,
            plan.e_slots)


def cost_model(engine: Any, prog: Any, *, bucket: int | None = None,
               batched_kw: dict | None = None,
               max_supersteps: int | None = None, **kw: Any) -> CostModel:
    """The memoized per-sweep ``CostModel`` for one dispatchable executable.

    ``engine`` is duck-typed (``plan``, ``mesh``, ``lower_hlo``); ``prog``
    needs only ``.name``.  Never raises — failures return an error model
    (also cached, so a persistently broken lowering is paid for once).
    """
    key = (getattr(prog, "name", str(prog)), _plan_key(engine.plan),
           engine.mesh is not None, bucket, _shape_sig(kw),
           _shape_sig(batched_kw))
    with _LOCK:
        model = _MODELS.get(key)
        if model is not None:
            _MODELS.move_to_end(key)
            _STATS["hits"] += 1
            return model
        _STATS["misses"] += 1

    import time
    t0 = time.perf_counter()
    try:
        mesh = engine.mesh
        device = (mesh.devices.flat[0] if mesh is not None
                  else jax.devices()[0])
        hlo = engine.lower_hlo(prog, batched_kw=batched_kw,
                               max_supersteps=max_supersteps, **kw)
        costs = analyze_hlo(hlo, trip_clamp=1)
        model = CostModel(
            program=key[0], plan_key=key[1], bucket=bucket,
            sharded=key[2], flops_per_sweep=costs.flops,
            hbm_bytes_per_sweep=costs.bytes_traffic,
            coll_bytes_per_sweep=costs.coll_bytes,
            unmodeled_ops=costs.unmodeled_ops, hlo_chars=len(hlo),
            compile_s=time.perf_counter() - t0,
            device_kind=device.device_kind)
    except Exception as e:  # noqa: BLE001 — profiling never breaks serving
        model = CostModel(
            program=key[0], plan_key=key[1], bucket=bucket,
            sharded=key[2], flops_per_sweep=0.0, hbm_bytes_per_sweep=0.0,
            coll_bytes_per_sweep=0.0,
            compile_s=time.perf_counter() - t0,
            error=f"{type(e).__name__}: {e}")
        with _LOCK:
            _STATS["errors"] += 1

    with _LOCK:
        _MODELS[key] = model
        while len(_MODELS) > _CACHE_CAP:
            _MODELS.popitem(last=False)

    rec = _rec.get()
    if rec.enabled:
        rec.event("profile.compile", program=model.program,
                  bucket=bucket, flops_per_sweep=model.flops_per_sweep,
                  hbm_bytes_per_sweep=model.hbm_bytes_per_sweep,
                  unmodeled_ops=model.unmodeled_ops,
                  compile_s=round(model.compile_s, 4),
                  error=model.error)
    return model


def profile_stats() -> dict:
    with _LOCK:
        return {"size": len(_MODELS), **_STATS}


def reset_models() -> None:
    """Drop all memoized models and zero the stats (tests)."""
    with _LOCK:
        _MODELS.clear()
        for k in _STATS:
            _STATS[k] = 0


_rec.get().register_provider("cost_models", profile_stats)
