"""Guarded execution: plan validation, the impl-fallback ladder, and
runtime NaN quarantine (DESIGN.md §11).

The plan/execute split (§8) assumes every `LayerPlan` is well-formed and
every impl lowers on the target backend.  Production serving cannot: a
hand-shipped checkpoint may carry a corrupt tile encoding, the Pallas
kernel may fail to lower under Mosaic on real TPU, and a poisoned weight
turns every downstream logit into NaN.  This module is the safety layer
between the planner and the launcher:

* `validate_plan` — structural invariants on every LayerPlan (index
  ranges, tile counts vs capacity, the equal-NZE balance invariant, block
  divisibility, finite values, dtype/shape agreement) returning a typed
  per-layer `PlanReport`; strict mode raises `PlanValidationError` naming
  the failing layer and check (fail-fast at plan build/restore), advisory
  mode returns the report (serve-time diagnostics).  An optional
  probe-vector pass spot-checks numerical parity of each layer's encoded
  path against its own densified weights.
* `harden_plan` — the degradation ladder (`execute.IMPL_LADDER`: pallas ->
  xla -> xla_gather -> dense).  Each layer's impl is probed in isolation;
  on a trace/compile/lowering failure or a VMEM-budget trip the layer
  retries once with halved blocks, then steps down the ladder until a rung
  works.  Demotions are recorded in the plan (``spec.degraded_from``, meta
  key ``degraded``) and surface in `execute.STATS` as
  ``degraded_dispatch``.
* `locate_poisoned` / `quarantine_layers` — the runtime NaN guard's
  back-half: bisect the plan's sparse layers against the dense reference
  to find which layer(s) poison the logits, then flip exactly those layers
  to dense (preferring a known-good reference weight over the suspect
  encoding).  `launch/serve.py --guard` drives this from its per-step
  finiteness check.

Everything here is *off the hot path*: validation and hardening run once
at plan build, and the NaN guard costs one host sync per decode step only
when ``--guard`` is on.
"""
from __future__ import annotations

import dataclasses
from typing import Callable, Dict, Iterable, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..core.pruning import BalancedSparse
from ..kernels import ops as kernel_ops
from ..kernels.tile_format import TiledBalanced
from ..launch import cost_model as _cost
from . import execute
from .plan import LayerPlan, ModelPlan

Array = jax.Array


class GuardError(RuntimeError):
    """A fault the guard layer cannot degrade around (names the component)."""


class PlanValidationError(ValueError):
    """Strict `validate_plan` failure; carries the full `PlanReport`."""

    def __init__(self, report: "PlanReport"):
        self.report = report
        bad = [lr for lr in report.layers.values() if not lr.ok]
        lines = [f"plan validation failed on {len(bad)} layer(s):"]
        for lr in bad:
            for v in lr.violations:
                lines.append(f"  layer {lr.name!r} [{lr.impl}] "
                             f"check={v.check}: {v.detail}")
            if lr.probe_error:
                lines.append(f"  layer {lr.name!r} [{lr.impl}] "
                             f"probe: {lr.probe_error}")
        super().__init__("\n".join(lines))


@dataclasses.dataclass(frozen=True)
class Violation:
    """One failed structural check on one layer."""
    layer: str
    check: str      # index_range | count_capacity | balance | block_shape |
                    # finite | dtype | weights_type | shape | perm |
                    # quant | scale
    detail: str


@dataclasses.dataclass
class LayerReport:
    name: str
    impl: str
    violations: Tuple[Violation, ...] = ()
    probe_max_diff: float | None = None   # probe pass: max |sparse - dense|
    probe_error: str | None = None        # probe raised / exceeded tol

    @property
    def ok(self) -> bool:
        return not self.violations and self.probe_error is None


@dataclasses.dataclass
class PlanReport:
    """Typed per-layer validation result (`validate_plan`)."""
    layers: Dict[str, LayerReport]

    @property
    def ok(self) -> bool:
        return all(lr.ok for lr in self.layers.values())

    def violations(self) -> Tuple[Violation, ...]:
        return tuple(v for lr in self.layers.values() for v in lr.violations)

    def summary(self) -> str:
        bad = sum(1 for lr in self.layers.values() if not lr.ok)
        if not bad:
            return f"plan valid: {len(self.layers)} layer(s) checked"
        return (f"plan INVALID: {bad}/{len(self.layers)} layer(s) failed — "
                + "; ".join(f"{lr.name}:{v.check}"
                            for lr in self.layers.values()
                            for v in lr.violations)
                + "".join(f"; {lr.name}:probe" for lr in self.layers.values()
                          if lr.probe_error))


@dataclasses.dataclass(frozen=True)
class Degradation:
    """One ladder event from `harden_plan`."""
    layer: str
    from_impl: str
    to_impl: str
    action: str     # "halved_blocks" | "demoted"
    reason: str


# ---------------------------------------------------------------------------
# Structural validation
# ---------------------------------------------------------------------------

def _pow2_ge8(x: int) -> bool:
    return x >= 8 and (x & (x - 1)) == 0


def _check_blocks(spec, add) -> None:
    c = spec.blocks
    if c is None:
        add("block_shape", "sparse impl with no BlockChoice")
        return
    for f in ("bm", "bo", "bn"):
        v = getattr(c, f)
        if not _pow2_ge8(v):
            add("block_shape", f"{f}={v} is not a power of two >= 8")


def _unpack_int4_np(packed: np.ndarray, kb: int) -> np.ndarray:
    """NumPy twin of `tile_format.unpack_int4` (sign-extended nibbles)."""
    lo = packed & 0xF
    hi = packed >> 4
    q = np.stack([lo, hi], axis=-1).reshape(
        *packed.shape[:-1], packed.shape[-1] * 2).astype(np.int8)
    return ((q.astype(np.int32) ^ 8) - 8)[..., :kb]


def _check_tiled(spec, w: TiledBalanced, add) -> None:
    vals, idx, cnt = (np.asarray(w.values), np.asarray(w.indices),
                      np.asarray(w.counts))
    quant = w.quant or "none"
    if quant != spec.quant:
        add("quant", f"encoding quant={quant!r} != spec.quant="
            f"{spec.quant!r}")
    # indices always carry the logical [.., NB, O, KB] geometry; int4
    # values pack two nibbles per byte, so their last axis is ceil(KB/2)
    nb, kb = idx.shape[-3], idx.shape[-1]
    want_kb = -(-kb // 2) if quant == "int4" else kb
    if idx.shape[:-1] != vals.shape[:-1] or vals.shape[-1] != want_kb \
            or cnt.shape != idx.shape[:-1]:
        add("shape", f"values {vals.shape} / indices {idx.shape} / "
            f"counts {cnt.shape} disagree (quant={quant})")
        return
    if idx.shape[-2] != spec.n_out:
        add("shape", f"O={idx.shape[-2]} != spec.n_out={spec.n_out}")
    if w.n_in != spec.n_in:
        add("shape", f"n_in={w.n_in} != spec.n_in={spec.n_in}")
    if nb * w.bn < w.n_in:
        add("shape", f"NB*bn={nb * w.bn} < n_in={w.n_in}")
    if spec.block_k and kb != spec.block_k:
        add("shape", f"KB={kb} != spec.block_k={spec.block_k}")
    if quant != "none":
        if w.scales is None:
            add("quant", "quantized encoding carries no scales")
            return
        s = np.asarray(w.scales)
        if s.shape != cnt.shape:
            add("quant", f"scales {s.shape} != counts {cnt.shape}")
            return
        if vals.dtype != (np.int8 if quant == "int8" else np.uint8):
            add("dtype", f"{quant} values must be "
                f"{'int8' if quant == 'int8' else 'packed uint8'}, "
                f"got {vals.dtype}")
            return
        if not np.isfinite(s.astype(np.float32)).all():
            add("scale", "non-finite block scales")
        elif (s < 0).any():
            add("scale", "negative block scales (absmax scales are >= 0)")
        else:
            q = _unpack_int4_np(vals, kb) if quant == "int4" \
                else vals.astype(np.int32)
            qmax = 7 if quant == "int4" else 127
            if np.abs(q).max(initial=0) > qmax:
                add("scale", f"quantized values exceed the symmetric "
                    f"range [-{qmax}, {qmax}]")
            # the encoder never emits a nonzero q against a zero scale —
            # a zero-scale block with live values is a corrupt encoding
            if ((s == 0)[..., None] & (q != 0)).any():
                add("scale", "zero-scale block carries nonzero quantized "
                    "values")
    elif w.scales is not None:
        add("quant", "unquantized encoding carries scales")
    if spec.blocks is not None and w.bn != spec.blocks.bn:
        add("block_shape", f"encoding bn={w.bn} != blocks.bn="
            f"{spec.blocks.bn}")
    if idx.dtype.kind not in "iu" or cnt.dtype.kind not in "iu":
        add("dtype", f"indices {idx.dtype} / counts {cnt.dtype} "
            "must be integer")
        return
    if idx.size and (idx.min() < 0 or idx.max() >= w.bn):
        add("index_range", f"block-local indices span "
            f"[{idx.min()}, {idx.max()}], valid range [0, {w.bn})")
    if cnt.size and (cnt.min() < 0 or cnt.max() > kb):
        add("count_capacity", f"counts span [{cnt.min()}, {cnt.max()}], "
            f"capacity KB={kb}")
        return
    totals = np.swapaxes(cnt, -1, -2).reshape(-1, nb).sum(axis=1)
    if totals.size and not (totals == totals[0]).all():
        add("balance", f"per-row NZE totals span [{totals.min()}, "
            f"{totals.max()}] — the equal-NZE invariant is broken")
    elif totals.size and spec.k and int(totals[0]) != spec.k:
        add("balance", f"per-row NZE total {int(totals[0])} != spec.k="
            f"{spec.k}")
    # valid slots within one block must index distinct columns
    rows = idx.reshape(-1, kb)
    valid = np.arange(kb)[None, :] < cnt.reshape(-1)[:, None]
    probe = np.where(valid, rows, w.bn + np.arange(kb)[None, :])
    srt = np.sort(probe, axis=-1)
    dup = (srt[..., 1:] == srt[..., :-1]) & (srt[..., 1:] < w.bn)
    if dup.any():
        add("index_range", "duplicate column index inside a tile block")
    if not np.isfinite(vals.astype(np.float32)).all():
        add("finite", "non-finite encoded values")
    # packed-format invariants: a column-combining perm must be a bijection
    # of the padded column space, and its presence must agree with the
    # spec's packing provenance (a packed spec with no perm — or vice
    # versa — means the encoding and the plan record disagree).
    packed = bool(getattr(spec, "packed", False))
    if w.perm is None:
        if packed:
            add("perm", "spec.packed=True but encoding carries no perm")
        return
    if not packed:
        add("perm", "encoding carries a perm but spec.packed=False")
    p = np.asarray(w.perm)
    if p.shape[-1] != nb * w.bn:
        add("perm", f"perm length {p.shape[-1]} != NB*bn={nb * w.bn}")
        return
    prows = p.reshape(-1, p.shape[-1])
    want = np.arange(prows.shape[1])
    if any((np.sort(r) != want).any() for r in prows):
        add("perm", "perm is not a bijection of [0, NB*bn)")


def _check_flat(spec, w: BalancedSparse, add) -> None:
    vals, idx = np.asarray(w.values), np.asarray(w.indices)
    if idx.shape != vals.shape:
        add("shape", f"values {vals.shape} / indices {idx.shape} disagree")
        return
    if vals.shape[-2] != spec.n_out or w.n_in != spec.n_in:
        add("shape", f"[O, K]={vals.shape[-2:]} over n_in={w.n_in} vs spec "
            f"(n_out={spec.n_out}, n_in={spec.n_in})")
    if spec.k and vals.shape[-1] != spec.k:
        add("balance", f"K={vals.shape[-1]} != spec.k={spec.k}")
    if idx.dtype.kind not in "iu":
        add("dtype", f"indices dtype {idx.dtype} must be integer")
        return
    if idx.size and (idx.min() < 0 or idx.max() >= w.n_in):
        add("index_range", f"indices span [{idx.min()}, {idx.max()}], "
            f"valid range [0, {w.n_in})")
    rows = idx.reshape(-1, idx.shape[-1])
    if rows.shape[1] > 1 and (np.diff(np.sort(rows, axis=1), axis=1)
                              <= 0).any():
        add("index_range", "duplicate column index within a row")
    if not np.isfinite(vals.astype(np.float32)).all():
        add("finite", "non-finite encoded values")


def _check_dense(spec, w, add) -> None:
    arr = np.asarray(w)
    if spec.kind == "conv":
        if arr.ndim != 4 or arr.shape[0] != spec.n_out \
                or int(np.prod(arr.shape[1:])) != spec.n_in:
            add("shape", f"dense conv weights {arr.shape} vs spec "
                f"(Co={spec.n_out}, Ci*Hk*Wk={spec.n_in})")
    elif arr.shape[-2:] != (spec.n_out, spec.n_in):
        add("shape", f"dense weights {arr.shape} vs spec "
            f"([.., {spec.n_out}, {spec.n_in}])")
    if not np.isfinite(arr.astype(np.float32)).all():
        add("finite", "non-finite dense weights")


_IMPL_FORMAT = {"pallas": TiledBalanced, "xla": BalancedSparse,
                "xla_gather": BalancedSparse}

_COST_MODES = ("RIF", "RWF", "ON_CHIP")


def _check_cost(spec, weights, add) -> None:
    """Cost-provenance invariants (`PlanSpec.cost`, DESIGN.md §14): the
    stored byte accounting must match the actual weight pytree *exactly* —
    a tag that disagrees means the plan was rebuilt or the weights swapped
    after costing, and the serve report would lie about traffic."""
    tag = spec.cost
    if tag.objective not in _cost.OBJECTIVES:
        add("cost_objective", f"unknown objective {tag.objective!r}")
    if tag.mode not in _COST_MODES:
        add("cost_mode", f"unknown dataflow mode {tag.mode!r}")
    if tag.dram_bits < 0 or not np.isfinite(tag.energy_pj) \
            or tag.energy_pj < 0 or not np.isfinite(tag.latency_s) \
            or tag.latency_s < 0:
        add("cost_range", f"negative/non-finite cost figures "
            f"(dram_bits={tag.dram_bits}, energy_pj={tag.energy_pj}, "
            f"latency_s={tag.latency_s})")
    nbytes = _cost.pytree_nbytes(weights)
    if tag.w_total_bytes != nbytes:
        add("cost_bytes", f"tag w_total_bytes={tag.w_total_bytes} but "
            f"weights hold {nbytes} bytes")
    elif tag.w_stream_bytes <= 0 or tag.w_stream_bytes > max(nbytes, 1) \
            or (tag.w_stream_bytes and nbytes % tag.w_stream_bytes):
        add("cost_bytes", f"w_stream_bytes={tag.w_stream_bytes} does not "
            f"divide the stored {nbytes} bytes")


def validate_layer(lp: LayerPlan, name: str | None = None) -> LayerReport:
    """Structural checks for one LayerPlan (no probe).  ``name`` overrides
    the report label (plans key layers by name; the spec's own name can be
    a bare kind like "fc" in hand-built plans)."""
    spec = lp.spec
    name = name if name is not None else spec.name
    violations: list = []

    def add(check: str, detail: str) -> None:
        violations.append(Violation(name, check, detail))

    want = _IMPL_FORMAT.get(spec.impl)
    if want is BalancedSparse and spec.quant != "none":
        # quantized plans keep the tiled format on every sparse rung (the
        # per-block scales are tile-local)
        want = TiledBalanced
    if want is not None and not isinstance(lp.weights, want):
        add("weights_type", f"impl {spec.impl!r} expects "
            f"{want.__name__}, got {type(lp.weights).__name__}")
    elif want is None and isinstance(lp.weights,
                                     (TiledBalanced, BalancedSparse)):
        add("weights_type", f"impl {spec.impl!r} expects dense weights, "
            f"got {type(lp.weights).__name__}")
    elif isinstance(lp.weights, TiledBalanced):
        _check_blocks(spec, add)
        _check_tiled(spec, lp.weights, add)
    elif isinstance(lp.weights, BalancedSparse):
        _check_flat(spec, lp.weights, add)
    else:
        _check_dense(spec, lp.weights, add)
    if spec.cost is not None:
        _check_cost(spec, lp.weights, add)
    return LayerReport(name=name, impl=spec.impl,
                       violations=tuple(violations))


# ---------------------------------------------------------------------------
# Probe-vector parity spot-check
# ---------------------------------------------------------------------------

def _probe_view(lp: LayerPlan) -> LayerPlan:
    """Slice away stacked lead axes (scan's L axis) so `execute.apply_layer`
    sees one layer's weights; expert plans keep the E axis."""
    if lp.spec.kind == "conv":
        return lp
    w = lp.weights
    if isinstance(w, TiledBalanced):
        nd, base = w.values.ndim, 3
    elif isinstance(w, BalancedSparse):
        nd, base = w.values.ndim, 2
    else:
        nd, base = w.ndim, 2
    target = base + (1 if lp.spec.experts else 0)
    while nd > target:
        w = jax.tree.map(lambda a: a[0], w)
        nd -= 1
    return LayerPlan(spec=lp.spec, weights=w)


def _probe_input(lp: LayerPlan, m: int) -> Array:
    spec = lp.spec
    vals = (lp.weights.values if isinstance(
        lp.weights, (TiledBalanced, BalancedSparse)) else lp.weights)
    dt = vals.dtype if jnp.issubdtype(vals.dtype, jnp.inexact) \
        else jnp.float32
    rng = np.random.default_rng(20)
    if spec.kind == "conv":
        ci = spec.n_in // (spec.hk * spec.wk)
        hw = max(spec.hk, spec.wk, 4)
        shape = (1, hw, hw, ci)
    elif spec.experts:
        shape = (spec.experts, m, spec.n_in)
    else:
        shape = (m, spec.n_in)
    return jnp.asarray(rng.standard_normal(shape, np.float32), dt)


def _probe_tol(dtype, quant: str = "none") -> float:
    """Per-dtype / per-quant probe parity tolerance.

    f32 unquantized paths keep the tight 1e-4 bound (the probe reference
    is the layer's own densified weights — the identical values in a
    different contraction order).  Quantized paths compare the kernel's
    in-VMEM dequant against the densified dequant reference: the values
    still agree exactly, but int accumulation-order and the f32
    scale-multiply widen the spread, so a hardened quant plan must not
    spuriously demote on round-off (the satellite-6 regression)."""
    if quant != "none":
        return 5e-2
    return 1e-4 if jnp.dtype(dtype) == jnp.float32 else 2e-2


def _probe_one(view: LayerPlan, m: int,
               tol: float | None) -> Tuple[float | None, str | None]:
    """One probe shape: run the planned path on an m-row input and compare
    against the layer's own densified weights (the dense ladder floor)."""
    spec = view.spec
    x = _probe_input(view, m)
    # a modeled VMEM-budget trip is a failure even if interpret mode would
    # limp through it — on hardware it is an OOM at compile time
    if spec.blocks is not None and spec.impl == "pallas" \
            and 2 * spec.blocks.vmem_bytes > kernel_ops._VMEM_BUDGET \
            and kernel_ops.halve_blocks(spec.blocks) is not None:
        return None, (f"vmem budget trip: 2x{spec.blocks.vmem_bytes}B "
                      f"modeled > {kernel_ops._VMEM_BUDGET}B budget")
    try:
        y = execute.apply_layer(x, view)
        if spec.impl == "dense":
            ref = y
        else:
            ref = execute.apply_layer(
                x, execute.demote_layer(view, to_impl="dense"))
        y = np.asarray(jax.block_until_ready(y), np.float32)
        ref = np.asarray(ref, np.float32)
    except Exception as e:  # noqa: BLE001 — any dispatch failure demotes
        return None, f"{type(e).__name__}: {e}"
    if not np.isfinite(y).all():
        return None, "non-finite probe output"
    diff = float(np.max(np.abs(y - ref))) if spec.impl != "dense" else 0.0
    tol = tol if tol is not None else _probe_tol(x.dtype, spec.quant)
    if diff > tol:
        return diff, f"probe parity {diff:.3e} exceeds tol {tol:g}"
    return diff, None


def probe_layer(lp: LayerPlan, *, m: int = 16, m_decode: int | None = None,
                tol: float | None = None) -> Tuple[float | None, str | None]:
    """Probe one layer's planned path at BOTH serving shapes: the prefill
    shape (``m`` rows) and the layer's decode shape (``m_decode``, default
    the plan-recorded ``spec.decode_m``, else 4).  `execute.apply_*` routes
    skinny M onto different kernels and block choices than wide M, so a
    single-shape probe would certify a path serving never runs — a decode
    kernel that cannot lower, or decode blocks that trip VMEM, must demote
    the layer just like a prefill failure.

    Returns ``(max_abs_diff, error)`` — the worst parity diff across the
    probed shapes; error (None on success) is prefixed with the failing
    shape (``m=<mm>:``).  This is both `validate_plan(probe=True)`'s
    spot-check and `harden_plan`'s per-rung health test.
    """
    view = _probe_view(lp)
    spec = view.spec
    # conv probes ignore m (the probe input is a fixed small NHWC image)
    shapes = [m] if spec.kind == "conv" else sorted(
        {m, m_decode or spec.decode_m or 4})
    worst: float | None = None
    for mm in shapes:
        diff, err = _probe_one(view, mm, tol)
        if err is not None:
            return diff, f"m={mm}: {err}"
        if diff is not None and (worst is None or diff > worst):
            worst = diff
    return worst, None


def validate_plan(plan: ModelPlan, *, strict: bool = True,
                  probe: bool = False, probe_m: int = 16,
                  tol: float | None = None) -> PlanReport:
    """Check every LayerPlan's structural invariants (and optionally probe
    numerical parity).  ``strict=True`` raises `PlanValidationError` naming
    each failing layer and check — the fail-fast mode for plan build and
    checkpoint restore; ``strict=False`` always returns the report — the
    advisory mode for serve-time diagnostics."""
    reports: Dict[str, LayerReport] = {}
    for nm in sorted(plan.layers):
        lr = validate_layer(plan.layers[nm], nm)
        if probe and not lr.violations:
            # probing a structurally broken layer would just crash into the
            # kernels; the structural finding is the actionable one
            lr.probe_max_diff, lr.probe_error = probe_layer(
                plan.layers[nm], m=probe_m, tol=tol)
        reports[nm] = lr
    report = PlanReport(layers=reports)
    if strict and not report.ok:
        raise PlanValidationError(report)
    return report


# ---------------------------------------------------------------------------
# The degradation ladder
# ---------------------------------------------------------------------------

def _meta_set(meta: Tuple, key: str, value) -> Tuple:
    d = dict(meta)
    d[key] = value
    return tuple(d.items())


def harden_plan(plan: ModelPlan, *, probe_m: int = 16,
                tol: float | None = None
                ) -> Tuple[ModelPlan, Tuple[Degradation, ...]]:
    """Probe every layer's impl and walk failures down the ladder.

    Per layer: probe the current rung; on failure, a pallas layer first
    retries once with halved (bm, bo) — the VMEM-pressure recovery — then
    the layer demotes one rung (`execute.demote_layer`) and re-probes,
    until a rung passes.  Dense failing is unrecoverable and raises
    `GuardError` naming the layer (the weights themselves are bad — that
    is `validate_plan`'s jurisdiction, not the ladder's).

    Returns ``(hardened_plan, events)``; events are also stamped into the
    plan meta (key ``degraded``) and each demoted spec carries
    ``degraded_from``, so `serve.py` can report the degraded mix and
    `execute.STATS` ticks ``degraded_dispatch`` on their dispatches.
    """
    events: list = []
    layers: Dict[str, LayerPlan] = {}
    for nm in sorted(plan.layers):
        lp = plan.layers[nm]
        tried_halve = False
        while True:
            _, err = probe_layer(lp, m=probe_m, tol=tol)
            if err is None:
                break
            spec = lp.spec
            if spec.impl == "dense":
                raise GuardError(
                    f"layer {nm!r}: dense ladder floor failed ({err}) — "
                    "the weights themselves are unusable (component: "
                    "plan weights; run validate_plan)")
            if spec.impl == "pallas" and not tried_halve \
                    and spec.blocks is not None:
                tried_halve = True
                halved = kernel_ops.halve_blocks(
                    spec.blocks, kb=spec.block_k or None)
                if halved is not None:
                    events.append(Degradation(nm, spec.impl, spec.impl,
                                              "halved_blocks", err))
                    lp = LayerPlan(
                        spec=dataclasses.replace(spec, blocks=halved),
                        weights=lp.weights)
                    continue
            nxt = execute.next_impl(spec.impl)
            events.append(Degradation(nm, spec.impl, nxt, "demoted", err))
            lp = execute.demote_layer(lp, to_impl=nxt)
        layers[nm] = lp
    meta = plan.meta
    if events:
        meta = _meta_set(meta, "degraded",
                         tuple((e.layer, e.from_impl, e.to_impl, e.action,
                                e.reason) for e in events))
    return ModelPlan(layers=layers, meta=meta), tuple(events)


# ---------------------------------------------------------------------------
# Runtime NaN guard: bisection + quarantine
# ---------------------------------------------------------------------------

def quarantine_layers(plan: ModelPlan, names: Iterable[str],
                      ref_blocks: dict | None = None) -> ModelPlan:
    """Flip ``names`` to the dense impl (the quarantine action).

    ``ref_blocks`` — params-layout ``{name: [*lead, n_in, n_out]}`` known-
    good weights (e.g. the masked-dense reference) — replaces the suspect
    encoding outright when given; otherwise the layer's own densified
    weights are used (right when the *kernel*, not the values, produced
    the NaN).  Quarantined names are stamped into plan meta.
    """
    layers = dict(plan.layers)
    names = sorted(set(names))
    for nm in names:
        lp = layers[nm]
        ref = None
        if ref_blocks is not None and nm in ref_blocks:
            ref = jnp.swapaxes(ref_blocks[nm], -1, -2)
        if lp.spec.impl == "dense":
            if ref is not None:
                layers[nm] = LayerPlan(spec=lp.spec, weights=ref)
            continue
        layers[nm] = execute.demote_layer(lp, to_impl="dense",
                                          ref_dense=ref)
    prev = dict(plan.meta).get("quarantined", ())
    meta = _meta_set(plan.meta, "quarantined",
                     tuple(sorted(set(prev) | set(names))))
    return ModelPlan(layers=layers, meta=meta)


def locate_poisoned(plan: ModelPlan, eval_finite: Callable[[ModelPlan], bool],
                    *, ref_blocks: dict | None = None
                    ) -> Tuple[Tuple[str, ...], bool]:
    """Bisect the plan's sparse layers against the dense reference.

    ``eval_finite(candidate_plan) -> bool`` re-evaluates the model (e.g. a
    prefill) under a candidate plan.  Strategy: quarantining a prefix of
    the sparse layer list is monotone (more quarantine can only remove
    poison sources), so binary-search the smallest prefix whose quarantine
    restores finiteness — its last element is a culprit; quarantine it for
    real and repeat until the logits are finite (multiple poisoned layers
    converge one per round, O(log n) evals each).

    Returns ``(culprits, attributable)``: ``attributable=False`` means even
    the all-dense plan is non-finite — the poison is outside the planned
    layers (model params / dense path) and quarantine cannot help.
    """
    poisoned: list = []
    current = plan
    while not eval_finite(current):
        cand = [nm for nm in sorted(current.layers)
                if current.layers[nm].spec.is_sparse]
        if not cand or not eval_finite(
                quarantine_layers(current, cand, ref_blocks)):
            return tuple(poisoned), False
        lo, hi = 1, len(cand)
        while lo < hi:
            mid = (lo + hi) // 2
            if eval_finite(quarantine_layers(current, cand[:mid],
                                             ref_blocks)):
                hi = mid
            else:
                lo = mid + 1
        culprit = cand[lo - 1]
        poisoned.append(culprit)
        current = quarantine_layers(current, [culprit], ref_blocks)
    return tuple(poisoned), True


def nonfinite_rows(logits) -> np.ndarray:
    """Per-row finiteness mask of a ``[B, vocab]`` logits batch.

    The serving engine's *request*-granular NaN guard: `serving.engine`
    quarantines exactly the rows flagged here (evict + free pages) and
    keeps serving the rest of the batch — the per-request complement of
    the plan-level layer quarantine above, for faults that ride in with
    one request (poisoned embedding row, corrupt prompt) rather than with
    a planned layer.
    """
    return np.asarray(~jnp.isfinite(jnp.asarray(logits)).all(axis=-1))


__all__ = ["GuardError", "PlanValidationError", "Violation", "LayerReport",
           "PlanReport", "Degradation", "validate_layer", "validate_plan",
           "probe_layer", "harden_plan", "quarantine_layers",
           "locate_poisoned", "nonfinite_rows"]
