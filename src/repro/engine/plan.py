"""Layer-plan construction: one offline pass that fixes every per-layer
execution decision (DESIGN.md §8).

Sense's system contribution is that *model-side* analysis (per-layer
sparsity, compressed storage sizes) drives the *hardware-side* execution
strategy — Adaptive Dataflow Configuration picks RIF/RWF/ON_CHIP per layer
from IFM/weight storage ratios (§V-C).  The engine restates that as a
plan/execute split: `build_layer_plan` runs once per prunable layer and
derives a `LayerPlan` —

* **dataflow mode** (RIF / RWF / ON_CHIP) from `core.dataflow.choose_dataflow`
  on the layer's measured sparsity,
* **kernel impl** (pallas | xla | xla_gather | dense) from the §VI-F
  computing-mode thresholds plus whether the pruning pattern is balanced,
* **block sizes** from `kernels.ops.choose_blocks` (the VMEM-budget
  autotuner), and
* the weights **pre-encoded** to the impl's native format (`TiledBalanced`
  for the Pallas kernel, flat `BalancedSparse` for the XLA fallbacks, dense
  otherwise) as an explicit pytree artifact.

`ModelPlan` is the per-model container: a registered pytree (jit-traceable,
shardable, checkpointable through `checkpoint.store`) whose static decisions
live in hashable aux data and whose weights are ordinary array leaves.  This
replaces the per-call `id()`-keyed weakref encoding caches that `kernels/
ops.py` needed when every call site re-derived its own dispatch: the plan
*is* the cache, with explicit lifetime and explicit contents.

Pattern vs values: plan construction requires the sparsity *pattern*
(mask / indices) to be concrete — patterns freeze at prune time — but the
*values* may be jit tracers, so `plan_smallcnn` can run inside a jitted,
differentiated training step while the mask-derived structure stays static.

Coverage (`plan_model` dispatches by family): transformer attention/MLP
projections, MoE expert tensors ([L, E, d, f] with per-expert encodings
sharing one BlockChoice), MoE shared-expert projections, the RWKV6
R/K/V/G/O + channel-mix family, and the Zamba2 Mamba-block in/out
projections.  `plan_specs`/`shard_plan` give the encoded leaves real
PartitionSpecs (FSDP over output channels, expert-parallel over E) instead
of replicating them.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..core.dataflow import LayerSpec, choose_dataflow, ifm_storage_bits
from ..core.pruning import BalancedSparse, keep_count
from ..core.sparse_ops import SparseLinearSpec
from ..kernels import autotune
from ..kernels import ops as kernel_ops
from ..kernels.tile_format import (_KB_ROUND, _round_up, QUANT_MODES,
                                   TiledBalanced, encode_tiled,
                                   quantize_tiled, tiled_to_dense)
from ..launch import cost_model as _cost

Array = jax.Array


# ---------------------------------------------------------------------------
# Mask analysis (moved here from models/cnn.py — plan-time, not call-time)
# ---------------------------------------------------------------------------

def balanced_mask_k(mask2d) -> int | None:
    """Per-row NZE count if the mask is load-balanced, else None."""
    counts = np.count_nonzero(np.asarray(mask2d), axis=1)
    if counts.size and (counts == counts[0]).all() and counts[0] > 0:
        return int(counts[0])
    return None


def mask_block_k(mask2d, bn: int = 128) -> int:
    """Static per-bn-block NZE capacity from a concrete mask [O, N].

    Computed at the coarsest kernel block width (128) by default; the
    autotuner only ever picks power-of-two bn <= 128, and those blocks
    nest, so this is a valid capacity for any finer partition.
    """
    m = np.asarray(mask2d) != 0
    o, n = m.shape
    nb = -(-n // bn)
    pad = nb * bn - n
    if pad:
        m = np.pad(m, ((0, 0), (0, pad)))
    return int(m.reshape(o, nb, bn).sum(axis=2).max())


def _is_concrete(x) -> bool:
    return not isinstance(x, jax.core.Tracer)


def _pattern_indices(pattern: np.ndarray, k: int) -> np.ndarray:
    """Nonzero column indices per row (ascending) of a balanced pattern —
    pure NumPy so it stays concrete under a jit trace."""
    idx = np.argsort(pattern == 0, axis=1, kind="stable")[:, :k]
    return np.sort(idx, axis=1).astype(np.int32)


# ---------------------------------------------------------------------------
# LayerPlan / ModelPlan pytrees
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class PlanSpec:
    """The static (hashable — it is jit aux data) half of a LayerPlan."""
    name: str
    kind: str                       # "fc" | "conv"
    impl: str                       # pallas | xla | xla_gather | dense
    mode: str                       # RIF | RWF | ON_CHIP (dataflow choice)
    n_in: int
    n_out: int
    k: int                          # NZE per output row (n_in when dense)
    block_k: int                    # static per-bn-block capacity (KB)
    blocks: kernel_ops.BlockChoice | None
    w_sparsity: float
    d_mem_bits: int                 # chosen-mode DRAM traffic (model)
    i_mem_bits: int
    w_mem_bits: int
    hk: int = 1                     # conv geometry (kind == "conv")
    wk: int = 1
    stride: int = 1
    conv_padding: Any = "SAME"      # "SAME" | "VALID" | int
    experts: int = 0                # per-layer expert count (MoE tensors);
                                    # 0 = plain stacked projection
    tuned: str = "static"           # where ``blocks`` came from: "static"
                                    # (the VMEM model), "cached" (warm
                                    # autotune cache), "swept" (measured
                                    # during this plan build)
    blocks_static: kernel_ops.BlockChoice | None = None
                                    # the static model's prior for this
                                    # layer's resolve key (None when
                                    # ``blocks`` is; equals ``blocks``
                                    # when tuned == "static")
    degraded_from: str = ""         # the impl the planner originally chose,
                                    # set when the guard ladder demoted or
                                    # quarantined this layer ("" = never
                                    # degraded; see engine.guard)
    m_hint: int = 0                 # prefill GEMM M ``blocks`` was resolved
                                    # at (0 = pre-provenance plan)
    decode_m: int = 0               # decode-step GEMM M this plan serves
                                    # (``blocks_decode``'s resolve shape;
                                    # 0 = none recorded, guard probes 4)
    blocks_decode: kernel_ops.BlockChoice | None = None
                                    # decode-shaped BlockChoice (resolved at
                                    # M = decode_m); execute routes skinny-M
                                    # dispatches onto it
    packed: bool = False            # column-combining perm recorded on the
                                    # encoding (TiledBalanced.perm)
    pack_kb: Tuple = ()             # (kb_unpacked, kb_packed) provenance
                                    # when packed
    quant: str = "none"             # tile-local block-quant mode of the
                                    # encoding ("none" | "int8" | "int4");
                                    # always "none" for dense impls
    cost: Any = None                # launch.cost_model.CostTag provenance:
                                    # modeled per-dispatch DRAM/energy at
                                    # the build objective + the exact
                                    # stored byte counts the execute STATS
                                    # counters must reproduce (None on
                                    # pre-cost plans, e.g. plan_from_balanced)
    mesh: Any = None                # the mesh `shard_plan` spread the
                                    # encoded leaves over (None: one
                                    # device); execute then runs the
                                    # kernel per shard

    @property
    def is_sparse(self) -> bool:
        return self.impl != "dense"

    def __hash__(self):
        # Cached: the spec is jit aux data, re-hashed on every dispatch-
        # cache lookup of every jitted call — at serving that is per decoded
        # token, per layer.  Safe to memoize on a frozen dataclass.
        h = self.__dict__.get("_hash")
        if h is None:
            h = hash(tuple(getattr(self, f.name)
                           for f in dataclasses.fields(self)))
            object.__setattr__(self, "_hash", h)
        return h


@dataclasses.dataclass
class LayerPlan:
    """One layer's frozen execution decision + its pre-encoded weights.

    ``weights`` is `TiledBalanced` (impl == "pallas"), `BalancedSparse`
    (impl in xla/xla_gather), or a dense array ([O, N] fc / [Co, Ci, Hk, Wk]
    conv).  Leaves may carry an extra leading stacked-layer axis — `lax.scan`
    slices it away while the spec aux rides along unchanged.
    """
    spec: PlanSpec
    weights: Any

    def tree_flatten(self):
        return (self.weights,), self.spec

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(spec=aux, weights=children[0])

    def dense_weights(self) -> Array:
        """Densify back to [.., O, N] (fc) / the stored 4-D array (conv
        dense) — the masked-dense reference this plan must match.  Encoded
        leaves may carry any number of leading stacked axes ([L, ...] for
        scanned layers, [L, E, ...] for MoE expert tensors)."""
        w = self.weights
        if isinstance(w, TiledBalanced):
            lead = w.values.shape[:-3]
            if lead:                    # stacked [*lead, NB, O, KB]
                vf = w.values.reshape(-1, *w.values.shape[-3:])
                jf = w.indices.reshape(-1, *w.indices.shape[-3:])
                cf = w.counts.reshape(-1, *w.counts.shape[-2:])
                pf = None if w.perm is None else \
                    w.perm.reshape(-1, w.perm.shape[-1])
                sf = None if w.scales is None else \
                    w.scales.reshape(-1, *w.scales.shape[-2:])
                dense = jnp.stack([
                    tiled_to_dense(TiledBalanced(
                        vf[i], jf[i], cf[i], w.n_in, w.bn,
                        perm=None if pf is None else pf[i],
                        scales=None if sf is None else sf[i],
                        quant=w.quant))
                    for i in range(vf.shape[0])])
                return dense.reshape(*lead, *dense.shape[-2:])
            return tiled_to_dense(w)
        if isinstance(w, BalancedSparse):
            from ..kernels import ref
            lead = w.values.shape[:-2]
            if lead:                    # stacked [*lead, O, K]
                vf = w.values.reshape(-1, *w.values.shape[-2:])
                jf = w.indices.reshape(-1, *w.indices.shape[-2:])
                dense = jnp.stack([
                    ref.balanced_dense(vf[i], jf[i], w.n_in)
                    for i in range(vf.shape[0])])
                return dense.reshape(*lead, *dense.shape[-2:])
            return ref.balanced_dense(w.values, w.indices, w.n_in)
        return w


jax.tree_util.register_pytree_node(
    LayerPlan, LayerPlan.tree_flatten, LayerPlan.tree_unflatten)


@dataclasses.dataclass
class ModelPlan:
    """Per-model container of LayerPlans (a registered pytree).

    ``layers`` maps layer name -> LayerPlan; ``meta`` is a hashable tuple of
    (key, value) pairs recording how the plan was built.  Flattening is
    ordered by sorted layer name so checkpoint save/restore round-trips.
    """
    layers: Dict[str, LayerPlan]
    meta: Tuple = ()

    def tree_flatten(self):
        # Cached on the identity (+ length, to catch in-place key edits) of
        # the layers dict: the plan flattens on every jitted call's argument
        # traversal — per decoded token in serving — and re-sorting the
        # names each step is pure per-token overhead.  All plan transforms
        # in this repo rebuild the dict, which invalidates the cache.
        cached = self.__dict__.get("_flat_names")
        if cached is None or cached[0] is not self.layers \
                or len(cached[1]) != len(self.layers):
            names = tuple(sorted(self.layers))
            cached = (self.layers, names)
            self.__dict__["_flat_names"] = cached
        names = cached[1]
        return tuple(self.layers[n] for n in names), (names, self.meta)

    @classmethod
    def tree_unflatten(cls, aux, children):
        names, meta = aux
        return cls(layers=dict(zip(names, children)), meta=meta)

    # -- summaries ----------------------------------------------------------

    def mode_mix(self) -> Dict[str, int]:
        """Per-layer dataflow-mode counts (Fig.22b's RIF/RWF split)."""
        mix: Dict[str, int] = {}
        for lp in self.layers.values():
            mix[lp.spec.mode] = mix.get(lp.spec.mode, 0) + 1
        return mix

    def impl_mix(self) -> Dict[str, int]:
        mix: Dict[str, int] = {}
        for lp in self.layers.values():
            mix[lp.spec.impl] = mix.get(lp.spec.impl, 0) + 1
        return mix

    def tuned_mix(self) -> Dict[str, int]:
        """Where each planned layer's `BlockChoice` came from
        (static model / warm autotune cache / fresh sweep)."""
        mix: Dict[str, int] = {}
        for lp in self.layers.values():
            mix[lp.spec.tuned] = mix.get(lp.spec.tuned, 0) + 1
        return mix

    def tune_deltas(self) -> Tuple:
        """``(name, tuned (bm, bo, bn), static (bm, bo, bn))`` triples for
        layers whose measured choice differs from the static model, as
        recorded at build time (`meta` key ``tune_deltas``)."""
        return dict(self.meta).get("tune_deltas", ())

    def degraded_mix(self) -> Dict[str, int]:
        """Per-layer ``"<original>-><current>"`` counts for layers the
        guard ladder demoted or quarantined (empty = nothing degraded)."""
        mix: Dict[str, int] = {}
        for lp in self.layers.values():
            s = lp.spec
            if s.degraded_from:
                key = f"{s.degraded_from}->{s.impl}"
                mix[key] = mix.get(key, 0) + 1
        return mix

    def quarantined(self) -> Tuple:
        """Layer names the runtime NaN guard flipped to dense (`meta` key
        ``quarantined``, stamped by `engine.guard.quarantine_layers`)."""
        return dict(self.meta).get("quarantined", ())

    def cost_summary(self) -> Dict[str, Any]:
        """Aggregate the per-layer `CostTag` provenance (DESIGN.md §14).

        Per-dispatch figures scale by the stacked-layer count
        (``w_total_bytes // w_stream_bytes``) so the totals cover the whole
        model.  Layers without a tag (pre-cost plans) are skipped and
        counted in ``untagged``.
        """
        meta = dict(self.meta)
        out: Dict[str, Any] = {
            "objective": meta.get("objective", "latency"),
            "deployment": meta.get("deployment", ""),
            "total_dram_bytes": 0.0, "total_energy_pj": 0.0,
            "total_w_stream_bytes": 0, "total_act_bytes": 0,
            "modes": {}, "untagged": 0, "per_layer": {},
        }
        for nm in sorted(self.layers):
            tag = self.layers[nm].spec.cost
            if tag is None:
                out["untagged"] += 1
                continue
            if not out["deployment"]:
                out["deployment"] = tag.deployment
            n_disp = max(1, tag.w_total_bytes // max(tag.w_stream_bytes, 1))
            out["total_dram_bytes"] += tag.dram_bits / 8.0 * n_disp
            out["total_energy_pj"] += tag.energy_pj * n_disp
            out["total_w_stream_bytes"] += tag.w_stream_bytes * n_disp
            out["total_act_bytes"] += \
                (tag.act_in_bytes + tag.act_out_bytes) * n_disp
            out["modes"][tag.mode] = out["modes"].get(tag.mode, 0) + 1
            out["per_layer"][nm] = {
                "mode": tag.mode, "dram_bytes": tag.dram_bits / 8.0,
                "energy_pj": tag.energy_pj, "latency_s": tag.latency_s,
                "w_stream_bytes": tag.w_stream_bytes,
                "dispatches": n_disp,
            }
        return out

    @property
    def sparse_layer_count(self) -> int:
        return sum(1 for lp in self.layers.values() if lp.spec.is_sparse)

    @property
    def packed_layer_count(self) -> int:
        """Layers whose encoding carries a column-combining perm."""
        return sum(1 for lp in self.layers.values() if lp.spec.packed)

    def summary(self) -> str:
        lines = [f"{'layer':14s} {'mode':>8s} {'impl':>10s} {'O':>6s} "
                 f"{'N':>6s} {'K':>6s} {'spars':>6s} {'Dmem(Kb)':>9s}"]
        for name in sorted(self.layers):
            s = self.layers[name].spec
            lines.append(f"{name:14s} {s.mode:>8s} {s.impl:>10s} "
                         f"{s.n_out:6d} {s.n_in:6d} {s.k:6d} "
                         f"{s.w_sparsity:6.2f} {s.d_mem_bits / 1e3:9.0f}")
        lines.append(f"mode mix {self.mode_mix()}  impl mix {self.impl_mix()}"
                     f"  blocks {self.tuned_mix()}")
        degraded = self.degraded_mix()
        if degraded:
            lines.append(f"degraded {degraded}  quarantined "
                         f"{list(self.quarantined())}")
        return "\n".join(lines)


jax.tree_util.register_pytree_node(
    ModelPlan, ModelPlan.tree_flatten, ModelPlan.tree_unflatten)


# ---------------------------------------------------------------------------
# Impl policy (§VI-F computing-mode switch + backend capability)
# ---------------------------------------------------------------------------

def default_impl(*, balanced: bool, w_sparsity: float,
                 ifm_sparsity: float = 0.0) -> str:
    """dense below the §VI-F thresholds or for unbalanced patterns; else the
    Pallas kernel where it compiles (a TPU backend), the XLA densify+dot
    fallback elsewhere (Pallas would only run interpreted)."""
    spec = SparseLinearSpec(w_sparsity=w_sparsity, ifm_sparsity=ifm_sparsity)
    if not balanced or not spec.use_sparse:
        return "dense"
    return "pallas" if jax.default_backend() == "tpu" else "xla"


# ---------------------------------------------------------------------------
# Cost-objective co-optimization (DESIGN.md §14; launch.cost_model)
# ---------------------------------------------------------------------------

def _encoded_format_bits(*, impl: str, n_out: int, n_in: int, k: int,
                         bn: int, block_k: int, quant: str,
                         elem_bits: int) -> int:
    """Format-level weight-stream bits of one encoding candidate."""
    if impl == "dense":
        return n_out * n_in * elem_bits
    if impl == "pallas" or quant != "none":
        nb = -(-n_in // bn)
        return _cost.tiled_format_bits(n_out, nb, block_k, bn,
                                       elem_bits=elem_bits, quant=quant)
    return _cost.flat_format_bits(n_out, k, n_in, elem_bits=elem_bits)


def _evaluate_cost(*, objective: str, dep, layer_spec: LayerSpec | None,
                   kind: str, m_hint: int, n_in: int, n_out: int, k: int,
                   w_format_bits: int, quant: str,
                   elem_bits: int) -> Dict[str, Any]:
    """Per-mode DRAM bits + energy/latency for one (impl, encoding)
    candidate.  Conv layers stream compressed-bitmap IFMs per the layer
    geometry; fc layers stream a dense ``[m_hint, N]`` activation block.
    """
    if kind == "conv" and layer_spec is not None:
        i_bits = ifm_storage_bits(layer_spec, elem_bits=elem_bits)
        o_elems = layer_spec.h_o * layer_spec.w_o * layer_spec.c_o
        o_bits = o_elems * dep.act_bits
        psum = o_elems * dep.psum_bits
        macs = round(layer_spec.macs * (k / max(n_in, 1)))
    else:
        i_bits = m_hint * n_in * dep.act_bits
        o_bits = m_hint * n_out * dep.act_bits
        psum = m_hint * n_out * dep.psum_bits
        macs = m_hint * n_out * k
    per_mode = _cost.mode_dram_bits(i_bits, w_format_bits, o_bits, psum, dep)
    mode = min(per_mode, key=lambda m: (per_mode[m],
                                        _cost._MODE_ORDER.index(m)))
    d = per_mode[mode]
    energy = _cost.layer_energy_pj(d, macs, dep, quant=quant)
    lat = _cost.layer_latency_s(d, macs, dep)
    return {"mode": mode, "per_mode": per_mode, "dram_bits": d,
            "energy_pj": energy, "latency_s": lat, "macs": macs,
            "i_bits": i_bits, "o_bits": o_bits,
            "score": _cost.objective_score(objective, dram_bits=d,
                                           energy_pj=energy, latency_s=lat)}


def _format_bits_of(weights: Any, *, elem_bits: int,
                    lead_layers: int = 1) -> int:
    """Per-dispatch format-level bits of an encoded weights pytree (the
    scanned leading axis divides out; expert axes stay in the dispatch)."""
    if isinstance(weights, TiledBalanced):
        nb, o, kb = weights.indices.shape[-3:]
        g = int(np.prod(weights.indices.shape[:-3])) if \
            weights.indices.ndim > 3 else 1
        per = _cost.tiled_format_bits(o, nb, kb, weights.bn,
                                      elem_bits=elem_bits,
                                      quant=weights.quant)
    elif isinstance(weights, BalancedSparse):
        o, k = weights.indices.shape[-2:]
        g = int(np.prod(weights.indices.shape[:-2])) if \
            weights.indices.ndim > 2 else 1
        per = _cost.flat_format_bits(o, k, weights.n_in,
                                     elem_bits=elem_bits)
    else:                                # dense array (fc 2-D or conv 4-D)
        g = 1
        per = int(weights.size) * elem_bits
    return per * g // max(1, lead_layers)


def _tag_for(*, objective: str, dep, ev: Dict[str, Any], mode: str,
             quant: str, weights: Any, lead_layers: int, m_hint: int,
             n_in: int, n_out: int, itemsize: int) -> "_cost.CostTag":
    """Stamp the provenance record at ``mode`` (the spec's mode — under the
    latency objective that is the §V-C choice, which the deployment's
    buffers may not even admit; fall back to the model's own pick then),
    plus the *exact* stored byte counts the execute STATS must reproduce."""
    d = ev["per_mode"].get(mode, ev["dram_bits"])
    w_total = _cost.pytree_nbytes(weights)
    return _cost.CostTag(
        objective=objective, deployment=dep.name, mode=mode,
        w_stream_bytes=w_total // max(1, lead_layers),
        w_total_bytes=w_total,
        act_in_bytes=m_hint * n_in * itemsize,
        act_out_bytes=m_hint * n_out * itemsize,
        dram_bits=int(d),
        energy_pj=float(_cost.layer_energy_pj(d, ev["macs"], dep,
                                              quant=quant)),
        latency_s=float(_cost.layer_latency_s(d, ev["macs"], dep)))


# ---------------------------------------------------------------------------
# Single-layer plan construction
# ---------------------------------------------------------------------------

def _maybe_pack(idx: np.ndarray, vals, pattern2: np.ndarray, n_in: int,
                bn: int, block_k: int):
    """Try column-combining packing (`tile_format.pack_columns`) on a flat
    balanced encoding before tiling.

    ``idx`` [..., O, K] ascending global indices, ``vals`` the matching
    value array, ``pattern2`` [rows, n_in] the pooled mask the shared KB is
    computed over.  Adopted only when the packed per-block capacity
    strictly shrinks KB (otherwise the permutation costs an input gather
    for nothing).  Returns ``(idx, vals, block_k, n_enc, perm, pack_kb)``:
    perm is None when not adopted (and ``n_enc == n_in``); when adopted,
    indices are remapped into packed column space (re-sorted ascending),
    ``n_enc`` is the padded packed width NB*bn, and ``pack_kb`` records
    ``(kb_unpacked, kb_packed)`` for spec provenance.
    """
    from ..kernels import tile_format
    nb = -(-n_in // bn)
    if nb <= 1:
        return idx, vals, block_k, n_in, None, ()
    perm = tile_format.pack_columns(pattern2, bn)
    inv = tile_format.invert_perm(perm)
    pidx = inv[idx]
    # packed positions fit 16 bits at every width served here, where the
    # stable argsort is a radix sort (linear, not n log n)
    key = pidx.astype(np.uint16) if nb * bn <= 1 << 16 else pidx
    order = np.argsort(key, axis=-1, kind="stable")
    pidx = np.take_along_axis(pidx, order, axis=-1).astype(np.int32)
    npack = nb * bn
    kb_packed = tile_format.max_block_count(
        pidx.reshape(-1, pidx.shape[-1]), npack, bn)
    if kb_packed >= block_k:
        return idx, vals, block_k, n_in, None, ()
    vals = jnp.take_along_axis(vals, jnp.asarray(order), axis=-1)
    return pidx, vals, kb_packed, npack, perm, (block_k, kb_packed)


def build_layer_plan(name: str, w: Array, *, mask: Array | None = None,
                     kind: str = "fc", layer_spec: LayerSpec | None = None,
                     m_hint: int = 128, decode_m: int = 4,
                     impl: str | None = None,
                     ifm_sparsity: float = 0.0, elem_bits: int = 16,
                     weight_buffer_bits: int | None = None,
                     n_is: int = 7, n_pe: int = 32,
                     dtype=None, stride: int = 1,
                     conv_padding: Any = "SAME", tune: str = "off",
                     tune_cache: str | None = None,
                     pack: bool = True, quant: str = "none",
                     objective: str = "latency",
                     deployment: Any = None) -> LayerPlan:
    """Derive one LayerPlan from a dense weight (output-major ``[O, N]`` for
    fc, ``[Co, Ci, Hk, Wk]`` for conv) and an optional pruning mask.

    The pattern (``mask``, or the nonzero structure of a concrete ``w``)
    must be concrete; ``w``'s values may be tracers.  ``impl`` overrides the
    §VI-F policy but degrades to "dense" when the pattern is unbalanced or
    unanalyzable (traced values, no mask) — the mask is still applied.
    ``m_hint`` is the prefill GEMM M the block autotuner optimizes for;
    ``decode_m`` is the decode-step M a second, decode-shaped `BlockChoice`
    (``PlanSpec.blocks_decode``) is resolved at, so skinny-M dispatches
    never run prefill-shaped blocks.  ``pack`` enables column-combining
    packing (`tile_format.pack_columns`) for pallas fc layers when it
    shrinks the shared per-block capacity KB.

    ``tune`` selects the block-choice policy (`kernels.autotune.
    resolve_blocks`): ``"off"`` uses the static VMEM model, ``"cached"``
    consults the measured autotune cache at ``tune_cache`` (default
    `autotune.default_cache_path`) and falls back to the static model on a
    miss, ``"sweep"`` additionally times candidates and persists the winner
    on a miss.  The provenance lands in ``PlanSpec.tuned``.

    ``quant`` selects the tile-local block-quant mode ("none" | "int8" |
    "int4"): sparse layers encode to `TiledBalanced` (for *every* sparse
    impl — the quantized scales live tile-locally, so the XLA fallbacks
    keep the tiled format too) and quantize per bn-block
    (`tile_format.quantize_tiled`); dense layers ignore it.

    ``objective``/``deployment`` select the plan objective (DESIGN.md §14):
    ``"latency"`` (the default) keeps today's §V-C / §VI-F selection rules
    bit-for-bit and only *annotates* the spec with `PlanSpec.cost`;
    ``"dram"`` / ``"energy"`` / ``"balanced"`` co-optimize the dataflow
    mode and the impl (sparse encoding vs dense stream, never promoting up
    the ladder) against `launch.cost_model`'s per-component accounting for
    the named `DeploymentProfile`.
    """
    if quant not in QUANT_MODES:
        raise ValueError(f"quant must be one of {QUANT_MODES}, "
                         f"got {quant!r}")
    # Pattern analysis runs in pure NumPy: inside a jit trace every jnp op
    # stages (omnistaging) even on concrete operands, and the pattern must
    # stay host-concrete for the static plan decisions.  Values may trace.
    hk = wk = 1
    if mask is not None:
        if not _is_concrete(mask):
            raise ValueError(f"{name}: plan construction needs a concrete "
                             "mask (patterns freeze at prune time)")
        mask_np = np.asarray(mask)
    else:
        mask_np = None
    if w.ndim == 4:
        kind = "conv"
        co, ci, hk, wk = w.shape
        w2 = w.reshape(co, -1)
        mask2 = mask_np.reshape(co, -1) if mask_np is not None else None
    elif w.ndim == 2:
        w2 = w
        mask2 = mask_np
    else:
        raise ValueError(f"expected 2-D or 4-D weights, got {w.shape}")
    o, n = w2.shape
    masked2 = w2 * mask2 if mask2 is not None else w2

    if mask2 is not None:
        pattern = mask2
    elif _is_concrete(w2):
        pattern = (np.asarray(w2) != 0).astype(np.float32)
    else:
        # traced values, no mask: nothing to analyze — stay dense
        pattern = None
    if pattern is not None:
        k = balanced_mask_k(pattern)
        balanced = k is not None and k < n
        w_sparsity = 1.0 - (k / n) if balanced \
            else 1.0 - float(np.count_nonzero(pattern)) / pattern.size
    else:
        k, balanced, w_sparsity = None, False, 0.0

    # -- dataflow mode (§V-C) ----------------------------------------------
    if layer_spec is None:
        layer_spec = LayerSpec(name=name, kind="fc", c_i=n, c_o=o)
    layer_spec = dataclasses.replace(layer_spec, w_sparsity=w_sparsity,
                                     ifm_sparsity=ifm_sparsity)
    flow = choose_dataflow(layer_spec, n_is=n_is, n_pe=n_pe,
                           weight_buffer_bits=weight_buffer_bits,
                           elem_bits=elem_bits)

    # -- kernel impl (§VI-F) + blocks + encoding ----------------------------
    if impl is None:
        impl = default_impl(balanced=balanced, w_sparsity=w_sparsity,
                            ifm_sparsity=ifm_sparsity)
    elif impl != "dense" and not balanced:
        # requested sparse impl is infeasible (unbalanced / dense pattern):
        # degrade to dense — the mask is still applied
        impl = "dense"

    dt = dtype or w2.dtype
    dep = _cost.get_deployment(deployment)
    if objective not in _cost.OBJECTIVES:
        raise ValueError(f"objective must be one of {_cost.OBJECTIVES}, "
                         f"got {objective!r}")
    if objective != "latency" and impl != "dense":
        # Impl co-optimization: flip to the dense stream when it scores
        # better under the objective (format-level comparison at the
        # static block choice; packing can only shrink the sparse side, so
        # a sparse win here is conservative).  Never promotes up the ladder.
        blk0 = autotune.resolve_blocks(
            m_hint, o, n, k, itemsize=jnp.dtype(dt).itemsize, impl=impl,
            tune="off", dtype=dt, quant=quant).blocks
        bk0 = max(_KB_ROUND,
                  _round_up(mask_block_k(pattern, bn=blk0.bn), _KB_ROUND))
        ev_s = _evaluate_cost(
            objective=objective, dep=dep, layer_spec=layer_spec, kind=kind,
            m_hint=m_hint, n_in=n, n_out=o, k=k,
            w_format_bits=_encoded_format_bits(
                impl=impl, n_out=o, n_in=n, k=k, bn=blk0.bn, block_k=bk0,
                quant=quant, elem_bits=elem_bits),
            quant=quant, elem_bits=elem_bits)
        ev_d = _evaluate_cost(
            objective=objective, dep=dep, layer_spec=layer_spec, kind=kind,
            m_hint=m_hint, n_in=n, n_out=o, k=n,
            w_format_bits=o * n * elem_bits, quant="none",
            elem_bits=elem_bits)
        if ev_d["score"] < ev_s["score"]:
            impl = "dense"
    blocks = None
    blocks_decode = None
    block_k = 0
    tuned = "static"
    blocks_static = None
    packed = False
    pack_kb: Tuple = ()
    if impl == "dense":
        # conv keeps the 4-D layout apply_conv convolves with
        masked = (w * mask_np if mask_np is not None else w) if w.ndim == 4 \
            else masked2
        weights: Any = masked.astype(dt)
        k = n
        quant = "none"
    else:
        itemsize = jnp.dtype(dt).itemsize
        res = autotune.resolve_blocks(m_hint, o, n, k, itemsize=itemsize,
                                      impl=impl, tune=tune,
                                      cache_path=tune_cache,
                                      dtype=dt, quant=quant)
        blocks, tuned, blocks_static = res.blocks, res.source, res.static
        blocks_decode = autotune.resolve_blocks(
            decode_m, o, n, k, itemsize=itemsize, impl=impl, tune=tune,
            cache_path=tune_cache, dtype=dt, quant=quant).blocks
        idx = _pattern_indices(pattern, k)                # np [O, K] int32
        vals = jnp.take_along_axis(jnp.asarray(masked2),
                                   jnp.asarray(idx), axis=1).astype(dt)
        block_k = max(_KB_ROUND,
                      _round_up(mask_block_k(pattern, bn=blocks.bn),
                                _KB_ROUND))
        if impl == "pallas" or quant != "none":
            n_enc, perm = n, None
            if impl == "pallas" and pack and kind == "fc":
                idx, vals, block_k, n_enc, perm, pack_kb = _maybe_pack(
                    idx, vals, pattern, n, blocks.bn, block_k)
            # np indices keep encode_tiled on its host (concrete) path
            tb = encode_tiled(vals, idx, n_enc, bn=blocks.bn, kb=block_k)
            weights = TiledBalanced(tb.values, tb.indices, tb.counts,
                                    n_in=n, bn=blocks.bn,
                                    perm=None if perm is None
                                    else jnp.asarray(perm))
            packed = perm is not None
            if quant != "none":
                weights = quantize_tiled(weights, quant)
        else:
            weights = BalancedSparse(vals, idx, n)

    # -- cost provenance (DESIGN.md §14) ------------------------------------
    # Final evaluation runs on the *actual* encoding (post-pack block_k /
    # tile counts), not the pre-encoding estimate the impl flip used.
    ev = _evaluate_cost(objective=objective, dep=dep, layer_spec=layer_spec,
                        kind=kind, m_hint=m_hint, n_in=n, n_out=o, k=int(k),
                        w_format_bits=_format_bits_of(weights,
                                                      elem_bits=elem_bits),
                        quant=quant, elem_bits=elem_bits)
    mode = flow.mode if objective == "latency" else ev["mode"]
    tag = _tag_for(objective=objective, dep=dep, ev=ev, mode=mode,
                   quant=quant, weights=weights, lead_layers=1,
                   m_hint=int(m_hint), n_in=n, n_out=o,
                   itemsize=jnp.dtype(dt).itemsize)

    spec = PlanSpec(name=name, kind=kind, impl=impl, mode=mode,
                    n_in=n, n_out=o, k=int(k), block_k=block_k,
                    blocks=blocks, w_sparsity=float(w_sparsity),
                    d_mem_bits=int(flow.d_mem_bits), i_mem_bits=int(flow.i_mem),
                    w_mem_bits=int(flow.w_mem), hk=hk, wk=wk, stride=stride,
                    conv_padding=conv_padding, tuned=tuned,
                    blocks_static=blocks_static, m_hint=int(m_hint),
                    decode_m=int(decode_m), blocks_decode=blocks_decode,
                    packed=packed, pack_kb=pack_kb, quant=quant, cost=tag)
    return LayerPlan(spec=spec, weights=weights)


def plan_from_balanced(sp: BalancedSparse, *, name: str = "adhoc",
                       impl: str = "pallas", block_k: int | None = None,
                       m_hint: int = 128, ifm_sparsity: float = 0.0,
                       tune: str = "off", tune_cache: str | None = None
                       ) -> LayerPlan:
    """Wrap an existing flat BalancedSparse as a single-layer plan (the
    `core.sparse_ops` delegation path).  Indices must be concrete."""
    o, k = sp.values.shape
    n = sp.n_in
    itemsize = jnp.dtype(sp.values.dtype).itemsize
    res = autotune.resolve_blocks(m_hint, o, n, k, itemsize=itemsize,
                                  impl=impl, tune=tune,
                                  cache_path=tune_cache)
    blocks = res.blocks
    if impl == "pallas":
        if block_k is None:
            from ..kernels.tile_format import max_block_count
            block_k = max_block_count(sp.indices, n, blocks.bn)
        else:
            block_k = max(_KB_ROUND, _round_up(block_k, _KB_ROUND))
        weights: Any = encode_tiled(sp.values, sp.indices, n, bn=blocks.bn,
                                    kb=block_k)
    else:
        weights = sp
    w_sparsity = 1.0 - k / n
    flow = choose_dataflow(LayerSpec(name=name, kind="fc", c_i=n, c_o=o,
                                     w_sparsity=w_sparsity,
                                     ifm_sparsity=ifm_sparsity))
    spec = PlanSpec(name=name, kind="fc", impl=impl, mode=flow.mode,
                    n_in=n, n_out=o, k=k, block_k=block_k or 0,
                    blocks=blocks, w_sparsity=w_sparsity,
                    d_mem_bits=int(flow.d_mem_bits), i_mem_bits=int(flow.i_mem),
                    w_mem_bits=int(flow.w_mem), tuned=res.source,
                    blocks_static=res.static)
    return LayerPlan(spec=spec, weights=weights)


# ---------------------------------------------------------------------------
# Model-level planners
# ---------------------------------------------------------------------------

def plan_smallcnn(cfg, params: dict, masks: dict | None = None, *,
                  impl: str | None = None, ifm_sparsity: float = 0.0,
                  weight_buffer_bits: int | None = None,
                  m_hint: int = 4096, tune: str = "off",
                  tune_cache: str | None = None,
                  quant: str = "none", objective: str = "latency",
                  deployment: Any = None) -> ModelPlan:
    """One offline pass over the small CNN: conv layers with balanced masks
    go through the sparse conv path, balanced fc masks through the balanced
    GEMM, everything else stays dense (mask still applied)."""
    masks = masks or {}
    layers: Dict[str, LayerPlan] = {}
    img, cin = cfg.img, 3
    for i, cout in enumerate(cfg.channels):
        name = f"conv{i}"
        hw = img // (2 ** i)
        geom = LayerSpec(name=name, kind="conv", h_i=hw, w_i=hw, c_i=cin,
                         c_o=cout, h_k=cfg.kernel, w_k=cfg.kernel, stride=1,
                         padding=cfg.kernel // 2)
        layers[name] = build_layer_plan(
            name, params[name], mask=masks.get(name), layer_spec=geom,
            m_hint=m_hint, impl=impl, ifm_sparsity=ifm_sparsity,
            weight_buffer_bits=weight_buffer_bits, conv_padding="SAME",
            tune=tune, tune_cache=tune_cache, quant=quant,
            objective=objective, deployment=deployment)
        cin = cout
    for name in ("fc1", "fc2"):
        layers[name] = build_layer_plan(
            name, params[name], mask=masks.get(name), kind="fc",
            m_hint=m_hint, impl=impl, ifm_sparsity=ifm_sparsity,
            weight_buffer_bits=weight_buffer_bits, tune=tune,
            tune_cache=tune_cache, quant=quant,
            objective=objective, deployment=deployment)
    meta = (("model", "smallcnn"),) + _cost_meta(objective, deployment) \
        + _tune_meta(tune, layers)
    return ModelPlan(layers=layers, meta=meta)


# The projection families the planner can prune, per model family: every
# entry is a stacked [L, n_in, n_out] (or [L, E, n_in, n_out] for MoE
# expert tensors) leaf of params["blocks"].
ATTN_PROJ_NAMES = ("wq", "wk", "wv", "wo")
MLP_PROJ_NAMES = ("w_gate", "w_up", "w_down", "w_in", "w_out")
MOE_SHARED_NAMES = ("ws_gate", "ws_up", "ws_down")
MOE_EXPERT_NAMES = ("we_gate", "we_up", "we_down")
# RWKV6 (models/rwkv6.py flags these Sense-applicable): time-mix R/K/V/G/O
# plus the channel-mix matrices; the WKV recurrence stays dense/elementwise.
RWKV6_PROJ_NAMES = ("wr", "wkm", "wv", "wg", "wo", "ck", "cv", "cr")
# Zamba2 Mamba-block in/out projections; B/C/dt projections are tiny
# (d -> ssm_state / nheads) and stay dense, like the paper's non-CONV/FC ops.
ZAMBA2_PROJ_NAMES = ("z_proj", "x_proj", "out_proj")


def _plan_stacked(nm: str, w: Array, *, sparsity: float, impl: str | None,
                  m_hint: int, cd, tune: str = "off",
                  tune_cache: str | None = None, decode_m: int = 4,
                  pack: bool = True, quant: str = "none",
                  objective: str = "latency",
                  deployment: Any = None) -> LayerPlan:
    """Plan one stacked projection ``[*lead, n_in, n_out]``.

    ``lead`` is any tuple of stacked axes — ``(L,)`` for scanned layers,
    ``(L, E)`` for MoE expert tensors.  Every slice is transposed to
    output-major, balanced-pruned along the input dim (equal NZE per output
    channel — the Sense invariant), encoded to the impl's native format
    with a *shared* `BlockChoice`/KB across all slices (one static spec for
    the whole stack; the choice comes from `kernels.autotune.resolve_blocks`
    under the ``tune`` policy), and restacked on the leading axes so
    `lax.scan` / the expert loop can slice per-layer weights while the spec
    rides as aux data.

    A second, decode-shaped `BlockChoice` is resolved at ``M = decode_m``
    (``PlanSpec.blocks_decode``) for skinny-M dispatch.  ``pack`` enables
    column-combining packing for pallas encodings: one shared permutation
    over the pooled [g*O, N] pattern (so the whole stack scans with one
    perm), adopted only when it shrinks the shared KB; the perm leaf is
    broadcast over the lead axes so per-layer pytree slicing stays
    shape-consistent.

    ``quant`` block-quantizes the encoding per bn-block ("int8" | "int4");
    every sparse impl then stores `TiledBalanced` (the scales are tile-
    local, so the XLA fallbacks keep the tiled format too).
    """
    if quant not in QUANT_MODES:
        raise ValueError(f"quant must be one of {QUANT_MODES}, "
                         f"got {quant!r}")
    cd = jnp.dtype(cd)  # accept dtype classes (jnp.bfloat16) and instances
    lead = w.shape[:-2]
    n_in, n_out = w.shape[-2:]
    g = int(np.prod(lead)) if lead else 1
    k = keep_count(n_in, sparsity)
    if impl is None:
        impl_nm = default_impl(balanced=True, w_sparsity=1.0 - k / n_in)
    else:
        impl_nm = impl
    # All g slices batch through one fused path (the tile layout is per-row
    # independent, so [g*O, K] encodes in a single pass — no per-slice
    # device round-trips even at L*E scale): output-major transpose, per-row
    # top-k prune (same stable tie-breaking as balanced_prune_rows), one
    # host sync for the pattern.
    wt = jnp.swapaxes(w.reshape(g, n_in, n_out), -1, -2).astype(cd)
    order = jnp.argsort(-jnp.abs(wt), axis=-1, stable=True)
    ranks = jnp.argsort(order, axis=-1, stable=True)
    masks = np.asarray(ranks < k)                         # [g, O, N] bool
    dep = _cost.get_deployment(deployment)
    if objective not in _cost.OBJECTIVES:
        raise ValueError(f"objective must be one of {_cost.OBJECTIVES}, "
                         f"got {objective!r}")
    lead0 = int(lead[0]) if lead else 1       # dispatches per scan step
    g_disp = g // lead0                       # slices per dispatch (experts)
    elem_bits = cd.itemsize * 8
    if objective != "latency" and impl_nm != "dense":
        # Impl co-optimization at the static block choice (same comparison
        # as build_layer_plan; per-dispatch basis so the scanned lead axis
        # divides out).  Never promotes up the ladder.
        blk0 = autotune.resolve_blocks(m_hint, n_out, n_in, k,
                                       itemsize=cd.itemsize, impl=impl_nm,
                                       tune="off", dtype=cd,
                                       quant=quant).blocks
        bk0 = max(_KB_ROUND, _round_up(
            mask_block_k(masks.reshape(g * n_out, n_in), bn=blk0.bn),
            _KB_ROUND))
        ev_s = _evaluate_cost(
            objective=objective, dep=dep, layer_spec=None, kind="fc",
            m_hint=m_hint, n_in=n_in, n_out=n_out, k=k,
            w_format_bits=g_disp * _encoded_format_bits(
                impl=impl_nm, n_out=n_out, n_in=n_in, k=k, bn=blk0.bn,
                block_k=bk0, quant=quant, elem_bits=elem_bits),
            quant=quant, elem_bits=elem_bits)
        ev_d = _evaluate_cost(
            objective=objective, dep=dep, layer_spec=None, kind="fc",
            m_hint=m_hint, n_in=n_in, n_out=n_out, k=n_in,
            w_format_bits=g_disp * n_out * n_in * elem_bits, quant="none",
            elem_bits=elem_bits)
        if ev_d["score"] < ev_s["score"]:
            impl_nm = "dense"
    tuned = "static"
    blk_static = None
    blk_dec = None
    packed = False
    pack_kb: Tuple = ()
    if impl_nm == "dense":
        weights: Any = (wt * masks).reshape(*lead, n_out, n_in)
        blk = None
        block_k = 0
        quant = "none"
    else:
        itemsize = cd.itemsize
        res = autotune.resolve_blocks(m_hint, n_out, n_in, k,
                                      itemsize=itemsize, impl=impl_nm,
                                      tune=tune, cache_path=tune_cache,
                                      dtype=cd, quant=quant)
        blk, tuned, blk_static = res.blocks, res.source, res.static
        blk_dec = autotune.resolve_blocks(decode_m, n_out, n_in, k,
                                          itemsize=itemsize, impl=impl_nm,
                                          tune=tune,
                                          cache_path=tune_cache,
                                          dtype=cd, quant=quant).blocks
        block_k = max(_KB_ROUND, _round_up(
            mask_block_k(masks.reshape(g * n_out, n_in), bn=blk.bn),
            _KB_ROUND))
        # nonzero positions ascending per row (the from_mask layout): the
        # k largest-magnitude columns, which `order` already lists
        idx = np.asarray(jnp.sort(order[..., :k], axis=-1)).astype(
            np.int32)                                     # [g, O, K]
        vals = jnp.take_along_axis(wt, jnp.asarray(idx), axis=-1)
        if impl_nm == "pallas" or quant != "none":
            n_enc, perm = n_in, None
            if impl_nm == "pallas" and pack:
                idx, vals, block_k, n_enc, perm, pack_kb = _maybe_pack(
                    idx, vals, masks.reshape(g * n_out, n_in), n_in,
                    blk.bn, block_k)
            tb = encode_tiled(vals.reshape(g * n_out, k),
                              idx.reshape(g * n_out, k), n_enc,
                              bn=blk.bn, kb=block_k)
            nb = tb.nb
            # encoded as one [NB, g*O, KB] stack; lead axes go in front
            def restack(a):
                a = a.reshape(nb, g, n_out, *a.shape[2:])
                return jnp.moveaxis(a, 1, 0).reshape(*lead, nb, n_out,
                                                     *a.shape[3:])
            perm_leaf = None
            if perm is not None:
                packed = True
                # broadcast over lead so per-layer slicing (scan / probes)
                # keeps a well-formed [.., NB*bn] perm per slice
                perm_leaf = jnp.asarray(np.ascontiguousarray(
                    np.broadcast_to(perm, (*lead, perm.shape[0]))) if lead
                    else perm)
            weights = TiledBalanced(
                restack(tb.values), restack(tb.indices), restack(tb.counts),
                n_in=n_in, bn=blk.bn, perm=perm_leaf)
            if quant != "none":
                weights = quantize_tiled(weights, quant)
        else:
            weights = BalancedSparse(vals.reshape(*lead, n_out, k),
                                     jnp.asarray(idx).reshape(
                                         *lead, n_out, k), n_in)
    flow = choose_dataflow(LayerSpec(name=nm, kind="fc", c_i=n_in,
                                     c_o=n_out,
                                     w_sparsity=1.0 - k / n_in))
    experts = int(lead[1]) if len(lead) > 1 else 0
    ev = _evaluate_cost(objective=objective, dep=dep, layer_spec=None,
                        kind="fc", m_hint=m_hint, n_in=n_in, n_out=n_out,
                        k=k if impl_nm != "dense" else n_in,
                        w_format_bits=_format_bits_of(weights,
                                                      elem_bits=elem_bits,
                                                      lead_layers=lead0),
                        quant=quant, elem_bits=elem_bits)
    mode = flow.mode if objective == "latency" else ev["mode"]
    tag = _tag_for(objective=objective, dep=dep, ev=ev, mode=mode,
                   quant=quant, weights=weights, lead_layers=lead0,
                   m_hint=int(m_hint), n_in=n_in, n_out=n_out,
                   itemsize=cd.itemsize)
    spec = PlanSpec(name=nm, kind="fc", impl=impl_nm, mode=mode,
                    n_in=n_in, n_out=n_out, k=k, block_k=block_k,
                    blocks=blk, w_sparsity=1.0 - k / n_in,
                    d_mem_bits=int(flow.d_mem_bits) * g,
                    i_mem_bits=int(flow.i_mem) * g,
                    w_mem_bits=int(flow.w_mem) * g,
                    experts=experts, tuned=tuned, blocks_static=blk_static,
                    m_hint=int(m_hint), decode_m=int(decode_m),
                    blocks_decode=blk_dec, packed=packed, pack_kb=pack_kb,
                    quant=quant, cost=tag)
    return LayerPlan(spec=spec, weights=weights)


def _cost_meta(objective: str, deployment: Any) -> Tuple:
    """Hashable meta entries recording the plan objective.  Empty at the
    default (latency objective, default deployment) so pre-cost plan metas
    stay byte-identical; `ModelPlan.cost_summary` falls back to the
    defaults when the entries are absent."""
    if objective == "latency" and deployment is None:
        return ()
    return (("objective", objective),
            ("deployment", _cost.get_deployment(deployment).name))


def _tune_meta(tune: str, layers: Dict[str, LayerPlan]) -> Tuple:
    """Hashable meta entries recording the tune policy and the per-layer
    tuned-vs-static `BlockChoice` deltas (each spec carries the static
    prior the resolver actually computed, `PlanSpec.blocks_static`)."""
    if tune == "off":
        return ()
    deltas = []
    for nm in sorted(layers):
        s = layers[nm].spec
        if s.blocks is None or s.blocks_static is None \
                or s.tuned == "static":
            continue
        stat = s.blocks_static
        if (s.blocks.bm, s.blocks.bo, s.blocks.bn) != \
                (stat.bm, stat.bo, stat.bn):
            deltas.append((nm, (s.blocks.bm, s.blocks.bo, s.blocks.bn),
                           (stat.bm, stat.bo, stat.bn)))
    return (("tune", tune), ("tune_deltas", tuple(deltas)))


def _resolve_sparsity(cfg, sparsity: float | None) -> float:
    sparsity = cfg.w_sparsity if sparsity is None else sparsity
    if not 0.0 < sparsity < 1.0:
        raise ValueError(f"need 0 < sparsity < 1, got {sparsity}")
    return sparsity


def plan_transformer(cfg, params: dict, *, sparsity: float | None = None,
                     impl: str | None = None, include_mlp: bool = True,
                     include_experts: bool = True,
                     m_hint: int | None = None, decode_m: int | None = None,
                     pack: bool = True, tune: str = "off",
                     tune_cache: str | None = None,
                     quant: str = "none", objective: str = "latency",
                     deployment: Any = None) -> ModelPlan:
    """Offline plan for a transformer's projection matrices.

    Stacked 2-D projections ``[L, n_in, n_out]`` go through `_plan_stacked`;
    for MoE configs the rank-3 expert tensors ``[E, d, f]`` (stacked
    ``[L, E, d, f]``) get a per-expert TiledBalanced/BalancedSparse encoding
    with a shared `BlockChoice`, so the router-selected expert decodes
    inside the kernel path (`engine.execute.apply_expert_fc`).  GEMV-shaped
    serving projections are ON_CHIP under §V-C — every weight is read once —
    so the mode mix here is the paper's FC story; the CNN planners exercise
    RIF/RWF.  ``tune``/``tune_cache`` select the block-choice policy (see
    `build_layer_plan`).
    """
    sparsity = _resolve_sparsity(cfg, sparsity)
    blocks = params["blocks"]
    names = [n for n in ATTN_PROJ_NAMES
             + ((MLP_PROJ_NAMES + MOE_SHARED_NAMES) if include_mlp else ())
             if n in blocks]
    cd = jnp.dtype(cfg.compute_dtype)
    m_hint = m_hint or 256
    decode_m = decode_m or 4
    layers: Dict[str, LayerPlan] = {}
    for nm in names:
        w = blocks[nm]
        if w.ndim != 3:
            continue
        layers[nm] = _plan_stacked(nm, w, sparsity=sparsity, impl=impl,
                                   m_hint=m_hint, cd=cd, tune=tune,
                                   tune_cache=tune_cache, decode_m=decode_m,
                                   pack=pack, quant=quant,
                                   objective=objective, deployment=deployment)
    if include_mlp and include_experts and cfg.family == "moe":
        for nm in MOE_EXPERT_NAMES:
            w = blocks.get(nm)
            if w is None or w.ndim != 4:
                continue
            layers[nm] = _plan_stacked(nm, w, sparsity=sparsity, impl=impl,
                                       m_hint=m_hint, cd=cd, tune=tune,
                                       tune_cache=tune_cache,
                                       decode_m=decode_m, pack=pack,
                                       quant=quant, objective=objective,
                                       deployment=deployment)
    meta = (("model", cfg.name), ("sparsity", float(sparsity)),
            ("n_layers", int(cfg.n_layers)),
            ("quant", quant)) + _cost_meta(objective, deployment) \
        + _tune_meta(tune, layers)
    return ModelPlan(layers=layers, meta=meta)


def plan_rwkv6(cfg, params: dict, *, sparsity: float | None = None,
               impl: str | None = None, m_hint: int | None = None,
               decode_m: int | None = None, pack: bool = True,
               tune: str = "off", tune_cache: str | None = None,
               quant: str = "none", objective: str = "latency",
               deployment: Any = None) -> ModelPlan:
    """Offline plan for the RWKV6 projection family (R/K/V/G/O time-mix
    plus channel-mix matrices).  The WKV recurrence itself is elementwise
    and stays dense — the exact analogue of the paper leaving non-CONV/FC
    ops dense (DESIGN.md §4)."""
    sparsity = _resolve_sparsity(cfg, sparsity)
    blocks = params["blocks"]
    cd = jnp.dtype(cfg.compute_dtype)
    m_hint = m_hint or 256
    decode_m = decode_m or 4
    layers = {nm: _plan_stacked(nm, blocks[nm], sparsity=sparsity, impl=impl,
                                m_hint=m_hint, cd=cd, tune=tune,
                                tune_cache=tune_cache, decode_m=decode_m,
                                pack=pack, quant=quant, objective=objective,
                                deployment=deployment)
              for nm in RWKV6_PROJ_NAMES if nm in blocks}
    meta = (("model", cfg.name), ("sparsity", float(sparsity)),
            ("n_layers", int(cfg.n_layers)),
            ("quant", quant)) + _cost_meta(objective, deployment) \
        + _tune_meta(tune, layers)
    return ModelPlan(layers=layers, meta=meta)


def plan_zamba2(cfg, params: dict, *, sparsity: float | None = None,
                impl: str | None = None, m_hint: int | None = None,
                decode_m: int | None = None, pack: bool = True,
                tune: str = "off", tune_cache: str | None = None,
                quant: str = "none", objective: str = "latency",
                deployment: Any = None) -> ModelPlan:
    """Offline plan for the Zamba2 Mamba-block in/out projections (z/x in,
    out_proj).  The SSD recurrence, depthwise convs and the small B/C/dt
    heads stay dense; the shared attention block is a single (non-stacked)
    weight set and is left to the dense path."""
    sparsity = _resolve_sparsity(cfg, sparsity)
    blocks = params["blocks"]
    cd = jnp.dtype(cfg.compute_dtype)
    m_hint = m_hint or 256
    decode_m = decode_m or 4
    layers = {nm: _plan_stacked(nm, blocks[nm], sparsity=sparsity, impl=impl,
                                m_hint=m_hint, cd=cd, tune=tune,
                                tune_cache=tune_cache, decode_m=decode_m,
                                pack=pack, quant=quant, objective=objective,
                                deployment=deployment)
              for nm in ZAMBA2_PROJ_NAMES if nm in blocks}
    meta = (("model", cfg.name), ("sparsity", float(sparsity)),
            ("n_layers", int(cfg.n_layers)),
            ("quant", quant)) + _cost_meta(objective, deployment) \
        + _tune_meta(tune, layers)
    return ModelPlan(layers=layers, meta=meta)


def plan_model(cfg, params: dict, **kwargs) -> ModelPlan:
    """Family dispatcher: one entry point for every servable architecture.

    Transformer families (dense/moe/audio/vlm) -> `plan_transformer`;
    ssm -> `plan_rwkv6`; hybrid -> `plan_zamba2`.  Keyword arguments are
    forwarded to the family planner unchanged — in particular ``sparsity``,
    ``impl``, ``m_hint``, ``decode_m`` (the decode-step M a second
    decode-shaped BlockChoice is resolved at — pass the serving batch),
    ``pack`` (column-combining packing), ``quant`` (tile-local block
    quantization: "none" | "int8" | "int4"), and the measured-autotuning
    knobs ``tune``
    (``"off" | "cached" | "sweep"``) and ``tune_cache`` (cache file path);
    ``include_mlp``/``include_experts`` apply to transformer families only
    and are dropped for the recurrent planners.

    ``objective`` ("latency" | "dram" | "energy" | "balanced") and
    ``deployment`` (a `launch.cost_model.DeploymentProfile` or its name)
    select the plan objective: non-latency objectives co-optimize dataflow
    mode and impl against the analytical cost model, and every spec carries
    `PlanSpec.cost` provenance (`ModelPlan.cost_summary()` aggregates it).
    """
    from ..models.api import TRANSFORMER_FAMILIES
    if cfg.family in TRANSFORMER_FAMILIES:
        return plan_transformer(cfg, params, **kwargs)
    kwargs.pop("include_mlp", None)
    kwargs.pop("include_experts", None)
    if cfg.family == "ssm":
        return plan_rwkv6(cfg, params, **kwargs)
    if cfg.family == "hybrid":
        return plan_zamba2(cfg, params, **kwargs)
    raise ValueError(f"no planner for family {cfg.family!r}")


def masked_dense_params(params: dict, plan: ModelPlan) -> dict:
    """The masked-dense reference: the same pruned weights as the plan,
    densified back into the params layout ([*lead, n_in, n_out]).
    Sparse-plan serving must match this numerically.  Each leaf takes the
    dtype of the params leaf it replaces; where the caller already dropped
    that leaf, the densified plan's own dtype."""
    blocks = dict(params["blocks"])
    for nm, lp in plan.layers.items():
        dense = lp.dense_weights()                        # [*lead, O, N]
        dt = blocks[nm].dtype if nm in blocks else dense.dtype
        blocks[nm] = jnp.swapaxes(dense, -1, -2).astype(dt)
    out = dict(params)
    out["blocks"] = blocks
    return out


# ---------------------------------------------------------------------------
# Shard-aware plans (encoded leaves get real PartitionSpecs, not replication)
# ---------------------------------------------------------------------------

def _layer_weight_specs(lp: LayerPlan, mesh):
    """A weights-shaped pytree of PartitionSpecs for one LayerPlan.

    Encoded leaves shard like the dense weights they replace: the stacked
    L axis replicated (scan slices it), the expert axis over ``model``
    (expert parallelism is TP over E), and the output-channel axis over the
    FSDP axes (``data``/``pod``) — all divisibility-guarded by
    `distributed.sharding.logical_spec`.  Tiled leaves are block-major
    ([.., NB, O, KB]): the NB axis stays whole, O shards.
    """
    from ..distributed import sharding as shd
    w = lp.weights
    fsdp = [shd.fsdp_axes(mesh)]

    def lead_plan(n_lead: int):
        # first stacked axis is L (replicated); second, when present, is the
        # expert axis (model-parallel)
        plans = [None, ["model"] if lp.spec.experts else None]
        return plans[:n_lead]

    if isinstance(w, TiledBalanced):
        lead = w.values.ndim - 3
        vplan = lead_plan(lead) + [None, fsdp, None]
        perm_spec = None
        if w.perm is not None:
            # every device permutes the full input row: replicated
            perm_spec = shd.logical_spec(
                mesh, w.perm.shape, lead_plan(w.perm.ndim - 1) + [None])
        scales_spec = None
        if w.scales is not None:
            # scales shard exactly like counts ([.., NB, O]): per-block
            # metadata rides with its output-channel shard
            scales_spec = shd.logical_spec(mesh, w.scales.shape,
                                           lead_plan(lead) + [None, fsdp])
        return TiledBalanced(
            shd.logical_spec(mesh, w.values.shape, vplan),
            shd.logical_spec(mesh, w.indices.shape, vplan),
            shd.logical_spec(mesh, w.counts.shape,
                             lead_plan(lead) + [None, fsdp]),
            n_in=w.n_in, bn=w.bn, perm=perm_spec,
            scales=scales_spec, quant=w.quant)
    if isinstance(w, BalancedSparse):
        lead = w.values.ndim - 2
        vplan = lead_plan(lead) + [fsdp, None]
        return BalancedSparse(
            shd.logical_spec(mesh, w.values.shape, vplan),
            shd.logical_spec(mesh, w.indices.shape, vplan), w.n_in)
    if lp.spec.kind == "conv":         # dense conv [Co, Ci, Hk, Wk]
        return shd.logical_spec(mesh, w.shape,
                                [fsdp] + [None] * (w.ndim - 1))
    lead = w.ndim - 2                  # dense fc [*lead, O, N]
    return shd.logical_spec(mesh, w.shape, lead_plan(lead) + [fsdp, None])


def plan_specs(plan: ModelPlan, mesh) -> ModelPlan:
    """PartitionSpec pytree exactly matching ``plan``'s structure.

    Returns a `ModelPlan` whose array leaves are replaced by PartitionSpecs
    (same aux data everywhere, so `jax.tree` maps it against the plan), fit
    for `distributed.sharding.tree_shardings` + `jax.device_put` /
    `with_sharding_constraint`.  This replaces the PR-2 behavior of
    replicating every encoded value onto every device.
    """
    return ModelPlan(
        layers={nm: LayerPlan(spec=lp.spec,
                              weights=_layer_weight_specs(lp, mesh))
                for nm, lp in plan.layers.items()},
        meta=plan.meta)


def shard_plan(plan: ModelPlan, mesh) -> ModelPlan:
    """device_put the plan onto its `plan_specs` shardings (FSDP-style
    distribution of the encoded values/indices/counts over the mesh) and
    record the mesh in every spec: a Mosaic kernel cannot be partitioned
    automatically, so `engine.execute` runs it per shard."""
    from ..distributed import sharding as shd
    placed = jax.device_put(plan, shd.tree_shardings(mesh,
                                                     plan_specs(plan, mesh)))
    return ModelPlan(
        layers={nm: LayerPlan(spec=dataclasses.replace(lp.spec, mesh=mesh),
                              weights=lp.weights)
                for nm, lp in placed.layers.items()},
        meta=placed.meta)


__all__ = ["LayerPlan", "ModelPlan", "PlanSpec", "balanced_mask_k",
           "mask_block_k", "build_layer_plan", "plan_from_balanced",
           "plan_smallcnn", "plan_transformer", "plan_rwkv6", "plan_zamba2",
           "plan_model", "masked_dense_params", "plan_specs", "shard_plan",
           "default_impl", "ATTN_PROJ_NAMES", "MLP_PROJ_NAMES",
           "MOE_SHARED_NAMES", "MOE_EXPERT_NAMES", "RWKV6_PROJ_NAMES",
           "ZAMBA2_PROJ_NAMES"]
