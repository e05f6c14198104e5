"""Fault injectors for the chaos suite (`tests/test_faults.py`).

Each injector produces exactly the damage one guard layer is built to
catch, so the tests exercise detection/degradation paths rather than hope
for organic failures:

* `corrupt_tile_encoding`  — structural plan damage -> `guard.validate_plan`
* `corrupt_scales`         — block-quant scale poison (NaN / zero) ->
  `guard.validate_plan`'s ``scale`` checks and the ``--guard`` NaN
  quarantine (a quant layer demotes to the dense reference)
* `inject_nan_output`      — weight poison -> serve's ``--guard`` NaN
  bisection + quarantine
* `truncate_shard` / `bit_flip_shard` — checkpoint damage vs the CRC
  manifest -> `CheckpointManager.restore_latest` fallback
* `poison_autotune_entry`  — cache damage -> `autotune.resolve_blocks`
  degrading to the static model
* `force_impl_failure`     — dispatch exceptions at a kernel impl site ->
  `guard.harden_plan`'s degradation ladder

Injectors never mutate their inputs in place when the subject is a plan
(plans are frozen pytrees — they return a rebuilt plan); filesystem
injectors damage files in place, as real corruption would.
"""
from __future__ import annotations

import contextlib
import dataclasses
import json
import pathlib
from typing import Callable, Iterator, Tuple

import jax.numpy as jnp
import numpy as np

from ..core.pruning import BalancedSparse
from ..engine.plan import LayerPlan, ModelPlan
from ..kernels import ops as kernel_ops
from ..kernels.tile_format import TiledBalanced

TILE_FAULTS = ("index_oob", "count_overflow", "nan", "imbalance")


def _pick_sparse(plan: ModelPlan, layer: str | None,
                 want=None) -> str:
    names = sorted(nm for nm, lp in plan.layers.items()
                   if lp.spec.is_sparse
                   and (want is None or isinstance(lp.weights, want)))
    if layer is not None:
        if layer not in plan.layers:
            raise KeyError(f"no layer {layer!r} in plan")
        return layer
    if not names:
        raise ValueError("plan has no sparse layer to corrupt")
    return names[len(names) // 2]


def _replace_layer(plan: ModelPlan, name: str, lp: LayerPlan) -> ModelPlan:
    layers = dict(plan.layers)
    layers[name] = lp
    return ModelPlan(layers=layers, meta=plan.meta)


def corrupt_tile_encoding(plan: ModelPlan, layer: str | None = None,
                          kind: str = "index_oob"
                          ) -> Tuple[ModelPlan, str]:
    """Damage one sparse layer's encoding the way a bad checkpoint or a
    buggy encoder would; `guard.validate_plan` must name the layer and the
    broken invariant.  Returns ``(corrupted_plan, layer_name)``.

    Kinds: ``index_oob`` (a column index outside its valid range),
    ``count_overflow`` (a tile count above the KB capacity),
    ``nan`` (a non-finite encoded value),
    ``imbalance`` (unequal per-row NZE totals — tiled encodings only).
    """
    if kind not in TILE_FAULTS:
        raise ValueError(f"kind must be one of {TILE_FAULTS}, got {kind!r}")
    name = _pick_sparse(plan, layer)
    lp = plan.layers[name]
    w = lp.weights
    if isinstance(w, TiledBalanced):
        vals = np.array(w.values, np.float32)
        idx = np.array(w.indices)
        cnt = np.array(w.counts)
        if kind == "index_oob":
            idx.reshape(-1)[0] = w.bn + 3
        elif kind == "count_overflow":
            cnt.reshape(-1)[0] = w.values.shape[-1] + 1
        elif kind == "nan":
            vals.reshape(-1)[0] = np.nan
        else:  # imbalance: give row 0 one fewer NZE than the rest
            row0 = cnt.reshape(-1, *cnt.shape[-2:])[0, :, 0]   # [NB] view
            nz = np.nonzero(row0)[0]
            if not nz.size:
                raise ValueError(f"{name}: row 0 has no NZE to drop")
            row0[nz[0]] -= 1
        new = dataclasses.replace(
            w, values=jnp.asarray(vals).astype(w.values.dtype),
            indices=jnp.asarray(idx), counts=jnp.asarray(cnt))
    elif isinstance(w, BalancedSparse):
        if kind in ("count_overflow", "imbalance"):
            raise ValueError(f"kind {kind!r} needs a tiled encoding; layer "
                             f"{name!r} holds the flat format")
        vals = np.array(w.values, np.float32)
        idx = np.array(w.indices)
        if kind == "index_oob":
            idx.reshape(-1)[0] = w.n_in + 7
        else:
            vals.reshape(-1)[0] = np.inf
        new = BalancedSparse(jnp.asarray(vals).astype(w.values.dtype),
                             jnp.asarray(idx), w.n_in)
    else:
        raise ValueError(f"layer {name!r} holds dense weights — nothing "
                         "encoded to corrupt")
    return _replace_layer(plan, name, LayerPlan(spec=lp.spec, weights=new)), \
        name


SCALE_FAULTS = ("nan", "zero")


def corrupt_scales(plan: ModelPlan, layer: str | None = None,
                   kind: str = "nan") -> Tuple[ModelPlan, str]:
    """Poison one quantized layer's per-block dequant scales.

    ``kind="nan"`` turns a slice of the scales non-finite: every dequant
    through them yields NaN at run time (serve's ``--guard`` must bisect
    and quarantine the layer to the dense reference), and
    `guard.validate_plan` flags the ``scale`` finiteness invariant.
    ``kind="zero"`` zeroes the scales of blocks that still carry live
    quantized values — silently wrong numerics, undetectable by a NaN
    guard, but structurally impossible for the encoder (it never emits a
    nonzero q against a zero scale), so `validate_plan` must flag the
    ``scale`` zero-consistency invariant.  Returns
    ``(corrupted_plan, layer_name)``.
    """
    if kind not in SCALE_FAULTS:
        raise ValueError(f"kind must be one of {SCALE_FAULTS}, got {kind!r}")
    if layer is None:
        names = sorted(nm for nm, lp in plan.layers.items()
                       if isinstance(lp.weights, TiledBalanced)
                       and lp.weights.quant != "none")
        if not names:
            raise ValueError("plan has no quantized layer to corrupt")
        name = names[len(names) // 2]
    else:
        name = _pick_sparse(plan, layer)
    lp = plan.layers[name]
    w = lp.weights
    if not isinstance(w, TiledBalanced) or w.quant == "none" \
            or w.scales is None:
        raise ValueError(f"layer {name!r} carries no block-quant scales")
    s = np.array(w.scales, np.float32)
    flat = s.reshape(-1)
    if kind == "nan":
        flat[:max(1, flat.size // 4)] = np.nan
    else:
        cnt = np.array(w.counts).reshape(-1)
        live = np.nonzero((cnt > 0) & (flat > 0))[0]
        if not live.size:
            raise ValueError(f"layer {name!r} has no live nonzero-scale "
                             "block to zero")
        flat[live[:max(1, live.size // 4)]] = 0.0
    new = dataclasses.replace(w, scales=jnp.asarray(s))
    return _replace_layer(plan, name, LayerPlan(spec=lp.spec, weights=new)), \
        name


def inject_nan_output(plan: ModelPlan, layer: str | None = None
                      ) -> Tuple[ModelPlan, str]:
    """Poison every encoded value of one sparse layer with NaN, so its
    output (and every downstream logit) goes non-finite at run time while
    the encoding stays structurally valid — the fault serve's ``--guard``
    must bisect to and quarantine.  Returns ``(poisoned_plan, name)``."""
    name = _pick_sparse(plan, layer)
    lp = plan.layers[name]
    w = lp.weights
    if isinstance(w, TiledBalanced) and w.quant != "none":
        # quantized values are integers and cannot hold NaN — poison the
        # dequant scales instead (same runtime effect: NaN outputs)
        new: TiledBalanced = dataclasses.replace(
            w, scales=jnp.full_like(w.scales, jnp.nan))
    elif isinstance(w, (TiledBalanced, BalancedSparse)):
        new = dataclasses.replace(w, values=jnp.full_like(w.values,
                                                          jnp.nan))
    else:
        new = jnp.full_like(w, jnp.nan)
    return _replace_layer(plan, name, LayerPlan(spec=lp.spec, weights=new)), \
        name


# ---------------------------------------------------------------------------
# Checkpoint damage
# ---------------------------------------------------------------------------

def _pick_shard(root, step: int | None) -> pathlib.Path:
    from ..checkpoint import store
    root = pathlib.Path(root)
    if step is None:
        step = store.latest_step(root)
        if step is None:
            raise FileNotFoundError(f"no complete checkpoint under {root}")
    d = root / f"step_{step:08d}"
    with open(d / "manifest.json") as f:
        manifest = json.load(f)
    leaves = sorted(manifest["leaves"].items())
    if not leaves:
        raise ValueError(f"{d.name}: manifest lists no leaves")
    return d / leaves[len(leaves) // 2][1]["file"]


def truncate_shard(root, step: int | None = None) -> pathlib.Path:
    """Cut one shard of the (newest by default) checkpoint to half size —
    a crash/partial-copy artifact.  Restore must fail that step and fall
    back.  Returns the damaged path."""
    shard = _pick_shard(root, step)
    size = shard.stat().st_size
    with open(shard, "r+b") as f:
        f.truncate(max(1, size // 2))
    return shard


def bit_flip_shard(root, step: int | None = None) -> pathlib.Path:
    """Flip one payload bit in one shard — silent media corruption the CRC
    manifest exists to catch.  Returns the damaged path."""
    shard = _pick_shard(root, step)
    data = bytearray(shard.read_bytes())
    # flip in the back half: past the .npy header, inside the array payload
    data[len(data) // 2 + len(data) // 4] ^= 0x10
    shard.write_bytes(bytes(data))
    return shard


# ---------------------------------------------------------------------------
# Autotune-cache damage
# ---------------------------------------------------------------------------

def poison_autotune_entry(path, key: str | None = None) -> str:
    """Garble one entry (by default: every entry) of an autotune cache file
    in the way a bad hand-edit would — block fields replaced with garbage
    while the file stays parseable JSON.  `autotune.resolve_blocks` must
    treat the entry as a miss and degrade to the static model.  Returns the
    poisoned key (or ``"*"``)."""
    from ..kernels import autotune
    path = pathlib.Path(path)
    doc = json.loads(path.read_text())
    entries = doc.get("entries", {})
    if key is not None:
        if key not in entries:
            raise KeyError(f"no cache entry {key!r} in {path}")
        targets = [key]
    else:
        targets = list(entries)
    for k in targets:
        entries[k] = dict(entries[k], bm="garbage", bo=-4, bn=None)
    path.write_text(json.dumps(doc))
    autotune._READ_MEMO.pop(str(path), None)
    return key if key is not None else "*"


# ---------------------------------------------------------------------------
# Forced dispatch failure
# ---------------------------------------------------------------------------

@contextlib.contextmanager
def force_impl_failure(*impls: str,
                       when: Callable[[dict], bool] | None = None
                       ) -> Iterator[None]:
    """Arm `kernel_ops` fault sites so the named impls raise
    `ops.InjectedKernelFault` at trace time — the stand-in for a Mosaic
    lowering error or backend compile failure that only real TPU would
    produce.  ``when(ctx)`` narrows the trip (e.g. only ``bm`` above a
    bound, to exercise the halved-blocks retry).  Restores the previous
    arming on exit.

    Sites ``xla_decode`` / ``pallas_decode`` trip only the skinny-M decode
    branches inside their parent impls (the parent site still trips the
    whole impl); batched (fused-expert) dispatches pass ``batched=True``
    in ctx so ``when`` can target them.
    """
    valid = ("pallas", "xla", "xla_gather", "xla_decode", "pallas_decode")
    for impl in impls:
        if impl not in valid:
            raise ValueError(f"no fault site for impl {impl!r} "
                             f"(valid: {valid})")
    pred = when if when is not None else (lambda ctx: True)
    prev = dict(kernel_ops._FORCED_FAULTS)
    kernel_ops._FORCED_FAULTS.update({impl: pred for impl in impls})
    try:
        yield
    finally:
        kernel_ops._FORCED_FAULTS.clear()
        kernel_ops._FORCED_FAULTS.update(prev)


__all__ = ["TILE_FAULTS", "SCALE_FAULTS", "corrupt_tile_encoding",
           "corrupt_scales", "inject_nan_output", "truncate_shard",
           "bit_flip_shard", "poison_autotune_entry", "force_impl_failure"]
