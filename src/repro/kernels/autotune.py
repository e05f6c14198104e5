"""Measured block autotuning: sweep, cache, and reuse `BlockChoice`s.

`ops.choose_blocks` is a closed-form *model* — a VMEM-occupancy prior that
picks (bm, bo, bn) without ever running the kernel.  Sense's §VI argument
(and S2Engine's / Column-Combining's) is that the right configuration is
workload-dependent and must ultimately be *fitted to measurement*.  This
module is that measured layer:

* ``candidate_blocks`` — the static model's pick plus its one-step
  power-of-two neighbors that still fit the (double-buffered) VMEM budget.
  The prior is the candidate generator, never discarded.
* ``sweep_blocks``     — time every candidate with a jitted micro-benchmark
  of the real kernel entry (`ops.tiled_spmm` on synthetic balanced weights
  of the exact (m, o, n, k) shape) and return the argmin.  The static
  choice is always a candidate, so a swept shape can never be slower than
  the model's pick on the sweep machine (modulo timer noise).
* an on-disk JSON **cache** with versioned keys — one entry per
  ``(version, backend, impl, dtype-itemsize, m, o, n, k, vmem_budget)`` —
  so sweeps run once per shape per machine and plan builds stay
  deterministic and fast afterwards.
* ``resolve_blocks``   — the single entry `engine/plan.py` calls:
  ``tune="off"`` returns the static model, ``"cached"`` consults the cache
  and falls back to the static model on a miss (or a foreign-backend
  cache), ``"sweep"`` fills the cache on a miss.

Only ``impl="pallas"`` is tunable: the XLA fallbacks (densify+dot,
gather+einsum) take no block parameters — their `BlockChoice` is
storage-accounting bookkeeping — so for them every tune mode degrades to
the static model (source ``"static"``).  On CPU containers the Pallas
kernel runs in interpret mode; sweep numbers there rank kernel
configurations under the emulator and are cached under the ``cpu`` backend
key, never consulted on TPU (the backend is part of the key).
"""
from __future__ import annotations

import contextlib
import functools
import json
import math
import os
import pathlib
import tempfile
import time
from typing import NamedTuple

try:
    import fcntl
except ImportError:  # pragma: no cover - non-POSIX fallback
    fcntl = None

import jax
import jax.numpy as jnp
import numpy as np

from . import ops
from .balanced_spmm import interpret_mode
from .tile_format import QUANT_MODES, encode_tiled, max_block_count, \
    quantize_tiled

CACHE_VERSION = 1

# impls whose execution actually consumes (bm, bo, bn); everything else
# gets the static model regardless of tune mode
TUNABLE_IMPLS = ("pallas",)

_ITEMSIZE_DTYPE = {2: jnp.bfloat16, 4: jnp.float32}


def default_cache_path() -> str:
    """``REPRO_AUTOTUNE_CACHE`` or ``~/.cache/repro/autotune.json``."""
    env = os.environ.get("REPRO_AUTOTUNE_CACHE")
    if env:
        return env
    return str(pathlib.Path.home() / ".cache" / "repro" / "autotune.json")


def cache_key(m: int, o: int, n: int, k: int, *, itemsize: int = 4,
              impl: str = "pallas", backend: str | None = None,
              vmem_budget: int = ops._VMEM_BUDGET,
              dtype=None, quant: str = "none") -> str:
    """Versioned cache key.  ``backend`` defaults to the live JAX backend —
    entries swept on one backend are invisible on another (a TPU never
    trusts CPU-interpret timings and vice versa).  ``m`` is bucketed to
    the next power of two (`ops.bucket_m`): the serving runtime's live M
    spread (batch buckets x chunk widths) must share entries per bucket,
    not fragment the cache per exact M.

    The key names the weight *dtype*, not just its itemsize: two dtypes
    can share an itemsize (bf16/f16) yet time differently, and an itemsize
    alone let a bf16 sweep collide with the f32 entry for the same
    (m, o, n, k) and silently serve the wrong blocks.  ``quant`` adds a
    ``|q<mode>`` segment for block-quantized encodings (narrower weight
    slots change the VMEM frontier, so int8/int4 sweeps must never share
    entries with full-precision ones)."""
    backend = backend or jax.default_backend()
    m = ops.bucket_m(m)
    dt = jnp.dtype(dtype if dtype is not None
                   else _ITEMSIZE_DTYPE.get(itemsize, jnp.float32)).name
    q = f"|q{quant}" if quant != "none" else ""
    return (f"v{CACHE_VERSION}|{backend}|{impl}|is{itemsize}|dt{dt}"
            f"|m{m}|o{o}|n{n}|k{k}|vmem{vmem_budget}{q}")


# ---------------------------------------------------------------------------
# On-disk cache (atomic writes, best-effort reads)
# ---------------------------------------------------------------------------

_READ_MEMO: dict = {}   # path -> ((mtime_ns, size), entries) parse memo


def load_cache(path: str | os.PathLike | None = None) -> dict:
    """Entry dict from ``path``; {} on missing/corrupt/version-mismatched
    files (a stale cache must degrade to the static model, never crash a
    plan build).  Parses are memoized on the file's (mtime, size), so a
    plan build resolving many layers against one unchanged cache reads the
    file once; callers get a fresh shallow copy each call."""
    path = pathlib.Path(path or default_cache_path())
    try:
        st = path.stat()
    except OSError:
        return {}
    sig = (st.st_mtime_ns, st.st_size)
    memo = _READ_MEMO.get(str(path))
    if memo is not None and memo[0] == sig:
        return dict(memo[1])
    try:
        doc = json.loads(path.read_text())
    except (OSError, ValueError):
        doc = None
    entries = {}
    if isinstance(doc, dict) and doc.get("version") == CACHE_VERSION \
            and isinstance(doc.get("entries"), dict):
        entries = doc["entries"]
    _READ_MEMO[str(path)] = (sig, entries)
    return dict(entries)


def save_cache(entries: dict, path: str | os.PathLike | None = None) -> str:
    """Atomically persist ``entries`` (tmp file + rename, so a concurrent
    reader never sees a torn write).  Returns the path written."""
    path = pathlib.Path(path or default_cache_path())
    path.parent.mkdir(parents=True, exist_ok=True)
    doc = {"version": CACHE_VERSION, "entries": entries}
    fd, tmp = tempfile.mkstemp(dir=str(path.parent), suffix=".tmp")
    try:
        with os.fdopen(fd, "w") as f:
            json.dump(doc, f, indent=1, sort_keys=True)
            f.write("\n")
        os.replace(tmp, path)
    finally:
        if os.path.exists(tmp):
            os.unlink(tmp)
    _READ_MEMO.pop(str(path), None)
    return str(path)


@contextlib.contextmanager
def _cache_lock(path: pathlib.Path):
    """Advisory exclusive lock on ``<path>.lock`` (flock).  Serializes the
    read-merge-write cycle in `update_cache` across processes; degrades to
    unlocked on platforms without fcntl (the atomic rename still prevents
    torn files, only last-writer-wins entry loss)."""
    if fcntl is None:  # pragma: no cover
        yield
        return
    path.parent.mkdir(parents=True, exist_ok=True)
    lock = path.with_suffix(path.suffix + ".lock")
    with open(lock, "w") as f:
        fcntl.flock(f, fcntl.LOCK_EX)
        try:
            yield
        finally:
            fcntl.flock(f, fcntl.LOCK_UN)


def update_cache(updates: dict,
                 path: str | os.PathLike | None = None) -> dict:
    """Merge ``updates`` into the on-disk cache under an exclusive lock.

    The unsafe pattern — load, mutate in memory, `save_cache` — lets two
    concurrent sweeps drop each other's entries (both read the same base,
    last rename wins).  This re-reads the file *inside* the lock, merges,
    and writes atomically, so concurrent writers union their entries.
    Returns the merged entry dict.
    """
    path = pathlib.Path(path or default_cache_path())
    with _cache_lock(path):
        entries = load_cache(path)
        entries.update(updates)
        save_cache(entries, path)
    return entries


# ---------------------------------------------------------------------------
# Candidate generation (the static model as prior)
# ---------------------------------------------------------------------------

def candidate_blocks(m: int, o: int, n: int, k: int, *, itemsize: int = 4,
                     vmem_budget: int = ops._VMEM_BUDGET,
                     max_candidates: int = 8, quant: str = "none") -> list:
    """The static `choose_blocks` pick (always first) plus its one-step
    power-of-two neighbors per dimension, filtered to the double-buffered
    VMEM budget and to sizes that do not exceed the padded problem dims.

    Decode-shaped problems (m <= `ops.SKINNY_M`) additionally grow
    ``bo``-heavy candidates (bo x2, x4): the skinny kernel has no M grid
    axis, its whole activation block stays resident, so the freed VMEM is
    best spent widening the output tile.  `cache_key` includes m, so
    decode shapes sweep and cache separately from prefill shapes — a plan
    resolving both gets an entry for each.  ``m`` is bucketed to its
    power-of-two bucket first, matching `cache_key`.
    """
    m = ops.bucket_m(m)
    wb = ops.QUANT_WBYTES[quant]
    static = ops.choose_blocks(m, o, n, k, itemsize=itemsize,
                               vmem_budget=vmem_budget, w_bytes=wb)
    caps = {"bm": max(8, ops._round_up(m, 8)),
            "bo": max(8, ops._round_up(o, 8)),
            "bn": max(8, ops._round_up(n, 8))}
    out: list = []
    seen: set = set()

    def add(bm, bo, bn, *, force=False):
        key = (bm, bo, bn)
        if key in seen or len(out) >= max_candidates:
            return
        fp = ops._tiled_footprint(bm, bo, bn, ops._tiled_kb_est(n, k, bn),
                                  itemsize, w_bytes=wb)
        if not force and 2 * fp > vmem_budget:
            return
        seen.add(key)
        out.append(ops.BlockChoice(bm=bm, bo=bo, bn=bn, vmem_bytes=fp))

    # the prior is always candidate 0, budget notwithstanding (it may sit
    # at the 8-floor overshoot the model accepts)
    add(static.bm, static.bo, static.bn, force=True)
    base = {"bm": static.bm, "bo": static.bo, "bn": static.bn}
    for dim in ("bm", "bo", "bn"):
        for cand in (base[dim] * 2, base[dim] // 2):
            if not 8 <= cand <= min(256, caps[dim]):
                continue
            trial = dict(base)
            trial[dim] = cand
            add(trial["bm"], trial["bo"], trial["bn"])
    if m <= ops.SKINNY_M:
        for cand in (base["bo"] * 2, base["bo"] * 4):
            if 8 <= cand <= min(256, caps["bo"]):
                add(base["bm"], cand, base["bn"])
    return out


# ---------------------------------------------------------------------------
# Sweep harness
# ---------------------------------------------------------------------------

def _bench_problem(m: int, o: int, n: int, k: int, dtype):
    """Deterministic synthetic balanced-sparse problem of the exact shape:
    x [m, n], values [o, k], sorted per-row indices [o, k] (k distinct
    columns per output row — the balance invariant)."""
    rng = np.random.default_rng([m, o, n, k])
    x = jnp.asarray(rng.standard_normal((m, n), np.float32), dtype)
    vals = jnp.asarray(rng.standard_normal((o, k), np.float32), dtype)
    idx = np.sort(np.argsort(rng.random((o, n)), axis=1)[:, :k],
                  axis=1).astype(np.int32)
    return x, vals, idx


def bench_time(fn, *args, iters: int, warmup: int = 1) -> float:
    """Best-of-``iters`` seconds per call: ``warmup`` untimed calls
    (compile), then ``iters`` independently timed calls, returning the
    minimum.  The min strips additive scheduler noise — on a shared host
    the mean swings 2-3x between runs while the min is reproducible, and
    the committed BENCH ratios are only a meaningful regression floor if
    rebuilt from the noise-free estimate.  Shared by the sweep and the
    `benchmarks/` harnesses so the timing discipline stays one
    implementation."""
    for _ in range(warmup):
        jax.block_until_ready(fn(*args))
    best = math.inf
    for _ in range(iters):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        best = min(best, time.perf_counter() - t0)
    return best


def sweep_blocks(m: int, o: int, n: int, k: int, *, itemsize: int = 4,
                 impl: str = "pallas", iters: int = 2, warmup: int = 1,
                 vmem_budget: int = ops._VMEM_BUDGET,
                 dtype=None, quant: str = "none") -> tuple:
    """Time every candidate `BlockChoice` on the real kernel entry and
    return ``(winner, record)``.

    Each candidate re-encodes the synthetic weights at its own ``bn`` (the
    tile-local format bakes the column-block width in) and times a jitted
    `ops.tiled_spmm` — the exact function `engine/execute.apply_fc`
    dispatches for planned pallas layers.  ``record`` carries every
    candidate's time plus the static pick's, ready to persist as a cache
    entry.  Non-tunable impls return the static model untimed.  ``m`` is
    bucketed first, so the synthetic problem is the exact shape the cache
    entry's key names.
    """
    m = ops.bucket_m(m)
    static = ops.choose_blocks(m, o, n, k, itemsize=itemsize,
                               vmem_budget=vmem_budget,
                               w_bytes=ops.QUANT_WBYTES[quant])
    dtype = dtype if dtype is not None \
        else _ITEMSIZE_DTYPE.get(itemsize, jnp.float32)
    base = {"backend": jax.default_backend(), "impl": impl,
            "m": m, "o": o, "n": n, "k": k, "itemsize": itemsize,
            "dtype": jnp.dtype(dtype).name, "quant": quant,
            "jax": jax.__version__, "interpret": interpret_mode()}
    if impl not in TUNABLE_IMPLS:
        record = dict(base, source="static",
                      note=f"impl={impl} takes no block parameters",
                      **_choice_fields(static), time_s=None,
                      static_time_s=None, candidates=[])
        return static, record

    x, vals, idx = _bench_problem(m, o, n, k, dtype)
    timed = []
    quarantined = []
    for cand in candidate_blocks(m, o, n, k, itemsize=itemsize,
                                 vmem_budget=vmem_budget, quant=quant):
        try:
            kb = max_block_count(idx, n, cand.bn)
            tb = encode_tiled(vals, idx, n, bn=cand.bn, kb=kb)
            if quant != "none":
                tb = quantize_tiled(tb, quant)
            fn = jax.jit(functools.partial(ops.tiled_spmm, tb=tb,
                                           block_m=cand.bm, block_o=cand.bo))
            t = bench_time(fn, x, iters=iters, warmup=warmup)
        except Exception as e:  # noqa: BLE001 — one bad candidate must not
            # abort the sweep: quarantine it (recorded, never the winner)
            quarantined.append(dict(_choice_fields(cand),
                                    error=f"{type(e).__name__}: {e}"))
            continue
        timed.append((t, cand))
    if not timed:
        # every candidate failed — fall back to the untimed static model
        # and do NOT mark the record a sweep (it must not be cached as one)
        record = dict(base, source="static",
                      note="all sweep candidates failed",
                      **_choice_fields(static), time_s=None,
                      static_time_s=None, candidates=[],
                      quarantined=quarantined)
        return static, record
    static_t = next((t for t, c in timed
                     if (c.bm, c.bo, c.bn) == (static.bm, static.bo,
                                               static.bn)), None)
    best_t, best = min(timed, key=lambda tc: tc[0])
    record = dict(base, source="sweep", **_choice_fields(best),
                  time_s=best_t, static_time_s=static_t,
                  candidates=[dict(_choice_fields(c), time_s=t)
                              for t, c in timed],
                  quarantined=quarantined)
    return best, record


def _choice_fields(c: ops.BlockChoice) -> dict:
    return {"bm": c.bm, "bo": c.bo, "bn": c.bn, "vmem_bytes": c.vmem_bytes}


def _valid_entry(e) -> bool:
    """A trustworthy swept entry: the cache file is hand-shippable, so
    entry-level damage (wrong type, missing/garbage/non-positive block
    fields) must read as a cache miss, never crash a plan build or reach
    the kernel."""
    try:
        return (isinstance(e, dict) and e.get("source") == "sweep"
                and all(int(e[f]) > 0 for f in ("bm", "bo", "bn"))
                and int(e.get("vmem_bytes", 0)) >= 0)
    except (KeyError, TypeError, ValueError):
        return False


def _choice_from_entry(e: dict) -> ops.BlockChoice:
    return ops.BlockChoice(bm=int(e["bm"]), bo=int(e["bo"]), bn=int(e["bn"]),
                           vmem_bytes=int(e.get("vmem_bytes", 0)))


# ---------------------------------------------------------------------------
# The plan-build entry point
# ---------------------------------------------------------------------------

class Resolved(NamedTuple):
    """`resolve_blocks` result: the choice to use, where it came from
    (``static`` | ``cached`` | ``swept``), and the static prior for
    delta reporting."""
    blocks: ops.BlockChoice
    source: str
    static: ops.BlockChoice


def resolve_blocks(m: int, o: int, n: int, k: int, *, itemsize: int = 4,
                   impl: str = "pallas", tune: str = "off",
                   cache_path: str | None = None,
                   vmem_budget: int = ops._VMEM_BUDGET,
                   iters: int = 2, warmup: int = 1,
                   dtype=None, quant: str = "none") -> Resolved:
    """Resolve a `BlockChoice` for one GEMM key under a tune policy.

    ``tune="off"``    — the static `ops.choose_blocks` model, untimed.
    ``tune="cached"`` — a warm cache entry for this exact (backend, impl,
                        itemsize, m, o, n, k, budget) key wins; any miss
                        (cold cache, foreign backend, version bump) falls
                        back to the static model.  Never times anything, so
                        plan builds stay deterministic and fast.
    ``tune="sweep"``  — like "cached", but a miss runs `sweep_blocks` and
                        persists the winner before returning it.

    Non-tunable impls (everything but "pallas") always resolve static.
    ``m`` is bucketed to its power-of-two bucket (`ops.bucket_m`) before
    anything else — the static model, the cache key, and the sweep all see
    the bucketed M, so two live shapes in one bucket resolve identically.
    """
    if tune not in ("off", "cached", "sweep"):
        raise ValueError(f"tune must be off|cached|sweep, got {tune!r}")
    if quant not in QUANT_MODES:
        raise ValueError(f"quant must be one of {QUANT_MODES}, got {quant!r}")
    m = ops.bucket_m(m)
    static = ops.choose_blocks(m, o, n, k, itemsize=itemsize,
                               vmem_budget=vmem_budget,
                               w_bytes=ops.QUANT_WBYTES[quant])
    if tune == "off" or impl not in TUNABLE_IMPLS:
        return Resolved(static, "static", static)
    path = cache_path or default_cache_path()
    key = cache_key(m, o, n, k, itemsize=itemsize, impl=impl,
                    vmem_budget=vmem_budget, dtype=dtype, quant=quant)
    entries = load_cache(path)
    hit = entries.get(key)
    if _valid_entry(hit):
        return Resolved(_choice_from_entry(hit), "cached", static)
    if tune == "cached":
        return Resolved(static, "static", static)
    best, record = sweep_blocks(m, o, n, k, itemsize=itemsize, impl=impl,
                                iters=iters, warmup=warmup,
                                vmem_budget=vmem_budget,
                                dtype=dtype, quant=quant)
    if record.get("source") == "sweep":
        # locked read-merge-write: concurrent sweeps union their entries
        update_cache({key: record}, path)
        return Resolved(best, "swept", static)
    return Resolved(static, "static", static)


def main(argv=None):  # pragma: no cover - thin CLI
    """``python -m repro.kernels.autotune --m 256 --o 512 --n 512 --k 256``
    sweeps one shape into the cache (the TPU workflow: run once per
    machine, ship the cache next to the checkpoint)."""
    import argparse
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--m", type=int, required=True)
    ap.add_argument("--o", type=int, required=True)
    ap.add_argument("--n", type=int, required=True)
    ap.add_argument("--k", type=int, required=True)
    ap.add_argument("--itemsize", type=int, default=4, choices=(2, 4))
    ap.add_argument("--quant", default="none", choices=QUANT_MODES)
    ap.add_argument("--cache", default=None)
    args = ap.parse_args(argv)
    res = resolve_blocks(args.m, args.o, args.n, args.k,
                         itemsize=args.itemsize, impl="pallas", tune="sweep",
                         cache_path=args.cache, quant=args.quant)
    print(f"{res.source}: bm={res.blocks.bm} bo={res.blocks.bo} "
          f"bn={res.blocks.bn} (static bm={res.static.bm} "
          f"bo={res.static.bo} bn={res.static.bn}) -> "
          f"{args.cache or default_cache_path()}")
    return 0


bucket_m = ops.bucket_m          # re-export: callers keying sweeps by hand

__all__ = ["CACHE_VERSION", "TUNABLE_IMPLS", "Resolved", "bench_time",
           "bucket_m", "cache_key", "candidate_blocks", "default_cache_path",
           "load_cache", "resolve_blocks", "save_cache", "sweep_blocks",
           "update_cache"]


if __name__ == "__main__":  # pragma: no cover
    import sys
    sys.exit(main())
