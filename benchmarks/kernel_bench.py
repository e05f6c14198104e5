"""Kernel benchmark harness: seed gather-einsum vs tile-local
decode-and-matmul `balanced_spmm`, on paper-network-shaped GEMMs.

Each CONV layer of AlexNet / VGG-16 / ResNet-50 becomes one balanced-sparse
GEMM: ``M = Ho*Wo`` output positions (capped per mode), ``N = Ci*Hk*Wk``
patch features, ``O = Co`` kernels, ``K = N/2`` nonzeros per row (the
paper's 50% CONV pruning).  For every shape we time:

* ``seed_gather``         — the seed kernel's math (gather + rank-3 einsum,
                            [M, O, K] buffer), jitted XLA.  The baseline
                            this repo's perf trajectory starts from.
* ``tiled_xla``           — the new path's XLA fallback (densify + rank-2
                            dot), jitted.
* ``seed_pallas_interp``  — the seed Pallas kernel (gather buffer +
                            fori_loop einsum) in interpret mode, reduced
                            shapes only (interpret is an emulator; numbers
                            are for kernel-vs-kernel trends, not absolutes).
* ``tiled_pallas_interp`` — the new grid-(M, O, N/bn) decode-and-matmul
                            kernel, interpret mode, same reduced shapes,
                            plus a numerical parity check vs the dense
                            reference (must stay exact-ish: rtol 1e-5 f32).

A ``decode`` section adds skinny-M rows (m <= `ops.SKINNY_M`, the serving
decode step's GEMM shape) timing the routed decode path against the
scatter-densify+dot baseline the fallback used to pay per token, plus
column-combining packing density (KB before/after `pack_columns`, per-block
occupancy) for each pattern.

A ``quant`` section times the block-quantized tile formats (int8 / int4
per-block absmax scales, DESIGN.md §13) against the same shape's f32 tiled
path, checks each quant row's parity against the f32 *dequant reference*
(``x @ dequantize(W).T`` — identical reconstructed values, so rtol 1e-5
like every other parity gate here), and reports the storage shrink.  One
reduced-shape interpret-mode row additionally runs the in-VMEM dequant
Pallas kernel itself.

Writes ``BENCH_kernels.json`` at the repo root so later PRs have a measured
trajectory to beat.  ``--smoke`` runs a <60 s subset for CI regression
gating.

    PYTHONPATH=src python benchmarks/kernel_bench.py [--smoke] [--out PATH]
"""
from __future__ import annotations

import argparse
import functools
import json
import pathlib
import sys
import time
import zlib

import numpy as np

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent.parent / "src"))

import jax                                                    # noqa: E402
import jax.numpy as jnp                                       # noqa: E402
from jax.experimental import pallas as pl                     # noqa: E402

from repro.core.pruning import to_balanced_sparse             # noqa: E402
from repro.kernels import ops, ref                            # noqa: E402
from repro.kernels.autotune import bench_time as timeit       # noqa: E402
from repro.kernels.tile_format import (encode_tiled,          # noqa: E402
                                       invert_perm, max_block_count,
                                       pack_columns, quantize_tiled,
                                       tiled_storage_bits, tiled_to_dense)
from repro.models.cnn import (alexnet_layers, resnet50_layers,  # noqa: E402
                              vgg16_layers)

REPO_ROOT = pathlib.Path(__file__).resolve().parent.parent


# ---------------------------------------------------------------------------
# Seed Pallas kernel (frozen copy of the pre-tiled implementation) — kept
# here, not in src/, purely as the interpret-mode baseline for this bench.
# ---------------------------------------------------------------------------

def _seed_kernel(x_ref, v_ref, i_ref, o_ref, *, bk: int):
    x = x_ref[...]
    vals = v_ref[...]
    idx = i_ref[...]
    bm, bo, k = x.shape[0], vals.shape[0], vals.shape[1]

    def body(step, acc):
        idx_c = jax.lax.dynamic_slice_in_dim(idx, step * bk, bk, axis=1)
        val_c = jax.lax.dynamic_slice_in_dim(vals, step * bk, bk, axis=1)
        xg = jnp.take(x, idx_c, axis=1)              # [bm, bo, bk] gather
        return acc + jnp.einsum("mok,ok->mo", xg, val_c,
                                preferred_element_type=jnp.float32)

    acc = jax.lax.fori_loop(0, k // bk, body,
                            jnp.zeros((bm, bo), jnp.float32))
    o_ref[...] = acc.astype(o_ref.dtype)


def seed_balanced_spmm_pallas(x, values, indices, *, bm=128, bo=128, bk=128):
    def rup(v, m):
        return -(-v // m) * m
    m, _ = x.shape
    o, k = values.shape
    bm, bo, bk = min(bm, rup(m, 8)), min(bo, rup(o, 8)), min(bk, rup(k, 8))
    mp, op_, kp = rup(m, bm), rup(o, bo), rup(k, bk)
    xp = jnp.pad(x, ((0, mp - m), (0, 0)))
    vp = jnp.pad(values, ((0, op_ - o), (0, kp - k)))
    ip = jnp.pad(indices, ((0, op_ - o), (0, kp - k)))
    y = pl.pallas_call(
        functools.partial(_seed_kernel, bk=bk),
        grid=(mp // bm, op_ // bo),
        in_specs=[
            pl.BlockSpec((bm, x.shape[1]), lambda i, j: (i, 0)),
            pl.BlockSpec((bo, kp), lambda i, j: (j, 0)),
            pl.BlockSpec((bo, kp), lambda i, j: (j, 0)),
        ],
        out_specs=pl.BlockSpec((bm, bo), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((mp, op_), x.dtype),
        interpret=True,
    )(xp, vp, ip)
    return y[:m, :o]


# ---------------------------------------------------------------------------
# Shapes and timing
# ---------------------------------------------------------------------------

def conv_gemm_shapes(layers, *, m_cap: int, max_layers: int):
    """Distinct (name, m, n, o) GEMM shapes from a LayerSpec list."""
    seen, out = set(), []
    for l in layers:
        if l.kind != "conv":
            continue
        n = l.c_i * l.h_k * l.w_k
        ho = (l.h_i + 2 * l.padding - l.h_k) // l.stride + 1
        m = min(ho * ho, m_cap)
        key = (n, l.c_o)
        if key in seen or n < 32:
            continue
        seen.add(key)
        out.append((l.name, m, n, l.c_o))
        if len(out) >= max_layers:
            break
    return out


def bench_network(net: str, layers, *, m_cap, max_layers, iters,
                  pallas_m, pallas_budget) -> dict:
    rows = []
    pallas_done = 0
    for name, m, n, o in conv_gemm_shapes(layers, m_cap=m_cap,
                                          max_layers=max_layers):
        k = max(8, n // 2)                     # 50% balanced CONV pruning
        # stable across processes (hash() is salted -> irreproducible data)
        key = zlib.crc32(f"{net}/{name}".encode()) % (1 << 31)
        x = jax.random.normal(jax.random.key(key), (m, n), jnp.float32)
        w = jax.random.normal(jax.random.key(key + 1), (o, n), jnp.float32)
        sp = to_balanced_sparse(w, k=k)

        f_seed = jax.jit(lambda a, v, i: ops.balanced_spmm(
            a, v, i, n_in=n, impl="xla_gather"))
        f_tiled = jax.jit(lambda a, v, i: ops.balanced_spmm(
            a, v, i, n_in=n, impl=_compiled_impl()))
        t_seed = timeit(f_seed, x, sp.values, sp.indices, iters=iters)
        t_tiled = timeit(f_tiled, x, sp.values, sp.indices, iters=iters)

        row = {
            "layer": name, "m": m, "n": n, "o": o, "k": k,
            "times_s": {"seed_gather": t_seed, "tiled_xla": t_tiled},
            "speedup_tiled_vs_seed": t_seed / max(t_tiled, 1e-12),
        }

        # interpret-mode kernel-vs-kernel on a reduced copy of the shape
        if pallas_done < pallas_budget:
            ms = min(m, pallas_m)
            xs = x[:ms]
            f_sp = lambda a, v, i: seed_balanced_spmm_pallas(a, v, i)
            f_tp = lambda a, v, i: ops.balanced_spmm(a, v, i, n_in=n,
                                                     impl="pallas")
            t_sp = timeit(f_sp, xs, sp.values, sp.indices, iters=1, warmup=1)
            t_tp = timeit(f_tp, xs, sp.values, sp.indices, iters=1, warmup=1)
            got = np.asarray(f_tp(xs, sp.values, sp.indices))
            want = np.asarray(ref.balanced_spmm_ref(xs, sp.values,
                                                    sp.indices))
            err = float(np.max(np.abs(got - want))
                        / max(np.max(np.abs(want)), 1e-9))
            row["times_s"]["seed_pallas_interp"] = t_sp
            row["times_s"]["tiled_pallas_interp"] = t_tp
            row["pallas_m"] = ms
            row["pallas_rel_err"] = err
            row["pallas_ok"] = bool(err < 1e-5)
            pallas_done += 1
        rows.append(row)
        print(f"  {net:9s} {name:10s} M={m:5d} N={n:5d} O={o:4d} "
              f"seed={t_seed * 1e3:8.2f}ms tiled={t_tiled * 1e3:8.2f}ms "
              f"x{row['speedup_tiled_vs_seed']:5.1f}"
              + (f"  [interp err {row['pallas_rel_err']:.1e}]"
                 if "pallas_rel_err" in row else ""))
    sp_ups = [r["speedup_tiled_vs_seed"] for r in rows]
    return {
        "layers": rows,
        "geomean_speedup_tiled_vs_seed":
            float(np.exp(np.mean(np.log(sp_ups)))) if sp_ups else None,
        "all_layers_faster": bool(all(s > 1.0 for s in sp_ups)),
        "pallas_all_ok": bool(all(r.get("pallas_ok", True) for r in rows)),
    }


# ---------------------------------------------------------------------------
# Decode-shaped rows (skinny M): the serving decode step's GEMM shape
# ---------------------------------------------------------------------------

# (m, n, o) — m is a decode batch (<= ops.SKINNY_M), n/o are hidden dims;
# k = n // 2 (50% balanced pruning) as everywhere else in this bench.
DECODE_SHAPES = {"smoke": [(4, 512, 512)],
                 "full": [(1, 1024, 1024), (4, 1024, 1024), (8, 2048, 2048)]}


def bench_decode(shapes, *, iters) -> dict:
    """Skinny-M rows: the per-token decode GEMM the serving loop actually
    runs.  Columns:

    * ``xla_scatter_dot`` — densify (scatter) + dot, jitted: what the XLA
      fallback used to pay *every decode step* before skinny routing.
    * ``seed_gather``     — the seed gather+einsum (``impl="xla_gather"``).
    * ``tiled_decode``    — the routed decode path (`ops.balanced_spmm`
      with the skinny branch engaged; Mosaic-compiled tiled kernel on TPU,
      the gather formulation on CPU).

    Also reports what column-combining (`tile_format.pack_columns`) buys
    each pattern at the static model's bn: KB before/after packing and the
    per-block occupancy ``(k / NB) / KB`` (1.0 == every padded slot full).
    """
    rows = []
    for m, n, o in shapes:
        k = max(8, n // 2)
        key = zlib.crc32(f"decode/{m}x{n}x{o}".encode()) % (1 << 31)
        x = jax.random.normal(jax.random.key(key), (m, n), jnp.float32)
        w = jax.random.normal(jax.random.key(key + 1), (o, n), jnp.float32)
        sp = to_balanced_sparse(w, k=k)

        f_scat = jax.jit(lambda a, v, i, n=n: jnp.dot(
            a, ref.balanced_dense(v, i, n).T))
        f_seed = jax.jit(lambda a, v, i, n=n: ops.balanced_spmm(
            a, v, i, n_in=n, impl="xla_gather"))
        f_dec = jax.jit(lambda a, v, i, n=n: ops.balanced_spmm(
            a, v, i, n_in=n, impl=_compiled_impl()))
        t_scat = timeit(f_scat, x, sp.values, sp.indices, iters=iters)
        t_seed = timeit(f_seed, x, sp.values, sp.indices, iters=iters)
        t_dec = timeit(f_dec, x, sp.values, sp.indices, iters=iters)
        got = np.asarray(f_dec(x, sp.values, sp.indices))
        want = np.asarray(ref.balanced_spmm_ref(x, sp.values, sp.indices))
        err = float(np.max(np.abs(got - want))
                    / max(np.max(np.abs(want)), 1e-9))

        blk = ops.choose_blocks(m, o, n, k)
        idx = np.asarray(sp.indices)
        mask = np.zeros((o, n), bool)
        np.put_along_axis(mask, idx, True, axis=1)
        perm = pack_columns(mask, blk.bn)
        npad = perm.shape[0]
        nb = npad // blk.bn
        kb_un = max_block_count(idx, n, blk.bn)
        pidx = np.sort(invert_perm(perm)[idx], axis=1)
        kb_pk = max_block_count(pidx, npad, blk.bn)
        row = {
            "m": m, "n": n, "o": o, "k": k,
            "times_s": {"xla_scatter_dot": t_scat, "seed_gather": t_seed,
                        "tiled_decode": t_dec},
            "speedup_decode_vs_scatter_dot": t_scat / max(t_dec, 1e-12),
            "rel_err": err, "parity_ok": bool(err < 1e-5),
            "pack": {"bn": blk.bn, "nb": nb,
                     "kb_unpacked": kb_un, "kb_packed": kb_pk,
                     "occupancy_unpacked": (k / nb) / kb_un,
                     "occupancy_packed": (k / nb) / kb_pk},
        }
        rows.append(row)
        print(f"  decode    M={m:5d} N={n:5d} O={o:4d} "
              f"scatter={t_scat * 1e3:8.2f}ms decode={t_dec * 1e3:8.2f}ms "
              f"x{row['speedup_decode_vs_scatter_dot']:5.1f}  "
              f"[KB {kb_un}->{kb_pk}]")
    ups = [r["speedup_decode_vs_scatter_dot"] for r in rows]
    return {
        "rows": rows,
        "geomean_speedup_decode_vs_scatter_dot":
            float(np.exp(np.mean(np.log(ups)))) if ups else None,
        "all_rows_faster": bool(all(s > 1.0 for s in ups)),
        "parity_all_ok": bool(all(r["parity_ok"] for r in rows)),
    }


# ---------------------------------------------------------------------------
# Quantized tile rows: int8/int4 block quant vs the f32 tiled path
# ---------------------------------------------------------------------------

# (m, n, o) — prefill-shaped plus one decode-shaped row; k = n // 2.
QUANT_SHAPES = {"smoke": [(32, 512, 512)],
                "full": [(128, 1024, 1024), (4, 1024, 1024)]}


def bench_quant(shapes, *, iters, interp_m) -> dict:
    """Block-quantized tiles (`tile_format.quantize_tiled`) through the
    same `ops.tiled_spmm` entry as the f32 rows.  Parity is gated against
    the dequant reference (the values the kernel reconstructs in VMEM),
    not the pre-quant f32 weights — quantization error is the format's
    contract (<= scale/2 per element), not a kernel defect.  Speedup and
    the storage ratio are reported against the f32 tiled row."""
    impl = _compiled_impl()
    rows = []
    for si, (m, n, o) in enumerate(shapes):
        k = max(8, n // 2)
        key = zlib.crc32(f"quant/{m}x{n}x{o}".encode()) % (1 << 31)
        x = jax.random.normal(jax.random.key(key), (m, n), jnp.float32)
        w = jax.random.normal(jax.random.key(key + 1), (o, n), jnp.float32)
        sp = to_balanced_sparse(w, k=k)
        blk = ops.choose_blocks(m, o, n, k)
        tb = encode_tiled(sp.values, sp.indices, n, bn=blk.bn)
        f_run = jax.jit(lambda a, t: ops.tiled_spmm(a, t, impl=impl))
        t_f32 = timeit(f_run, x, tb, iters=iters)
        bits_f32 = tiled_storage_bits(tb, elem_bits=32)
        row = {"m": m, "n": n, "o": o, "k": k, "bn": blk.bn,
               "times_s": {"tiled_f32": t_f32}, "quant": {}}
        for qm in ("int8", "int4"):
            qt = quantize_tiled(tb, qm)
            t_q = timeit(f_run, x, qt, iters=iters)
            got = np.asarray(f_run(x, qt))
            want = np.asarray(x @ tiled_to_dense(qt).T)
            err = float(np.max(np.abs(got - want))
                        / max(np.max(np.abs(want)), 1e-9))
            cell = {"rel_err_vs_dequant_ref": err,
                    "parity_ok": bool(err < 1e-5),
                    "speedup_vs_f32_tiled": t_f32 / max(t_q, 1e-12),
                    "storage_ratio_vs_f32":
                        bits_f32 / tiled_storage_bits(qt)}
            row["times_s"][f"tiled_{qm}"] = t_q
            # one reduced-shape pass through the Pallas kernel itself
            # (interpret mode on CPU): the in-VMEM dequant formulation
            if si == 0:
                xs = x[:min(m, interp_m)]
                got_p = np.asarray(ops.tiled_spmm(xs, qt, impl="pallas"))
                want_p = np.asarray(xs @ tiled_to_dense(qt).T)
                perr = float(np.max(np.abs(got_p - want_p))
                             / max(np.max(np.abs(want_p)), 1e-9))
                cell["pallas_interp_rel_err"] = perr
                cell["parity_ok"] = cell["parity_ok"] and perr < 1e-5
            row["quant"][qm] = cell
            print(f"  quant     M={m:5d} N={n:5d} O={o:4d} {qm:5s} "
                  f"f32={t_f32 * 1e3:8.2f}ms {qm}={t_q * 1e3:8.2f}ms "
                  f"x{cell['speedup_vs_f32_tiled']:5.2f}  "
                  f"[err {err:.1e}  {cell['storage_ratio_vs_f32']:.2f}x "
                  f"smaller]")
        rows.append(row)
    geo = {}
    for qm in ("int8", "int4"):
        ups = [r["quant"][qm]["speedup_vs_f32_tiled"] for r in rows]
        geo[qm] = float(np.exp(np.mean(np.log(ups)))) if ups else None
    return {
        "rows": rows,
        "geomean_speedup_vs_f32_tiled": geo,
        "parity_all_ok": bool(all(c["parity_ok"]
                                  for r in rows
                                  for c in r["quant"].values())),
    }


def bench_dram_model() -> dict:
    """The ``dram`` section: modeled DRAM traffic of the four paper CNNs
    under adaptive vs fixed-RIF dataflow (`repro.launch.cost_model`,
    DESIGN.md §14) on the zcu102 profile.  Analytical — no timing, so it
    runs identically in smoke and full mode, and `tests/test_paper_claims`
    pins the same figures against the paper's 1.17–1.8x ADC band."""
    from repro.launch import cost_model
    from repro.models.cnn import network_layers
    dep = cost_model.DEPLOYMENTS["zcu102"]
    nets = {}
    for net in ("alexnet", "vgg16", "resnet50", "googlenet"):
        layers = network_layers(net, "sense")
        adap = cost_model.network_cost(layers, dep, adaptive=True,
                                       scope="adc")
        fixed = cost_model.network_cost(layers, dep, adaptive=False,
                                        scope="adc")
        nets[net] = {
            "adaptive_dram_bytes": adap["total_bytes"],
            "fixed_rif_dram_bytes": fixed["total_bytes"],
            "reduction": cost_model.adc_reduction(layers, dep, scope="adc"),
            "frac_rwf": adap["frac_rwf"],
            "adaptive_energy_pj": adap["energy_pj"],
        }
        print(f"  {net:9s} adaptive={adap['total_bytes'] / 1e6:8.2f} MB "
              f"fixed-RIF={fixed['total_bytes'] / 1e6:8.2f} MB "
              f"x{nets[net]['reduction']:.2f}")
    return {"deployment": dep.name, "scope": "adc", "networks": nets}


def _compiled_impl() -> str:
    """The impl the main timing columns run: real compiled code only — the
    Mosaic-compiled tiled kernel on a TPU backend, the tiled path's XLA
    fallback elsewhere (interpret mode is an emulator; it gets its own
    reduced-shape columns + parity check)."""
    return "pallas" if jax.default_backend() == "tpu" else "xla"


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--smoke", action="store_true",
                    help="<60 s subset: fewer layers, smaller M (CI gate)")
    ap.add_argument("--out", default=str(REPO_ROOT / "BENCH_kernels.json"))
    ap.add_argument("--iters", type=int, default=None)
    args = ap.parse_args(argv)

    if args.smoke:
        m_cap, max_layers, iters, pallas_m, pallas_budget = 128, 2, 2, 32, 1
    else:
        m_cap, max_layers, iters, pallas_m, pallas_budget = 256, 5, 3, 64, 2
    if args.iters:
        iters = args.iters

    nets = {"alexnet": alexnet_layers(), "vgg16": vgg16_layers(),
            "resnet50": resnet50_layers()}
    t0 = time.time()
    results = {}
    for net, layers in nets.items():
        print(f"{net}:")
        results[net] = bench_network(net, layers, m_cap=m_cap,
                                     max_layers=max_layers, iters=iters,
                                     pallas_m=pallas_m,
                                     pallas_budget=pallas_budget)
    print("decode:")
    decode = bench_decode(
        DECODE_SHAPES["smoke" if args.smoke else "full"], iters=iters)
    print("quant:")
    quant = bench_quant(
        QUANT_SHAPES["smoke" if args.smoke else "full"], iters=iters,
        interp_m=pallas_m)
    print("dram (modeled, cost_model):")
    dram = bench_dram_model()
    report = {
        "meta": {
            "bench": "balanced_spmm seed-gather vs tiled decode-and-matmul",
            "mode": "smoke" if args.smoke else "full",
            "backend": jax.default_backend(),
            "jax": jax.__version__,
            "m_cap": m_cap, "iters": iters,
            "wall_s": None,         # filled below
        },
        "networks": results,
        "decode": decode,
        "quant": quant,
        "dram": dram,
    }
    report["meta"]["wall_s"] = round(time.time() - t0, 2)
    pathlib.Path(args.out).write_text(json.dumps(report, indent=2) + "\n")
    print(f"wrote {args.out} ({report['meta']['wall_s']} s)")

    vgg = results["vgg16"]
    parity = all(r.get("pallas_ok", True)
                 for n in results.values() for r in n["layers"]) \
        and decode["parity_all_ok"] and quant["parity_all_ok"]
    faster = (vgg["geomean_speedup_tiled_vs_seed"] or 0) > 1.0 \
        and decode["all_rows_faster"]
    print(f"vgg16 geomean speedup: {vgg['geomean_speedup_tiled_vs_seed']:.2f}"
          f"  decode geomean vs scatter+dot: "
          f"{decode['geomean_speedup_decode_vs_scatter_dot']:.2f}"
          f"  quant int8/int4 vs f32 tiled: "
          f"{quant['geomean_speedup_vs_f32_tiled']['int8']:.2f}/"
          f"{quant['geomean_speedup_vs_f32_tiled']['int4']:.2f}"
          f"  parity: {'ok' if parity else 'FAIL'}")
    # smoke is a correctness/regression gate (shapes too small to be
    # perf-representative); full mode also gates on the VGG-16 speedup and
    # on every decode row beating the scatter+dot baseline.
    ok = parity if args.smoke else (parity and faster)
    return 0 if ok else 1


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    sys.exit(main())
