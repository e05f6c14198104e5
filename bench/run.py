"""Run one benchmark cell once, on the accelerator this process finds.

    python3 bench/run.py --workload olmo-1b.decode --seed 7 --seconds 20 \
        --trace 0

Everything about a cell is found by name: the workload in
``BENCHMARK.json`` names a configuration (``bench/configs/<name>.json``)
and a traffic mix (``bench/traffic/<mix>.json``, whose ``kind`` picks the
loop in ``bench/loops/``); each per-layer metric is read by
``bench/metrics/<metric>.py``.

Set-up (weights drawn on the device from the seed, the plan build, the
compiles or cache loads, and the cache fill the traffic needs) runs
first; then the window is measured for ``--seconds``; then, once the
window has closed and the program's state is freed, the plain float32
reference checks what the window served.  With ``--trace 1`` the window
runs under the profiler and the per-layer metrics are reported instead
of the end-to-end ones.

The last line of standard output is one JSON object.  A run that finds
no TPU, fewer chips than the cell asks for, or a device kind missing from
``bench/peaks.json`` exits nonzero and prints no result.
"""
from __future__ import annotations

import time

T_START = time.monotonic()

import argparse                                              # noqa: E402
import contextlib                                            # noqa: E402
import dataclasses                                           # noqa: E402
import gc                                                    # noqa: E402
import importlib                                             # noqa: E402
import importlib.util                                        # noqa: E402
import json                                                  # noqa: E402
import os                                                    # noqa: E402
import pathlib                                               # noqa: E402
import shutil                                                # noqa: E402
import sys                                                   # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parent.parent
for p in (str(ROOT / "src"), str(ROOT)):
    if p not in sys.path:
        sys.path.insert(0, p)

COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
CACHE_DIR = ROOT / ".jax_cache"
TRACE_DIR = ROOT / ".bench_trace"


def log(msg: str) -> None:
    print(f"[bench] {msg}", flush=True)


@dataclasses.dataclass
class Context:
    """What a loop gets: the cell's data, the seed, and the harness's
    clocks, spans and counters."""
    workload: str
    spec: dict
    mix: dict
    seed: int
    seconds: float
    trace: bool
    peak: dict
    t_start: float = T_START
    setup_end: float | None = None
    compiles: list = dataclasses.field(default_factory=list)
    counters: dict = dataclasses.field(default_factory=dict)
    checks: dict = dataclasses.field(default_factory=dict)
    summary: object = None
    control: bool = False

    def model_config(self):
        from repro.configs import ModelConfig
        return ModelConfig(**self.spec["model"], sparse_serving=True)

    def span(self, name: str):
        if not self.trace:
            return contextlib.nullcontext()
        import jax
        return jax.profiler.TraceAnnotation(name)

    def end_setup(self) -> None:
        self.setup_end = time.monotonic()
        self.counters["compiles_at_window"] = len(self.compiles)
        self.counters["compile_s"] = sum(self.compiles)

    @contextlib.contextmanager
    def window(self):
        """The measured window; under ``--trace 1`` it is profiled."""
        if not self.trace:
            yield
            return
        import jax
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        jax.profiler.start_trace(str(TRACE_DIR))
        try:
            with jax.profiler.TraceAnnotation("bench.window"):
                yield
        finally:
            jax.profiler.stop_trace()

    def limit(self, name: str) -> float:
        return float(self.spec["limits"][name])

    def check(self, name: str, value: float, limit: float) -> None:
        self.checks[name] = {"value": value, "limit": limit}


def _load_reader(name: str):
    path = ROOT / "bench" / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(
        f"bench_metric_{name.replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def _applies(metric: dict, workload: str) -> bool:
    return workload in metric.get("workloads", [workload])


def use_compile_cache() -> str:
    """JAX's persistent cache: ``$JAX_COMPILATION_CACHE_DIR`` where the
    environment sets it, else one fixed directory inside the checkout
    (the path is part of the cache key, so it never moves)."""
    import jax
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    return jax.config.jax_compilation_cache_dir


def free_device_memory() -> None:
    """Drop what the program left behind before the reference runs."""
    import jax
    gc.collect()
    jax.clear_caches()
    gc.collect()


def run_cell(bench: dict, workload: str, seed: int, seconds: float,
             trace: bool, device_kind: str, chips: int,
             spec: dict | None = None, mix: dict | None = None,
             control: bool = False) -> dict:
    """Run one cell in this process and return the result object.  The
    caller has checked the devices.  ``spec`` and ``mix`` replace the
    cell's configuration and traffic files (the tests run small ones).
    With ``control`` the fp8 control takes the program's place in the
    comparison that decides ``correct`` (`bench/calibrate.py`), and the
    program's own gap is returned beside it."""
    import jax

    from bench import traffic, work
    wl = next(w for w in bench["workloads"] if w["name"] == workload)
    cfg = next(c for c in bench["configs"] if c["name"] == wl["config"])
    if spec is None:
        spec = json.loads((ROOT / cfg["file"]).read_text())
    if mix is None:
        mix = traffic.load_mix(wl["traffic"])
    ctx = Context(workload=workload, spec=spec, mix=mix, seed=seed,
                  seconds=seconds, trace=trace, peak=work.peaks(device_kind),
                  control=control)

    def on_event(event, duration, **_):
        if event == COMPILE_EVENT:
            ctx.compiles.append(duration)
    jax.monitoring.register_event_duration_secs_listener(on_event)

    loop = importlib.import_module(f"bench.loops.{mix['kind']}")
    e2e = loop.run(ctx)
    if ctx.counters.get("compiles_in_window"):
        log(f"compiles inside the window: "
            f"{ctx.counters['compiles_in_window']}")
    e2e["setup_s"] = ctx.setup_end - ctx.t_start
    if trace:
        from bench import trace as trace_mod
        ctx.summary = trace_mod.summarize(trace_mod.load(str(TRACE_DIR)))
        values = {}
        for m in bench["per_layer"]:
            if _applies(m, workload):
                v = _load_reader(m["name"])(ctx)
                if v is not None:
                    values[m["name"]] = {"value": v, "unit": m["unit"]}
    else:
        values = {m["name"]: {"value": e2e[m["name"]], "unit": m["unit"]}
                  for m in bench["end_to_end"] if _applies(m, workload)}
    correct = all(c["value"] <= c["limit"] for c in ctx.checks.values()) \
        and bool(ctx.checks)
    devs = jax.devices()[:chips]
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs),
              "memory_peak_bytes": ctx.counters["memory_peak_bytes"]}
    result = {"correct": correct,
              "attempted": ctx.counters["attempted"],
              "failed": ctx.counters["failed"],
              "metrics": values, "device": device}
    if trace:
        device["busy_s"] = ctx.summary.busy_s
        device["window_s"] = ctx.summary.window_s
        result["breakdown"] = ctx.summary.breakdown()
    for name, c in ctx.checks.items():
        print(f"[bench] check {name} = {c['value']!r} (limit "
              f"{c['limit']!r})", file=sys.stderr, flush=True)
    result["checks"] = ctx.checks
    if control:
        result["program_max_logit_gap"] = ctx.counters["max_logit_gap"]
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    wl = [w for w in bench["workloads"] if w["name"] == args.workload]
    if not wl:
        print(f"[bench] no workload {args.workload!r} in BENCHMARK.json",
              file=sys.stderr)
        return 2
    import jax

    from bench import work
    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"[bench] no TPU: JAX sees {devices[0].platform} devices",
              file=sys.stderr)
        return 2
    if len(devices) < wl[0]["chips"]:
        print(f"[bench] the cell needs {wl[0]['chips']} chips; JAX sees "
              f"{len(devices)}", file=sys.stderr)
        return 2
    try:
        work.peaks(devices[0].device_kind)
    except KeyError as e:
        print(f"[bench] {e}", file=sys.stderr)
        return 2
    log(f"compile cache: {use_compile_cache()}")
    result = run_cell(bench, args.workload, args.seed, args.seconds,
                      bool(args.trace), devices[0].device_kind,
                      wl[0]["chips"])
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
