"""Run one cell of ``BENCHMARK.json`` once, on the chip this process holds.

    python3 -m bench.run --workload sel-cifar10 --seed 7 --seconds 30 --trace 0

Set-up (process start to the first timed operation, compiles included) is
``setup_s``.  The window then runs the cell's traffic for ``--seconds``; with
``--trace 1`` it runs under the profiler, for at most ``TRACE_WINDOW_S`` (a
trace holds every operation the device ran, and a selection loop runs
hundreds of thousands a second), and the per-layer metrics are reported in
place of the end-to-end ones.  Once the window has closed and
the device's peak memory has been read, the driver frees the program's state
and compares what the window produced with the plain reference.

The last line of standard output is one JSON object (``correct``,
``attempted``, ``failed``, ``metrics``, ``device``, with ``--trace 1`` also
``breakdown``, and last ``checks``: each number compared beside its limit).
The checks are also the last lines of standard error.  Without a TPU, or with
fewer chips than the cell asks for, the run exits non-zero and prints no
result.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
OUT_DIR = ROOT / ".bench_out"
TRACE_WINDOW_S = 5.0


class Window:
    """The clock of one run's measured window."""

    def __init__(self):
        self.t0 = time.perf_counter()

    def elapsed(self) -> float:
        return time.perf_counter() - self.t0


class Run:
    """What a driver needs from the harness for one run."""

    def __init__(self, *, seed: int, seconds: float, trace: bool,
                 config: dict, traffic: dict, limits: dict, devices,
                 trace_dir: Path, t_start: float):
        from bench.device import CompileClock

        self.seed, self.seconds, self.trace = int(seed), float(seconds), trace
        self.config, self.traffic, self.limits = config, traffic, limits
        self.devices = devices
        self.trace_dir = trace_dir
        self.t_start = t_start
        self.clock = CompileClock()
        self.setup_s = None
        self.window_s = None
        self.window_compiles = None
        self.peak_bytes = 0
        self.bytes_limit = 0

    @contextlib.contextmanager
    def window(self):
        """Ends set-up, runs the block as the measured window, then reads
        the peak memory of the fullest chip."""
        import jax

        from bench.device import memory

        self.setup_s = time.perf_counter() - self.t_start
        compiles0 = self.clock.count
        if self.trace:
            jax.profiler.start_trace(str(self.trace_dir))
        w = Window()
        try:
            with jax.profiler.TraceAnnotation("bench.window"):
                yield w
        finally:
            self.window_s = w.elapsed()
            self.window_compiles = self.clock.count - compiles0
            if self.trace:
                jax.profiler.stop_trace()
        mem = [memory(d) for d in self.devices]
        self.peak_bytes = max(p for p, _ in mem)
        self.bytes_limit = min(lim for _, lim in mem)


def run_cell(manifest, cell: dict, *, seed: int, seconds: float, trace: bool,
             devices, t_start: float, trace_dir: Path | None = None,
             config: dict | None = None, traffic: dict | None = None,
             limits: dict | None = None) -> dict:
    """Run one cell and return its result object (not yet printed).

    ``config``, ``traffic`` and ``limits`` default to the cell's files; tests
    pass small ones.  ``devices`` are the chips the run holds (the tests pass the CPU).
    """
    import jax

    from bench import trace as trace_mod
    from bench.device import peaks

    config = config if config is not None else manifest.config(cell["config"])
    traffic = traffic if traffic is not None else manifest.traffic(cell["traffic"])
    limits = limits if limits is not None else manifest.limits(cell["name"])
    driver = manifest.driver(traffic)
    own_trace_dir = trace_dir is None
    if own_trace_dir:
        trace_dir = OUT_DIR / f"trace-{cell['name']}"
        shutil.rmtree(trace_dir, ignore_errors=True)
    if trace:
        seconds = min(seconds, TRACE_WINDOW_S)
    run = Run(seed=seed, seconds=seconds, trace=trace, config=config,
              traffic=traffic, limits=limits, devices=devices,
              trace_dir=trace_dir,
              t_start=t_start)
    out = driver.run(run)

    dev = devices[0]
    values = dict(out["values"], setup_s=run.setup_s)
    record = dict(out.get("record", {}),
                  window_s=run.window_s, window_compiles=run.window_compiles,
                  peak_bytes=run.peak_bytes, bytes_limit=run.bytes_limit,
                  trace=None)
    if dev.platform == "tpu":
        record["peaks"] = peaks(dev.device_kind)
    result = {"correct": None, "attempted": int(out["attempted"]),
              "failed": int(out["failed"]), "metrics": {}}
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices()), "memory_peak_bytes": run.peak_bytes}
    if trace:
        red = trace_mod.reduce_file(trace_mod.find_xplane(trace_dir))
        record["trace"] = red
        device.update(busy_s=red["busy_s"], window_s=red["window_s"])
        for m in manifest.per_layer_for(cell["name"]):
            v = manifest.metric_reader(m["name"]).read(record)
            if v is not None:
                result["metrics"][m["name"]] = {"value": v, "unit": m["unit"]}
        result["breakdown"] = {"device_ops": red["device_ops"],
                               "idle_gaps": red["idle_gaps"]}
        if own_trace_dir:
            shutil.rmtree(trace_dir, ignore_errors=True)
    else:
        for m in manifest.end_to_end_for(cell["name"]):
            result["metrics"][m["name"]] = {"value": values[m["name"]],
                                            "unit": m["unit"]}
    result["device"] = device
    checks = out["checks"]
    result["correct"] = bool(out["failed"] == 0 and checks and all(
        math.isfinite(v) and v <= lim for _, v, lim in checks))
    result["checks"] = {n: {"value": v, "limit": lim} for n, v, lim in checks}
    return result


def enable_compile_cache() -> None:
    """JAX's persistent compilation cache at a fixed path in the checkout
    (``$JAX_COMPILATION_CACHE_DIR`` where that is set), holding every
    program of a cell however quick to compile, so that only a cell's first
    run in a checkout compiles."""
    import os

    import jax

    jax.config.update("jax_compilation_cache_dir",
                      os.environ.get("JAX_COMPILATION_CACHE_DIR")
                      or str(ROOT / ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--trace-dir", default=None,
                    help="keep the profiler trace here instead of deleting it")
    args = ap.parse_args(argv)

    sys.path.insert(0, str(ROOT / "src"))
    from bench.device import NoChipError, require_tpu
    from bench.manifest import Manifest

    manifest = Manifest(ROOT)
    cell = manifest.workload(args.workload)
    try:
        devices = require_tpu(int(cell["chips"]))
    except NoChipError as e:
        print(f"bench.run: {e}", file=sys.stderr)
        return 3

    enable_compile_cache()

    result = run_cell(
        manifest, cell, seed=args.seed, seconds=args.seconds,
        trace=bool(args.trace), devices=devices, t_start=T_START,
        trace_dir=Path(args.trace_dir) if args.trace_dir else None)
    for name, c in result["checks"].items():
        print(f"check {name} = {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
