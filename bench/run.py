"""Run one cell of the on-chip benchmark and print its result line.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell is found by name in ``BENCHMARK.json``; its configuration, traffic
mix and per-layer metric readers are files found by name (``bench/common.py``).
A run loads and warms up (``setup_s``), measures for ``--seconds`` with the
profiler off (``--trace 0``: the end-to-end metrics) or on (``--trace 1``:
the per-layer metrics, over the mix's ``trace_seconds`` at most), reads the
device's peak memory, frees the program's state, and compares what the
window produced with the plain reference.  Every number compared is printed
beside its limit as the last lines of standard error; the last line of
standard output is the result object.  Without a TPU, or with fewer chips
than the cell asks for, it exits non-zero and prints no result.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402

CHECKOUT = Path(__file__).resolve().parents[1]
CACHE_DIR = CHECKOUT / "bench" / ".jax_cache"


class NoChip(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell asks for."""


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


class Run:
    """What a driver and the metric readers see of one run."""

    def __init__(self, cell, seed: int, seconds: float, trace: bool) -> None:
        self.cell = cell
        self.seed = int(seed)
        self.window_s = float(seconds)
        self.trace = bool(trace)
        self.stats: dict = {}
        self.state: dict = {}
        self.delta: dict = {"counters": {}, "histograms": {}}
        self.reduced: dict | None = None
        self.peaks = None

    def span(self, name: str):
        """Host span around one call into a layer (in the trace when traced)."""
        if not self.trace:
            return contextlib.nullcontext()
        import jax

        return jax.profiler.TraceAnnotation(name)


def devices(cell, require_chip: bool):
    import jax

    devs = jax.devices()
    if require_chip and (devs[0].platform != "tpu" or len(devs) < cell.chips):
        raise NoChip(f"cell {cell.name} needs {cell.chips} TPU chip(s); JAX found "
                     f"{len(devs)} {devs[0].platform} device(s) ({devs[0].device_kind})")
    return devs


def memory_peak_bytes(devs) -> int:
    peak = 0
    for d in devs:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return peak


def _window(run, driver, window_s: float) -> dict:
    """The measured window, traced or not; fills ``run.delta`` and ``run.reduced``."""
    import jax

    from bench.common import counters_delta, program_counters
    from bench.trace_reduce import load, reduce_events

    before = program_counters()
    with tempfile.TemporaryDirectory(prefix="bench-trace-") as tdir:
        if run.trace:
            # Device ops and the benchmark's own host spans; no Python
            # function tracing, whose cost would slow the host path measured.
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.enable_hlo_proto = False
            jax.profiler.start_trace(tdir, profiler_options=opts)
        try:
            with run.span("bench.window"):
                out = driver.window(run, window_s)
        finally:
            if run.trace:
                jax.profiler.stop_trace()
        run.delta = counters_delta(before, program_counters())
        if run.trace:
            files = sorted(Path(tdir).rglob("*.xplane.pb"))
            run.reduced = reduce_events(load(str(files[-1])))
    return out


def execute(cell, seed: int, seconds: float, trace: bool, require_chip: bool = True,
            t_start: float | None = None, control: bool = False) -> tuple[dict, list]:
    """One run of ``cell``: the result object and the checks ``(name, value, limit)``.

    ``control`` adds the control's readings (``bench/control.py``) under
    ``"control"``.
    """
    from bench.peaks import peaks

    t_start = T_START if t_start is None else t_start
    devs = devices(cell, require_chip)
    dev = devs[0]
    run = Run(cell, seed, seconds, trace)
    if require_chip:
        run.peaks = peaks(dev.device_kind)
    window_s = min(seconds, float(cell.traffic.get("trace_seconds", seconds))) if trace else seconds
    run.window_s = window_s
    driver = cell.driver()
    try:
        driver.setup(run, log)
        setup_s = time.perf_counter() - t_start
        log(f"[setup] {setup_s:.3f} s; window {window_s} s, trace {int(trace)}")
        out = _window(run, driver, window_s)
        mem = memory_peak_bytes(devs[: cell.chips])
    finally:
        # Ends what the driver started (a client process, a server) and
        # frees the program's state before the reference runs.
        driver.release(run)
    checks = driver.check(run, log)
    readings = driver.control(run, log) if control else None

    metrics = {}
    if not trace:
        for m in cell.end_to_end():
            value = setup_s if m["name"] == "setup_s" else out["e2e"][m["name"]]
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    else:
        for m in cell.per_layer():
            value = cell.metric_reader(m["name"]).read(run)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    device = {"platform": dev.platform, "kind": dev.device_kind, "count": len(devs),
              "memory_peak_bytes": mem}
    result = {
        "correct": all(v <= lim for _, v, lim in checks),
        "attempted": out["attempted"],
        "failed": out["failed"],
        "metrics": metrics,
        "device": device,
    }
    if trace:
        device["busy_s"] = run.reduced["busy_s"]
        device["window_s"] = run.reduced["window_s"]
        result["breakdown"] = {"device_ops": run.reduced["device_ops"],
                               "idle_gaps": run.reduced["idle_gaps"]}
    for k, v in sorted(out.get("info", {}).items()):
        log(f"[info] {k} {v}")
    if readings is not None:
        result["control"] = readings
    result["checks"] = {name: {"value": v if math.isfinite(v) else None, "limit": lim}
                        for name, v, lim in checks}
    return result, checks


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    here = str(Path(__file__).resolve().parent)
    sys.path[:] = [str(CHECKOUT), str(CHECKOUT / "src")] + [p for p in sys.path if p != here]
    # The persistent compile cache lives at one fixed path inside the checkout
    # (the path is part of the cache key), whatever the environment says.
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(CACHE_DIR)
    import jax

    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    from bench.common import Cell

    cell = Cell(args.workload)
    try:
        result, checks = execute(cell, args.seed, args.seconds, bool(args.trace))
    except NoChip as exc:
        log(f"bench: {exc}")
        return 2
    for name, value, limit in checks:
        log(f"check {name} {value!r} limit {limit!r} "
            f"{'ok' if value <= limit else 'FAILED'}")
    print(json.dumps(result, allow_nan=False), flush=True)
    return 0


if __name__ == "__main__":
    code = main()
    sys.stdout.flush()
    sys.stderr.flush()
    # Nothing may print after the checks and the result line: skip the
    # interpreter's teardown, whose runtime logs would follow them.
    os._exit(code)
