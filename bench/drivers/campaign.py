"""Closed-loop driver of measurement campaigns on the wall-clock platform.

The window starts a fresh campaign (fresh hub and journal, seed derived from
the run's seed) and lets it measure until the window closes; a campaign that
ends inside the window is followed by the next, with the next seed.  The
benchmark reaches the timed path through the platform object it hands the
campaign, and only to watch it:

* each program the platform compiles is kept (its call count and the dtype
  and shapes of the operands of its first call), so that the check can run
  the very programs the window timed;
* ``measure`` refuses to start a shape once the window has closed, which
  ends the campaign; the shape in flight at the close still completes.

Traffic parameters: ``n_samples`` per campaign, ``check_shapes`` compared
after the window.  Configuration (``campaign``): platform, ``layer_types``,
``repeats``, ``dtype``, and ``precision``: the matmul precision the program
runs at, set through JAX's own option (``jax_default_matmul_precision``),
under which the platform compiles every program.
"""

from __future__ import annotations

import glob
import json
import math
import os
import shutil
import tempfile
import time

import numpy as np

from bench.common import derived_seed, seed_key
from bench.reference import dense_error
from repro.accelerators.xla_cpu import XLACPUPlatform
from repro.api import Campaign, CampaignSpec, RuntimeSpec
from repro.runtime.scheduler import MeasurementError


class WindowClosed(RuntimeError):
    """The window closed: the campaign may start no further shape."""


class _Program:
    """A compiled dense program as the platform runs it, with its calls counted."""

    def __init__(self, exe) -> None:
        self.exe = exe
        self.calls = 0
        self.operands = None

    def __call__(self, a, b):
        if self.operands is None:
            self.operands = (a.dtype.name, tuple(a.shape), b.dtype.name, tuple(b.shape))
        self.calls += 1
        return self.exe(a, b)


def _platform(run):
    c = run.cell.config["campaign"]
    return XLACPUPlatform(repeats=int(c["repeats"]), dtype=c["dtype"])


def setup(run, log) -> None:
    import jax

    jax.config.update("jax_default_matmul_precision", run.cell.config["campaign"]["precision"])
    plat = _platform(run)
    dev = plat.device
    key = plat.cache_key()
    if f"{dev.platform}:{dev.device_kind}" not in key:
        raise RuntimeError(f"cache key {key!r} names no device")
    # Warm the backend, the compiler and the timed path on a shape outside
    # the parameter space, so the window's first shape pays only its own work.
    plat.measure("dense", {"tokens": 8, "d_in": 8, "d_out": 8})
    run.state.update(key=key, campaigns=[], programs={}, measured={})
    log(f"[setup] campaign platform {key}")


def _watch(plat, k: int, deadline: float, programs: dict, measured: dict):
    compile_ = plat._compile
    measure = plat.measure

    def compile_kept(shape):
        prog = _Program(compile_(shape))
        programs.setdefault((k, shape), []).append(prog)
        return prog

    def measure_until_close(layer_type, cfg):
        if time.perf_counter() >= deadline:
            raise WindowClosed
        shape = (cfg["tokens"], cfg["d_in"], cfg["d_out"])
        fresh = (k, shape) not in programs
        t = measure(layer_type, cfg)
        if fresh and time.perf_counter() <= deadline:
            measured[(k, shape)] = t
        return t

    plat._compile = compile_kept
    plat.measure = measure_until_close


def window(run, seconds: float) -> dict:
    c = run.cell.config["campaign"]
    st = run.state
    t0 = time.perf_counter()
    deadline = t0 + seconds
    k = 0
    while time.perf_counter() < deadline:
        plat = _platform(run)
        _watch(plat, k, deadline, st["programs"], st["measured"])
        hub = tempfile.mkdtemp(prefix="bench-campaign-")
        st["campaigns"].append(hub)
        spec = CampaignSpec(platform="xla_cpu", layer_types=tuple(c["layer_types"]),
                            n_samples=int(run.cell.traffic["n_samples"]),
                            seed=derived_seed(run.seed, k), hub_dir=hub)
        try:
            with run.span("bench.campaign"):
                Campaign(spec, platform=plat).run(runtime=RuntimeSpec(workers=1))
        except (WindowClosed, MeasurementError):
            if time.perf_counter() < deadline:
                raise
        k += 1
    n = len(st["measured"])
    return {"attempted": n, "failed": 0, "e2e": {"campaign_configs_per_s": n / seconds},
            "info": {"campaigns_started": k, "configs_measured": n,
                     "closed_after_s": time.perf_counter() - t0}}


def release(run) -> None:
    pass


def _records(hub: str) -> list[dict]:
    out = []
    for path in glob.glob(os.path.join(hub, "*.jsonl")):
        with open(path) as f:
            out.extend(json.loads(line) for line in f if line.strip())
    return out


def check(run, log) -> list[tuple[str, float, float]]:
    """The window's programs on seeded operands against the exact product,
    the measurement protocol, and the journal's records."""
    import jax

    st = run.state
    c = run.cell.config["campaign"]
    lim = run.cell.config["limits"]
    repeats = int(c["repeats"])
    dtype = np.dtype(c["dtype"]).name
    protocol = dup = 0
    for (k, (m, kk, n)), progs in st["programs"].items():
        dup += len(progs) - 1
        if (k, (m, kk, n)) not in st["measured"]:
            continue
        p = progs[0]
        if p.calls != 1 + repeats or p.operands != (dtype, (m, kk), dtype, (kk, n)):
            protocol += 1
    bad = 0
    for k, hub in enumerate(st["campaigns"]):
        seen = set()
        for rec in _records(hub):
            if rec.get("platform") != st["key"]:
                bad += len(rec.get("rows", [])) or 1
                continue
            for row, sec in zip(rec["rows"], rec["seconds"]):
                shape = (k, tuple(row))
                if shape in seen or not (math.isfinite(sec) and sec > 0):
                    bad += 1
                elif shape in st["measured"] and st["measured"][shape] != sec:
                    bad += 1
                seen.add(shape)
    err = 0.0
    dev = jax.devices()[0]
    compared = _compared(run)
    for key, a, b in compared:
        prog = st["programs"][key][0].exe
        err = max(err, dense_error(a, b, np.asarray(prog(jax.device_put(a, dev),
                                                          jax.device_put(b, dev)))))
    for hub in st["campaigns"]:
        shutil.rmtree(hub, ignore_errors=True)
    log(f"[check] {len(compared)} of {len(st['measured'])} measured shapes compared")
    return [
        ("dense_err", err, float(lim["dense_err"])),
        ("protocol_faults", float(protocol), 0.0),
        ("duplicate_shapes", float(dup), 0.0),
        ("bad_records", float(bad), 0.0),
    ]


def _compared(run) -> list[tuple]:
    """A seeded sample of the measured shapes, with the largest, each with
    seeded operands: ``(shape key, a, b)``."""
    shapes = sorted(run.state["measured"])
    if not shapes:
        return []
    dtype = np.dtype(run.cell.config["campaign"]["dtype"])
    rng = np.random.default_rng(seed_key(run.seed, 6))
    n_check = min(len(shapes), int(run.cell.traffic["check_shapes"]))
    pick = set(rng.choice(len(shapes), size=n_check, replace=False).tolist())
    pick.add(max(range(len(shapes)), key=lambda i: int(np.prod(shapes[i][1]))))
    out = []
    for i in sorted(pick):
        m, k, n = shapes[i][1]
        out.append((shapes[i], rng.standard_normal((m, k)).astype(dtype),
                    rng.standard_normal((k, n)).astype(dtype)))
    return out


def control(run, log) -> dict:
    """The control's reading: the window's shapes compiled by the platform
    one precision below the configuration's (``control_precision``: three
    bfloat16 passes for float32 at ``highest``), on the check's operands."""
    import jax

    plat = _platform(run)
    err = 0.0
    with jax.default_matmul_precision(run.cell.config["campaign"]["control_precision"]):
        for (_, shape), a, b in _compared(run):
            exe = plat._compile(shape)
            err = max(err, dense_error(a, b, np.asarray(exe(jax.device_put(a, plat.device),
                                                             jax.device_put(b, plat.device)))))
    return {"dense_err": err}
