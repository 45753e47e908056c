"""Run one cell once: set up, measure a closed-loop window of rounds,
check the result against the plain reference, and report.

Everything that belongs to one configuration, cell or per-layer metric
is found by its name in ``BENCHMARK.json``:

  * the configuration's file (``configs[].file``) gives the model's sizes,
    the program's configuration it starts from (``repo_config`` plus
    ``override``), its data and its ``family``;
  * the family names the system under test (``bench/systems/<family>.py``),
    the plain reference (``bench/reference/<family>.py``) and the FLOP
    count (``bench/flops/<family>.py``);
  * the cell's traffic file (``bench/cells/<traffic>.json``) gives the
    rounds' shape, its overrides of the program's configuration and the
    limits of the comparison;
  * each per-layer metric is read by ``bench/metrics/<name>.py``.

The run: data and weights from the seed, the trainer, then the set-up
rounds through the window's own call (the first compiles every program
the window uses; the comparison follows them), then rounds back to back
until the first round that ends after ``seconds``.  Each round is timed
from the call to the moment the new global D and G are on the device.
"""
from __future__ import annotations

import gc
import importlib
import importlib.util
import json
import os
import statistics
import sys
import tempfile
import time
from typing import Any, Callable, Dict, Optional

import numpy as np

from bench import check, trace as tr

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


class NoChip(RuntimeError):
    """JAX finds no TPU, or fewer chips than the cell asks for."""


def load_cell(name: str):
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        spec = json.load(f)
    work = {w["name"]: w for w in spec["workloads"]}[name]
    entry = {c["name"]: c for c in spec["configs"]}[work["config"]]
    with open(os.path.join(ROOT, entry["file"])) as f:
        conf = json.load(f)
    with open(os.path.join(ROOT, "bench", "cells",
                           work["traffic"] + ".json")) as f:
        cell = json.load(f)
    return spec, work, conf, cell


def seeds_from(seed: int) -> Dict[str, int]:
    """31-bit seeds for the data, the trainer's host RNG and the weights,
    all drawn from ``seed`` (any size)."""
    s = np.random.SeedSequence(int(seed)).generate_state(3) & 0x7FFFFFFF
    return {"data": int(s[0]), "trainer": int(s[1]), "weights": int(s[2])}


def chips(n: int, require_tpu: bool):
    import jax
    devs = jax.devices()
    if require_tpu and (devs[0].platform != "tpu" or len(devs) < n):
        raise NoChip(f"cell needs {n} TPU chip(s); JAX has "
                     f"{len(devs)} {devs[0].platform} device(s)")
    return devs[:n]


class Compiles:
    """Counts XLA compiles and their seconds from JAX's own events."""

    def __init__(self):
        import jax
        self.n, self.s = 0, 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, secs: float, **_) -> None:
        if event == COMPILE_EVENT:
            self.n += 1
            self.s += secs

    def take(self):
        out = (self.n, self.s)
        self.n, self.s = 0, 0.0
        return out


def _spanned(fn, name):
    import jax

    def call(*a, **kw):
        with jax.profiler.TraceAnnotation(name):
            return fn(*a, **kw)
    return call


def wrap_spans(system) -> None:
    """Put a host span around each layer call the system names, on the
    instance, for the traced run only."""
    for name, (obj, attr) in system.spans().items():
        fn = getattr(obj, attr, None)
        if fn is None:
            print(f"span {name}: {type(obj).__name__}.{attr} not found",
                  file=sys.stderr)
            continue
        setattr(obj, attr, _spanned(fn, name))


def load_metric(name: str):
    path = os.path.join(ROOT, "bench", "metrics", name + ".py")
    spec = importlib.util.spec_from_file_location(
        "bench_metric_" + name.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def cell_metrics(spec: Dict, name: str, kind: str):
    """The metrics of ``kind`` (``end_to_end``/``per_layer``) cell
    ``name`` reports: every end-to-end metric, and the per-layer metrics
    that list the cell under ``workloads``."""
    if kind == "end_to_end":
        return list(spec[kind])
    return [m for m in spec[kind] if name in m["workloads"]]


def setup_rounds(system, rounds: int) -> Dict[str, Any]:
    """The set-up rounds through the window's own call (the first
    compiles), with the program's draws recorded for the reference.
    Returns the program's readings for the comparison."""
    with system.recording():
        for r in range(rounds):
            system.round()
            if r == 0:
                m1 = system.first_moments()
    return {"losses": list(system.losses), "m1": m1,
            "params": system.params()}


def start_trace(log_dir: str) -> None:
    """The JAX profiler with its Python tracer off: device ops and the
    harness's ``TraceAnnotation`` spans, without an event for every
    Python call slowing the host."""
    import jax
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(log_dir, profiler_options=opts)


class Context:
    """What a per-layer metric reader may read."""

    def __init__(self, **kw):
        self.__dict__.update(kw)


def _memory_peak(devices) -> int:
    peaks = []
    for d in devices:
        stats = d.memory_stats() or {}
        peaks.append(int(stats.get("peak_bytes_in_use", 0)))
    return max(peaks)


def run(name: str, seed: int, seconds: float, trace: bool, *,
        t0: float, require_tpu: bool = True, compile_cache: bool = True,
        tweak: Optional[Callable] = None) -> Dict[str, Any]:
    """One run of cell ``name``; returns the result line's object.
    Tests run it on the CPU at small sizes: ``require_tpu`` and
    ``compile_cache`` off, and ``tweak(conf, cell)`` to change the
    sizes."""
    import jax
    spec, work, conf, cell = load_cell(name)
    if tweak is not None:
        conf, cell = tweak(conf, cell)
    devices = chips(int(work["chips"]), require_tpu)
    if compile_cache:
        from repro.launch.cache import use_compile_cache
        use_compile_cache()
    compiles = Compiles()
    family = conf["family"]
    system = importlib.import_module(f"bench.systems.{family}").System(
        conf, cell, seeds_from(seed))

    check_rounds = int(cell["check_rounds"])
    prog = setup_rounds(system, check_rounds)
    n_c, s_c = compiles.take()
    print(f"set-up: {check_rounds} rounds, {n_c} compiles ({s_c:.3f} s)",
          file=sys.stderr)

    log_dir = None
    if trace:
        wrap_spans(system)
        log_dir = tempfile.mkdtemp(prefix="bench_trace_")
        start_trace(log_dir)
    t_win = time.perf_counter()
    setup_s = t_win - t0
    times, samples = [], 0
    with jax.profiler.TraceAnnotation("bench.window"):
        while True:
            a = time.perf_counter()
            with jax.profiler.TraceAnnotation("bench.round"):
                samples += system.round()
            b = time.perf_counter()
            times.append(b - a)
            if b - t_win >= seconds:
                break
    window_s = b - t_win
    if trace:
        jax.profiler.stop_trace()
    n_c, s_c = compiles.take()
    print(f"window: {len(times)} rounds in {window_s:.3f} s, "
          f"{n_c} compiles ({s_c:.3f} s); round seconds "
          + " ".join(f"{x:.4f}" for x in times), file=sys.stderr)
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices),
              "memory_peak_bytes": _memory_peak(devices)}

    metrics: Dict[str, Dict[str, Any]] = {}
    breakdown = None
    if not trace:
        values = {
            "samples_per_s": samples / window_s,
            "round_s.p95": float(np.percentile(times, 95)),
            "setup_s": setup_s}
        for m in cell_metrics(spec, name, "end_to_end"):
            metrics[m["name"]] = {"value": values[m["name"]],
                                  "unit": m["unit"]}
    else:
        metrics, breakdown, busy_s, span_s = _per_layer(
            spec, name, family, system, log_dir, devices, len(times),
            window_s)
        device.update(busy_s=busy_s, window_s=span_s)

    # free the program, then run the reference and compare
    system.release()
    gc.collect()
    a = time.perf_counter()
    ref = system.reference()
    numbers = {**system.draw_numbers(),
               **check.compare(prog, ref, system.init_host)}
    limits = cell["limits"]
    correct = check.judge(numbers, limits)
    print(f"reference: {time.perf_counter() - a:.3f} s; round times "
          f"median {statistics.median(times):.4f} s", file=sys.stderr)
    print("losses (d, g) per set-up round: program "
          + json.dumps(prog["losses"]) + " reference "
          + json.dumps(ref["losses"]), file=sys.stderr)
    for k in limits:
        print(f"check {k} {numbers[k][0]!r} limit {limits[k]!r} "
              f"(worst at {numbers[k][1]})", file=sys.stderr)
    out = {"correct": bool(correct), "attempted": len(times), "failed": 0,
           "metrics": metrics, "device": device}
    if breakdown is not None:
        out["breakdown"] = breakdown
    out["check"] = {k: {"value": numbers[k][0], "limit": limits[k]}
                    for k in limits}
    return out


def _per_layer(spec, name, family, system, log_dir, devices, rounds,
               window_s):
    import shutil
    from bench import hw
    try:
        t = tr.load(log_dir)
    finally:
        shutil.rmtree(log_dir, ignore_errors=True)
    win = [(s, e) for s, e, n in t.spans if n == "bench.window"]
    if not win:
        raise RuntimeError("the trace holds no bench.window span")
    lo, hi = win[0]
    ids = [d.id for d in devices]
    flops = importlib.import_module(f"bench.flops.{family}")
    per_round = flops.round_flops(system.dims, system.batch,
                                  len(system.data), system.batches)["total"]
    ctx = Context(trace=t, lo=lo, hi=hi, devices=ids, rounds=rounds,
                  window_s=window_s, chips=len(ids),
                  flops_per_round=per_round,
                  peaks=hw.peaks(devices[0].device_kind))
    metrics = {}
    for mdef in cell_metrics(spec, name, "per_layer"):
        v = load_metric(mdef["name"]).read(ctx)
        if v is None:
            print(f"metric {mdef['name']}: nothing to read",
                  file=sys.stderr)
            continue
        metrics[mdef["name"]] = {"value": v, "unit": mdef["unit"]}
    busy = [tr.busy_ns(t.ops.get(d, []), lo, hi) for d in ids]
    ops, idle = {}, {}
    for d in ids:
        evs = t.ops.get(d, [])
        for k, v in tr.time_by_op(evs, t.modules.get(d, []), lo,
                                  hi).items():
            ops[k] = ops.get(k, 0) + v / len(ids)
        for k, v in tr.attribute_gaps(tr.gaps(evs, lo, hi),
                                      t.spans).items():
            idle[k] = idle.get(k, 0) + v / len(ids)
    breakdown = {"device_ops": tr.top(ops), "idle_gaps": tr.top(idle)}
    return metrics, breakdown, sum(busy) / len(busy) / 1e9, (hi - lo) / 1e9
