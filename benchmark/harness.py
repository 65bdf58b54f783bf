"""One benchmark cell, from its files to its result line.

Everything that belongs to one configuration, traffic mix or metric sits in
a file of its own, found by the name ``BENCHMARK.json`` gives it:

- ``configs/<config>.json``: the configuration's sizes, the model class and
  the keys its constructor takes, the ``spawn_xla`` capacities, and the
  reference module with its parameters and control;
- ``traffic/<mix>.json``: the parameters ``driver.py`` reads;
- ``metrics/<metric>.py``: a reader, ``read(run) -> number or None``;
- ``reference/<module>.py``: ``explore``, ``replay`` and ``from_program``.
"""

from __future__ import annotations

import gc
import importlib
import importlib.util
import json
import os
import shutil
import sys
import time
from dataclasses import dataclass
from typing import Any, Dict, List, Optional

HERE = os.path.dirname(os.path.abspath(__file__))
if HERE not in sys.path:
    sys.path.insert(0, HERE)

import compare  # noqa: E402
import driver  # noqa: E402
import trace_reduce  # noqa: E402

#: The benchmark's own host annotations, which label the trace's idle gaps.
ANNOTATIONS = ("spawn_xla", "join", "verdict")


def log(msg: str) -> None:
    print(f"[bench] {msg}", file=sys.stderr, flush=True)


def load_module(path: str):
    name = "bench_" + os.path.relpath(path, HERE).replace(os.sep, "_").replace(".", "_")
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None:
        raise FileNotFoundError(path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


@dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    end_to_end: List[dict]
    per_layer: List[dict]


def _applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(root: str, workload: str) -> Cell:
    """The cell ``workload`` of ``<root>/BENCHMARK.json`` with its files."""
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r}; known: {sorted(cells)}")
    w = cells[workload]
    configs = {c["name"]: c for c in bench["configs"]}
    with open(os.path.join(root, configs[w["config"]]["file"])) as f:
        config = json.load(f)
    with open(os.path.join(HERE, "traffic", w["traffic"] + ".json")) as f:
        traffic = driver.validate_traffic(json.load(f))
    return Cell(
        name=workload,
        chips=int(w["chips"]),
        config=config,
        traffic=traffic,
        end_to_end=[m for m in bench["end_to_end"] if _applies(m, workload)],
        per_layer=[m for m in bench["per_layer"] if _applies(m, workload)],
    )


def make_model(config: dict):
    spec = config["model"]
    module_name, _, cls_name = spec["class"].rpartition(".")
    cls = getattr(importlib.import_module(module_name), cls_name)
    return cls(**{k: config[k] for k in spec["kwargs"]})


def reference_of(config: dict):
    ref = config["reference"]
    module = load_module(os.path.join(HERE, "reference", ref["module"] + ".py"))
    return module, {k: config[k] for k in ref["params"]}


@dataclass
class Run:
    """What the metric readers read."""

    cell: Cell
    checks: List[driver.Check]
    window_s: float
    setup_s: float
    setup_compiles: int
    memory_peak_bytes: Optional[int]
    trace: Optional[trace_reduce.Reduction]


def memory_peak(devices) -> Optional[int]:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use") for d in devices]
    peaks = [p for p in peaks if p is not None]
    return max(peaks) if peaks else None


def read_metrics(run: Run, metrics: List[dict]) -> Dict[str, dict]:
    out = {}
    for m in metrics:
        value = load_module(os.path.join(HERE, "metrics", m["name"] + ".py")).read(run)
        if value is not None:
            out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out


def run_cell(root: str, workload: str, seconds: float, trace: bool, devices,
             events: driver.CompileEvents, process_start: float) -> Dict[str, Any]:
    """Set-up, the window, then the comparison with the reference; returns
    the result object (without ``device``)."""
    cell = load_cell(root, workload)
    caps = cell.config["spawn_xla"]
    at_setup = events.snapshot()
    model = make_model(cell.config)
    n_warm = driver.warm(model, caps, cell.traffic, events)
    setup_compiles = driver.CompileEvents.compiles(at_setup, events.snapshot())

    trace_dir = None
    if trace:
        seconds = float(cell.traffic["trace_seconds"])
        trace_dir = os.path.join(HERE, "out", "trace", workload)
        shutil.rmtree(trace_dir, ignore_errors=True)
    window_start = time.monotonic()
    setup_s = window_start - process_start
    log(f"set-up {setup_s:.3f} s, {n_warm} warm checks, {setup_compiles} compiles, "
        f"program requests/cache hits {events.snapshot()}")
    at_window = events.snapshot()
    with driver.profiled(trace_dir):
        checks, checker, window_s = driver.run_window(model, caps, seconds)
    peak = memory_peak(devices)
    log(f"window {window_s:.3f} s, {len(checks)} checks, "
        f"{driver.CompileEvents.compiles(at_window, events.snapshot())} compiles "
        f"and {events.snapshot()[1] - at_window[1]} cache loads in it, peak bytes {peak}")
    slow = driver.slow_checks(checks)
    if slow:
        log(f"{len(slow)} slow checks (over 1.5 times the median), "
            f"{sum(c.end - c.start for _, c in slow):.3f} s in all")
    for i, c in slow[:10]:
        log(f"slow check {i}: {c.end - c.start:.3f} s at {c.start - checks[0].start:.3f} s "
            f"into the window; spawn_xla/join/verdict "
            f"{'/'.join(f'{p:.3f}' for p in c.phases)} s, cpu {c.cpu_s:.3f} s, "
            f"gc {c.gc_s:.3f} s, {c.preempted} involuntary context switches")

    reduction = None
    if trace:
        reduction = trace_reduce.reduce_trace(trace_dir, ANNOTATIONS)
        if reduction is not None:
            log(f"trace: busy {reduction.busy_s:.6f} s of {reduction.window_s:.6f} s, "
                f"{len(reduction.gaps)} idle gaps, longest {reduction.gaps[:5]}")

    # The comparison, after the window: the last check's visited set and
    # witness paths come to the host, the program's state is freed, then
    # the reference runs.
    t_ref = time.monotonic()
    audit = compare.audit_table(checker)
    paths = compare.discovery_paths(checker)
    del checker, model
    gc.collect()
    reference, params = reference_of(cell.config)
    ref = reference.explore(params)
    bad_paths = compare.witnesses(paths, reference, params, ref)
    numbers = compare.compare(checks, ref, audit, bad_paths)
    log(f"reference {ref}, audit {audit}, comparison {time.monotonic() - t_ref:.3f} s")
    for why in bad_paths:
        log(f"bad witness: {why}")

    run = Run(cell, checks, window_s, setup_s, setup_compiles, peak, reduction)
    result: Dict[str, Any] = {
        "correct": compare.is_correct(numbers),
        "attempted": len(checks),
        "failed": compare.failed_checks(checks, ref, numbers),
        "metrics": read_metrics(run, cell.per_layer if trace else cell.end_to_end),
        "memory_peak_bytes": peak,
    }
    if reduction is not None:
        result["busy_s"] = reduction.busy_s
        result["window_s"] = reduction.window_s
        result["breakdown"] = {
            "device_ops": sorted(reduction.programs.items(), key=lambda kv: -kv[1])[:10],
            "idle_gaps": reduction.idle_by_annotation()[:10],
        }
    result["compared"] = {
        k: {"value": v, "limit": compare.LIMITS[k]} for k, v in numbers.items()
    }
    return result
