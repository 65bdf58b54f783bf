"""Run one benchmark cell on the accelerator and print its result line.

    python3 benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

One process holds the chip. With no accelerator, or fewer chips than the
cell asks for, it exits non-zero and prints no result; there is no CPU
fallback. Progress goes to standard error; the last lines there are the
numbers compared with the reference, each beside its limit. The last line
of standard output is the result object.

Set-up (``setup_s``) runs from the start of this process to the window's
start: imports, the compile cache, the model and its warm checks. JAX's
persistent compile cache is always ``benchmark/out/jax_cache`` in this
checkout, whatever ``JAX_COMPILATION_CACHE_DIR`` said before: the variable
is set to that path, so the program's own cache rule finds the same one.
Every program is cached, however quickly it compiled.
"""

import time

PROCESS_START = time.monotonic()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
CACHE_DIR = os.path.join(HERE, "out", "jax_cache")


def configure_cache() -> str:
    os.environ["JAX_COMPILATION_CACHE_DIR"] = CACHE_DIR
    import jax

    jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return CACHE_DIR


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = p.parse_args(argv)

    if ROOT not in sys.path:
        sys.path.insert(0, ROOT)
    sys.path.insert(0, HERE)
    import harness

    cell = harness.load_cell(ROOT, args.workload)
    cache = configure_cache()
    import jax

    devices = jax.devices()
    if devices[0].platform == "cpu":
        print("benchmark: no accelerator (JAX selected the CPU); "
              "the benchmark never runs on the CPU", file=sys.stderr)
        return 1
    if len(devices) < cell.chips:
        print(f"benchmark: {args.workload} needs {cell.chips} chips, "
              f"JAX sees {len(devices)}", file=sys.stderr)
        return 1
    harness.log(f"{args.workload} seed {args.seed} seconds {args.seconds} "
                f"trace {args.trace}; cache {cache}; {devices[0].device_kind} "
                f"x{len(devices)}")
    events = harness.driver.CompileEvents()
    events.install()
    result = harness.run_cell(ROOT, args.workload, args.seconds, bool(args.trace),
                              devices[:cell.chips], events, PROCESS_START)
    print(json.dumps(build_line(result, devices, bool(args.trace))), flush=True)
    for name, c in result["compared"].items():
        print(f"compared {name} {c['value']} limit {c['limit']}", file=sys.stderr)
    print(f"correct {result['correct']}", file=sys.stderr, flush=True)
    return 0


def build_line(result: dict, devices, trace: bool) -> dict:
    """The result object, with ``device`` from JAX and the compared
    numbers last."""
    device = {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
        "memory_peak_bytes": result["memory_peak_bytes"],
    }
    if trace and "busy_s" in result:
        device["busy_s"] = result["busy_s"]
        device["window_s"] = result["window_s"]
    line = {k: result[k] for k in ("correct", "attempted", "failed", "metrics")}
    line["device"] = device
    if "breakdown" in result:
        line["breakdown"] = result["breakdown"]
    line["compared"] = result["compared"]
    return line


if __name__ == "__main__":
    sys.exit(main())
