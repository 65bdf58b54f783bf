"""Drive one benchmark cell on the CPU, skipping ``run.py``'s look for a
chip: everything else is the run as on the chip, at whatever size the
cell's configuration gives. For tests only.

    JAX_PLATFORMS=cpu python benchmark/tests/cpu_cell.py <checkout> <workload> [seconds] [trace]
"""

import json
import os
import sys
import time


def run_cpu(checkout: str, workload: str, seconds: float = 1.0, trace: bool = False) -> dict:
    bench = os.path.join(checkout, "benchmark")
    for p in (checkout, bench):
        if p not in sys.path:
            sys.path.insert(0, p)
    import harness
    import run

    run.configure_cache()
    import jax

    events = harness.driver.CompileEvents()
    events.install()
    devices = jax.devices()
    result = harness.run_cell(checkout, workload, seconds, trace, devices[:1],
                              events, time.monotonic())
    return run.build_line(result, devices, trace)


if __name__ == "__main__":
    args = sys.argv[1:]
    line = run_cpu(args[0], args[1], float(args[2]) if len(args) > 2 else 1.0,
                   bool(int(args[3])) if len(args) > 3 else False)
    print(json.dumps(line))
