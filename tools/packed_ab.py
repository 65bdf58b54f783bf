"""Engine-level A/B: u64-packed sort lanes vs u32 pairs, on this backend.

Each variant runs in its own SUBPROCESS: STPU_SORTEDSET_KEYS is a
trace-time constant (the documented process-restart A/B convention) and
packed mode needs ``jax_enable_x64`` enabled before first backend use —
neither may leak into the other variant. The child runs a full
count-checked 2pc rm=N check on the sorted engine (warm pass compiles,
measured pass times) and prints one JSON line; the parent just relays.

Usage: python tools/packed_ab.py [rm] [--cpu]
"""

from __future__ import annotations

import json
import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

CHILD = r"""
import json, os, sys, time
sys.path.insert(0, {repo!r})
import jax
if {cpu!r} == "cpu":
    jax.config.update("jax_platforms", "cpu")
else:
    from stateright_tpu.backend import configure_compile_cache
    configure_compile_cache()
if os.environ.get("STPU_SORTEDSET_KEYS") == "packed":
    jax.config.update("jax_enable_x64", True)
from stateright_tpu.models.two_phase_commit import PackedTwoPhaseSys
sys.path.insert(0, {repo!r})
from bench import EXPECTED_2PC as EXPECTED

rm = {rm}
fcap, tcap = 1 << 19, 1 << 22
if {cpu!r} == "cpu":
    rm = min(rm, 6)
    fcap, tcap = 1 << 15, 1 << 17
m = PackedTwoPhaseSys(rm)
t0 = time.monotonic()
m.checker().spawn_xla(dedup="sorted", frontier_capacity=fcap, table_capacity=tcap).join()
warm = time.monotonic() - t0
c = m.checker().spawn_xla(dedup="sorted", frontier_capacity=fcap, table_capacity=tcap)
t0 = time.monotonic()
c.join()
dt = time.monotonic() - t0
want = EXPECTED.get(rm)
ok = want is None or (c.state_count(), c.unique_state_count()) == want
print(json.dumps({{
    "keys": os.environ.get("STPU_SORTEDSET_KEYS", "pair"),
    "rm": rm, "warm_s": round(warm, 2), "measured_s": round(dt, 3),
    "gen_per_s": round(c.state_count() / dt, 1),
    "gen": c.state_count(), "uniq": c.unique_state_count(),
    "count_ok": bool(ok),
}}))
"""


def main() -> None:
    cpu = "cpu" if "--cpu" in sys.argv else "tpu"
    if "--cpu" in sys.argv:
        sys.argv.remove("--cpu")
    rm = int(sys.argv[1]) if len(sys.argv) > 1 else 8
    code = CHILD.format(repo=REPO, cpu=cpu, rm=rm)
    for keys in ("pair", "packed"):
        env = dict(os.environ)
        env["STPU_SORTEDSET_KEYS"] = keys
        env["STPU_SORTEDSET_VALUES"] = "sort"
        proc = subprocess.run(
            [sys.executable, "-c", code],
            env=env,
            capture_output=True,
            text=True,
            timeout=2400,
        )
        line = (proc.stdout.strip().splitlines() or ["(no output)"])[-1]
        print(line, flush=True)
        if proc.returncode != 0:
            print(
                json.dumps(
                    {"keys": keys, "error": proc.stderr.strip()[-400:]}
                ),
                flush=True,
            )


if __name__ == "__main__":
    main()
