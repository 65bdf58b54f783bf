"""Localize the on-chip paxos count drift to a specific program shape.

Round-5 on-chip finding (tpu_paxos_ab.jsonl): paxos 2c/3s drifts on TPU
under BOTH visited-set structures and BOTH ladders, while the same engine
is count-exact on CPU and 2pc is count-exact on the same chip:

  - sorted+ramp inflates to 33,752/17,198 — byte-distinct table keys
    (audit clean), the exact totals the round-3 HASH engine produced,
    so the divergence is upstream of the insert;
  - sorted+jump (which replays levels in larger reused buckets)
    under-generates from identical frontier widths (899 gen from 297
    rows where the oracle makes 925 from 286) — the expansion itself
    computes differently at some bucket shapes.

This tool bisects by stage and shape:

  capture (CPU): run the level-synchronous engine one level per
    dispatch, snapshotting the exact frontier rows fed to each level and
    the successor grid + validity the CPU program computes from them.

  replay (TPU): feed the captured frontiers to the same jitted
    programs the engine builds — fingerprint, bare expand (vmap of
    packed_step), expand+transpose+reshape (the engine's fused "rows"
    layout), and the "planes" layout variant — at several bucket
    capacities, and bit-compare against the CPU truth.

A mismatch names the level, bucket, stage, lane, and word — the shape
to pin and the lowering to avoid (the method that found the XLA:CPU
transpose-into-vmap miscompile, xla.py:_build_superstep_planes).

Usage:
  python tools/paxos_diag.py capture        # CPU; writes paxos_diag.npz
  python tools/paxos_diag.py replay         # on the chip; reads the npz
  python tools/paxos_diag.py replay --cpu   # control: must be all-zero
Run replay under `timeout` — a hung dispatch never returns.
"""

from __future__ import annotations

import os
import sys

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

NPZ = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                   "paxos_diag.npz")
# Levels around the first observed divergences (frontier widths 26..867).
CAPTURE_DEPTHS = tuple(range(4, 11))
REPLAY_CAPS = (64, 256, 1024, 2048, 4096)


def _step3(model):
    import jax.numpy as jnp

    def step3(words):
        out = model.packed_step(words)
        if len(out) == 3:
            return out
        nxt, valid = out
        return nxt, valid, jnp.zeros_like(valid)

    return step3


def capture() -> None:
    import jax

    jax.config.update("jax_platforms", "cpu")
    import jax.numpy as jnp

    from stateright_tpu.models.paxos import PackedPaxos
    from stateright_tpu.ops import fphash

    model = PackedPaxos(2, 3)
    ck = model.checker().spawn_xla(
        frontier_capacity=1 << 12, table_capacity=1 << 16,
        dedup="sorted", ladder="ramp", levels_per_dispatch=1,
    )
    step3 = _step3(model)
    expand = jax.jit(lambda f: jax.vmap(step3)(f))
    out: dict = {}
    while not ck.is_done():
        depth = ck._depth
        n = ck._frontier_count
        if depth in CAPTURE_DEPTHS and n > 0:
            rows = np.asarray(ck._frontier)[:n]
            nxt, valid, _ = expand(jnp.asarray(rows))
            fhi, flo = fphash.fingerprint_words(jnp.asarray(rows), jnp)
            out[f"frontier_{depth}"] = rows
            out[f"nxt_{depth}"] = np.asarray(nxt)
            out[f"valid_{depth}"] = np.asarray(valid)
            out[f"fhi_{depth}"] = np.asarray(fhi)
            out[f"flo_{depth}"] = np.asarray(flo)
        ck._run_block()
    assert (ck.state_count(), ck.unique_state_count()) == (32_971, 16_668), (
        ck.state_count(), ck.unique_state_count())
    out["depths"] = np.asarray(
        [d for d in CAPTURE_DEPTHS if f"frontier_{d}" in out], np.int32)
    np.savez_compressed(NPZ, **out)
    print(f"captured {len(out['depths'])} levels -> {NPZ}; "
          f"counts exact on {jax.default_backend()}")


def replay() -> None:
    import jax

    if "--cpu" in sys.argv:
        jax.config.update("jax_platforms", "cpu")
    else:
        from stateright_tpu.backend import configure_compile_cache

        configure_compile_cache()
    import jax.numpy as jnp

    from stateright_tpu.models.paxos import PackedPaxos
    from stateright_tpu.ops import fphash

    model = PackedPaxos(2, 3)
    A, W = model.max_actions, model.state_words
    step3 = _step3(model)
    data = np.load(NPZ)
    print(f"platform={jax.devices()[0].platform} A={A} W={W}", flush=True)

    # The engine's two expand lowerings, at fixed bucket f_cap
    # (xla.py:_build_superstep_planes step 2-3).
    def grid_rows(f):
        nxt, valid, _ = jax.vmap(step3)(f)  # [F, A, W]
        return jnp.transpose(nxt, (2, 1, 0)).reshape(W, A * f.shape[0]), valid

    def grid_planes(f):
        nxt, valid, _ = jax.vmap(step3, out_axes=(2, 0, 0))(f)  # [A, W, F]
        return jnp.transpose(nxt, (1, 0, 2)).reshape(W, A * f.shape[0]), valid

    fails = 0
    for depth in data["depths"]:
        rows = data[f"frontier_{depth}"]
        n = rows.shape[0]
        want_nxt = data[f"nxt_{depth}"]          # [n, A, W]
        want_valid = data[f"valid_{depth}"]
        want_fhi, want_flo = data[f"fhi_{depth}"], data[f"flo_{depth}"]
        for cap in REPLAY_CAPS:
            if cap < n:
                continue
            pad = np.zeros((cap, W), np.uint32)
            pad[:n] = rows
            f = jnp.asarray(pad)

            fhi, flo = jax.jit(lambda x: fphash.fingerprint_words(x, jnp))(f)
            bad = int(np.sum((np.asarray(fhi)[:n] != want_fhi)
                             | (np.asarray(flo)[:n] != want_flo)))
            if bad:
                fails += 1
                print(f"FAIL fp      depth={depth} cap={cap}: {bad}/{n} lanes")

            nxt, valid, _ = jax.jit(lambda x: jax.vmap(step3)(x))(f)
            bad_v = int(np.sum(np.asarray(valid)[:n] != want_valid))
            bad_w = int(np.sum(np.asarray(nxt)[:n] != want_nxt))
            if bad_v or bad_w:
                fails += 1
                print(f"FAIL expand  depth={depth} cap={cap}: "
                      f"{bad_v} valid lanes, {bad_w} words differ")
                _detail(np.asarray(nxt)[:n], want_nxt,
                        np.asarray(valid)[:n], want_valid)

            for name, fn in (("grid-rows", grid_rows),
                             ("grid-planes", grid_planes)):
                grid, valid = jax.jit(fn)(f)
                g = np.asarray(grid).reshape(W, A, cap)
                got = np.transpose(g[:, :, :n], (2, 1, 0))  # [n, A, W]
                bad_v = int(np.sum(np.asarray(valid)[:n] != want_valid))
                bad_w = int(np.sum(got != want_nxt))
                if bad_v or bad_w:
                    fails += 1
                    print(f"FAIL {name} depth={depth} cap={cap}: "
                          f"{bad_v} valid lanes, {bad_w} words differ")
                    _detail(got, want_nxt, np.asarray(valid)[:n], want_valid)
            print(f"done depth={depth} cap={cap}", flush=True)
    print(f"{'CLEAN' if fails == 0 else f'{fails} FAILING (stage, shape) pairs'}")
    sys.exit(0 if fails == 0 else 2)


def _detail(got, want, got_valid, want_valid, k: int = 5) -> None:
    """First few mismatching (state, action) sites, valid-lane and word."""
    dv = np.argwhere(got_valid != want_valid)
    for s, a in dv[:k]:
        print(f"    valid[{s},{a}]: got {got_valid[s, a]} want {want_valid[s, a]}")
    dw = np.argwhere((got != want).any(axis=2) & want_valid.astype(bool))
    for s, a in dw[:k]:
        ws = np.argwhere(got[s, a] != want[s, a]).ravel()
        print(f"    nxt[{s},{a}] words {ws.tolist()}: "
              f"got {[hex(int(got[s, a, w])) for w in ws[:4]]} "
              f"want {[hex(int(want[s, a, w])) for w in ws[:4]]}")


def main() -> None:
    if "capture" in sys.argv:
        capture()
    elif "replay" in sys.argv:
        replay()
    else:
        print(__doc__)
        sys.exit(1)


if __name__ == "__main__":
    main()
