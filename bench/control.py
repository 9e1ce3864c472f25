"""The control of ``correct``: the plain reference queue, computed in
bfloat16, put in the program's place.

The configurations state f32 keys.  The nearest precision below is
bfloat16, the step that would tempt a later change: :class:`Bf16Queue`
is an exact priority queue (one sort per tick) that holds its keys in
bfloat16, so it serves rounded keys, and ties among keys that round
alike in any order.  A run with it in the program's place must come out
not ``correct``; this is how the limits of the check were shown to
separate a sound queue from a lower-precision one.

    python3 bench/control.py --workload <cell> --seconds <s> --seeds <n> ...

runs the control through the whole harness, one run per seed in one
process, on the first TPU chip, and prints each run's checks.  The
benchmark's own runs never run it.
"""

from __future__ import annotations

import sys
from pathlib import Path
from typing import NamedTuple

ROOT = Path(__file__).resolve().parents[1]


class Result(NamedTuple):
    rm_keys: object
    rm_vals: object
    rm_served: object


class Bf16Queue:
    """An exact queue over (bfloat16 key, payload) pairs, with the
    engine surface the harness drives."""

    kind = "bf16_reference"

    def __init__(self, config: dict, devices=None):
        import jax
        import jax.numpy as jnp

        self.width = int(config["spec"]["width"])
        cap = int(config["resident"]) + 2 * self.width
        self.capacity = -(-cap // 1024) * 1024
        w, c = self.width, self.capacity

        def one(state, xs):
            keys, vals = state
            ak, av, am, rm = xs
            ak = jnp.where(am, ak, jnp.inf).astype(jnp.bfloat16)
            av = jnp.where(am, av, -1)
            sk, sv = jax.lax.sort((jnp.concatenate([keys, ak]),
                                   jnp.concatenate([vals, av])), num_keys=1)
            n = jnp.minimum(rm, jnp.isfinite(sk).sum())
            got = jnp.arange(w) < n
            out = Result(jnp.where(got, sk[:w].astype(jnp.float32), jnp.inf),
                         jnp.where(got, sv[:w], -1), got)
            pad_k = jnp.concatenate([sk, jnp.full((w,), jnp.inf, sk.dtype)])
            pad_v = jnp.concatenate([sv, jnp.full((w,), -1, sv.dtype)])
            keys = jax.lax.dynamic_slice(pad_k, (n,), (c,))
            vals = jax.lax.dynamic_slice(pad_v, (n,), (c,))
            return (keys, vals), out

        @jax.jit
        def tick_n(state, keys, vals, mask, rm):
            return jax.lax.scan(one, state, (keys, vals, mask, rm))

        self._tick_n = tick_n

    def init(self, *, seed: int = 0):
        import jax.numpy as jnp

        del seed
        return (jnp.full((self.capacity,), jnp.inf, jnp.bfloat16),
                jnp.full((self.capacity,), -1, jnp.int32))

    def tick_n(self, state, keys, vals, mask, rm):
        return self._tick_n(state, keys, vals, mask, rm)

    def stats(self, state):
        return None

    def resident(self, state):
        import jax.numpy as jnp

        keys, vals = state
        return keys.astype(jnp.float32), vals, jnp.isfinite(keys)

    def size(self, state):
        import jax.numpy as jnp

        return jnp.isfinite(state[0]).sum()


def main(argv=None) -> int:
    import argparse

    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    import json

    import jax

    from bench import harness

    devs = jax.devices()
    if devs[0].platform != "tpu":
        sys.exit(f"control: needs a TPU; JAX found {devs[0].platform!r}")
    bench = harness.load_benchmark(ROOT)
    for seed in args.seeds:
        r = harness.run_cell(bench, args.workload, seed=seed,
                             seconds=args.seconds, traced=False,
                             devices=devs[:1], make=Bf16Queue)
        print(json.dumps({"workload": args.workload, "seed": seed,
                          "correct": r["correct"], "checks": r["checks"]}),
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
