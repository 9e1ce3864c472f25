"""The one traffic generator: a mix file's parameters -> op batches.

A mix (``bench/traffic/<mix>.json``) fixes the add share ``p_add`` of
each width-W batch, the distribution of the resident keys loaded before
the window (``load``), and that of the keys added in the mix (``add``).
Every tick carries the same counts -- ``n_add`` live adds and ``n_rm``
removeMin requests -- so each seed asks for the same work and only the
keys differ.

``add`` is one key distribution, or a list of parts, each a
distribution with a ``share`` of the tick's adds (the shares sum to 1);
every tick holds each part's count of adds, in a random order.  The
distributions:

* ``uniform`` -- keys uniform over ``[lo, hi)``;
* ``hold`` -- Jones's hold model (CACM 29(4), 1986) with exponential
  increments, closed loop: each add lands an exponential increment of
  mean ``mean_spacings`` key spacings above the clock, the largest key
  served so far (a simulation's now), so adds land just above the
  current minimum;
* ``urgent`` -- the same increments below the clock: adds keyed under
  the current minimum.

A key spacing is the load's span over the resident depth.  Any part may
set ``quantum``, in key spacings: its keys are rounded down to
multiples of it, so they tie.

Every draw is stratified: ``n`` keys are one from each of ``n`` strata
of equal probability, in a random order.  So each seed loads nearly the
same resident multiset and sends each tick nearly the same multiset of
adds (or increments), in another order and with other low digits.
Where even those digits change the work, as they do the hold model's
(they decide how often the head runs dry, and each moveHead costs ~8
ticks), a distribution sets ``"jitter": false``: each stratum's key is
then its middle, every seed draws the same multisets, and the seed
changes only their order and so the payload ids the keys carry.

Batches are made in blocks of ``BLOCK`` ticks, each from its own
generator seeded by ``(seed, block)``, so a tick's keys do not depend
on how many blocks were made before the window.  A block holds what
does not depend on the run (uniform keys, increments); :meth:`Traffic.keys`
adds the clock when the tick is sent.

Payload ids come from :class:`IdPool`: unique among the keys resident
at once and below the payload bound, and recycled when a key is served.
"""

from __future__ import annotations

from typing import NamedTuple, Optional

import numpy as np

#: ticks per generated block
BLOCK = 64

#: stream tags of the seed sequence (load keys, mix blocks)
_LOAD, _MIX = 0, 1

#: key distributions of the adds, and the sign of the clock in each
#: (0: absolute keys; +1 / -1: increments above / below the clock)
DISTS = {"uniform": 0, "hold": 1, "urgent": -1}


def _entropy(seed: int) -> int:
    """Any whole number as a non-negative seed entropy."""
    return int(seed) & ((1 << 64) - 1)


def stratified(rng, shape, jitter: bool = True) -> np.ndarray:
    """Uniform draws on [0, 1), the last axis one from each of its
    ``shape[-1]`` equal strata, in a random order along that axis;
    without ``jitter`` each stratum's draw is its middle."""
    n = shape[-1]
    off = rng.random(shape) if jitter else np.full(shape, 0.5)
    u = (np.arange(n) + off) / n
    return rng.permuted(u, axis=-1)


class Block(NamedTuple):
    """``BLOCK`` ticks of adds: ``base`` f64 ``[BLOCK, n_add]`` and, for
    mixes keyed on the clock, ``sign`` (the clock's factor per add)."""

    base: np.ndarray
    sign: Optional[np.ndarray]


class Traffic:
    """The batches of one mix at one width, resident depth and seed."""

    def __init__(self, mix: dict, *, width: int, resident: int, seed: int):
        p_add = float(mix["p_add"])
        if not 0.0 <= p_add <= 1.0:
            raise ValueError(f"p_add {p_add} outside [0, 1]")
        self.width = int(width)
        self.resident = int(resident)
        self.seed = _entropy(seed)
        self.n_add = int(round(self.width * p_add))
        self.n_rm = self.width - self.n_add
        self.load_dist = dict(mix["load"])
        if self.load_dist["dist"] != "uniform":
            raise ValueError("the load draws uniform keys")
        #: where the clock starts: the load's lowest key
        self.clock0 = float(self.load_dist["lo"])
        add = mix["add"]
        parts = [dict(add, share=1.0)] if isinstance(add, dict) else add
        shares = [float(p["share"]) for p in parts]
        if min(shares) <= 0 or abs(sum(shares) - 1.0) > 1e-9:
            raise ValueError(f"add shares {shares} do not sum to 1")
        for p in parts:
            if p["dist"] not in DISTS:
                raise ValueError(f"unknown key distribution {p['dist']!r}")
        counts = [int(round(s * self.n_add)) for s in shares[:-1]]
        counts.append(self.n_add - sum(counts))
        if min(counts) < 0:
            raise ValueError(f"add shares {shares} give counts {counts}")
        self.parts = [(dict(p), n) for p, n in zip(parts, counts)]
        #: whether a tick's keys depend on the keys served before it
        self.closed_loop = any(DISTS[p["dist"]] for p, _ in self.parts)

    # -- the load ---------------------------------------------------------

    def load_keys(self) -> np.ndarray:
        """The ``resident`` keys loaded before the mix, f32."""
        d = self.load_dist
        rng = np.random.default_rng([self.seed, _LOAD])
        lo, hi = float(d["lo"]), float(d["hi"])
        u = stratified(rng, (self.resident,), d.get("jitter", True))
        keys = lo + (hi - lo) * u
        return keys.astype(np.float32)

    # -- the mix ----------------------------------------------------------

    def _spacing(self) -> float:
        d = self.load_dist
        return (float(d["hi"]) - float(d["lo"])) / max(self.resident, 1)

    def _part(self, rng, d: dict, n: int) -> np.ndarray:
        u = stratified(rng, (BLOCK, n), d.get("jitter", True))
        step = self._spacing()
        if d["dist"] == "uniform":
            lo, hi = float(d["lo"]), float(d["hi"])
            base = lo + (hi - lo) * u
        else:
            # exponential increments by the inverse of their distribution
            base = -step * float(d["mean_spacings"]) * np.log1p(-u)
        if "quantum" in d:
            q = step * float(d["quantum"])
            base = np.floor(base / q) * q
        return base

    def block(self, b: int) -> Block:
        """The adds of mix ticks ``[b * BLOCK, (b + 1) * BLOCK)``."""
        rng = np.random.default_rng([self.seed, _MIX, int(b)])
        if len(self.parts) == 1:
            d, n = self.parts[0]
            sign = DISTS[d["dist"]]
            base = self._part(rng, d, n)
            return Block(base, np.full(base.shape, float(sign))
                         if sign else None)
        bases = [self._part(rng, d, n) for d, n in self.parts]
        signs = [np.full((BLOCK, n), float(DISTS[d["dist"]]))
                 for d, n in self.parts]
        order = rng.permuted(np.tile(np.arange(self.n_add), (BLOCK, 1)),
                             axis=-1)
        base = np.take_along_axis(np.concatenate(bases, axis=1), order, 1)
        sign = np.take_along_axis(np.concatenate(signs, axis=1), order, 1)
        return Block(base, sign if self.closed_loop else None)

    def keys(self, blk: Block, i: int, clock: float) -> np.ndarray:
        """Tick ``i`` of the block at the clock ``clock``: f32 keys."""
        if blk.sign is None:
            return blk.base[i].astype(np.float32)
        return (blk.base[i] + blk.sign[i] * clock).astype(np.float32)


class IdPool:
    """Payload ids in ``[0, bound)``, unique among those handed out and
    not yet given back.  First in, first out, so an id is reused as
    late as possible."""

    def __init__(self, bound: int):
        self.bound = int(bound)
        self._ring = np.arange(self.bound, dtype=np.int32)
        self._out = np.zeros(self.bound, bool)
        self._head = 0          # next id to hand out
        self._free = self.bound

    def __len__(self) -> int:
        return self._free

    def take(self, n: int) -> np.ndarray:
        if n > self._free:
            raise RuntimeError(f"payload ids exhausted: {n} > {self._free}")
        idx = (self._head + np.arange(n)) % self.bound
        ids = self._ring[idx]
        self._head = (self._head + n) % self.bound
        self._free -= n
        self._out[ids] = True
        return ids

    def give(self, ids) -> None:
        """Return served ids; an id that is not out (never handed out,
        or served twice) is ignored here and caught by the check."""
        ids = np.unique(np.asarray(ids, np.int64))
        ids = ids[(ids >= 0) & (ids < self.bound)]
        ids = ids[self._out[ids]]
        if not ids.size:
            return
        self._out[ids] = False
        tail = (self._head + self._free + np.arange(ids.size)) % self.bound
        self._ring[tail] = ids
        self._free += ids.size
