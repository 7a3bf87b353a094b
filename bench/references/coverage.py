"""Plain reference of maximum k-cover (GreedyML paper §6).

    f(S) = | union of the sets in S |

Sets are packed uint32 bitmaps over the universe. The marginal gain of c
given S is popcount(c AND NOT covered(S)), an exact integer. Greedy adds
the set of largest gain while that gain is positive.

- `replay` / `greedy` / `value`: exact integer arithmetic on the host (the
  comparison), sharing nothing with the system under test.
- `device_greedy`: the reference put in the program's place with jax, for
  the controls and for planted faults. `precision="bf16"` rounds the
  gains to bfloat16 before the argmax, one step below the float32 in
  which the program reports them; `"fp8"` to float8 (e5m2), the step
  below that.
"""
from __future__ import annotations

from typing import Optional

import numpy as np

NAME = "coverage"


def _count(words: np.ndarray) -> np.ndarray:
    return np.bitwise_count(words).sum(axis=-1, dtype=np.int64)


def value(ground, gvalid, sel) -> float:
    """Items covered by the chosen bitmaps `sel` (m, W)."""
    del ground, gvalid
    sel = np.asarray(sel, np.uint32)
    if not len(sel):
        return 0.0
    return float(_count(np.bitwise_or.reduce(sel, axis=0)))


class _State:
    def __init__(self, cands, cvalid):
        self.c = np.asarray(cands, np.uint32)
        self.open = np.asarray(cvalid, bool).copy()
        self.gains = _count(self.c)
        self.covered = np.zeros(self.c.shape[1], np.uint32)

    def best(self):
        g = np.where(self.open, self.gains, -1)
        i = int(np.argmax(g))
        return float(g[i]), i

    def gain(self, c: int) -> float:
        return float(self.gains[c])

    def add(self, c: int) -> None:
        new = self.c[c] & ~self.covered
        nz = np.nonzero(new)[0]
        if len(nz):
            self.gains -= _count(self.c[:, nz] & new[nz])
        self.covered |= self.c[c]
        self.open[c] = False


def greedy(ground, gvalid, cands, cvalid, k: int):
    st = _State(cands, cvalid)
    picks = []
    for _ in range(k):
        g, i = st.best()
        if not g > 0:
            break
        picks.append(i)
        st.add(i)
    return picks


def replay(ground, gvalid, cands, cvalid, picks, pvalid) -> dict:
    """Teacher-forced check: at each step, the gap by which the program's
    pick covers fewer new items than the best set, given the program's
    earlier picks; after the program stops, the best gain left. A share
    of the first step's best gain; exactly 0 for a correct greedy."""
    del ground, gvalid
    picks = [int(p) for p in picks]
    pvalid = [bool(v) for v in pvalid]
    cvalid = np.asarray(cvalid, bool)
    taken = [p for p, ok in zip(picks, pvalid) if ok]
    if any(not (0 <= p < len(cvalid) and cvalid[p]) for p in taken) or \
            len(set(taken)) != len(taken) or \
            any(pvalid[i + 1] and not pvalid[i]
                for i in range(len(pvalid) - 1)):
        return {"gap": float("inf")}
    st = _State(cands, cvalid)
    g1, worst = None, 0.0
    for p, ok in zip(picks, pvalid):
        best, _ = st.best()
        if g1 is None:
            g1 = best if best > 0 else 1.0
        worst = max(worst, ((best - st.gain(p)) if ok else max(best, 0.0))
                    / g1)
        if not ok:
            break
        st.add(p)
    return {"gap": worst}


def _round(x, bits: int):
    """x rounded to `bits` significant bits, to nearest: the rounding of a
    float format with that many, spelled out so that every backend
    rounds the same (a cast there and back may be folded away)."""
    import jax.numpy as jnp
    m, e = jnp.frexp(x)
    scale = 2.0 ** bits
    return jnp.ldexp(jnp.round(m * scale) / scale, e)


SIGNIFICANT_BITS = {"bf16": 8, "fp8": 3}     # bfloat16, float8 e5m2


def device_greedy(cands, cvalid, k: int, ground=None, gvalid=None, *,
                  precision: str = "exact", fault: Optional[str] = None):
    """Greedy on the device: (picks (k,) i32, valid (k,) bool, value ()).
    `fault`: 'stale' (covered words never updated), 'half' (gains over the
    first half of each bitmap's words)."""
    import jax
    import jax.numpy as jnp
    del ground, gvalid
    c = jnp.asarray(cands, jnp.uint32)
    w = c.shape[1]
    if fault == "half":
        c_gain = c[:, :w // 2]
    else:
        c_gain = c

    def step(carry, _):
        cov, open_ = carry
        gains = jnp.sum(jax.lax.population_count(
            c_gain & ~cov[:c_gain.shape[1]]).astype(jnp.int32), axis=1)
        gains = gains.astype(jnp.float32)
        if precision in SIGNIFICANT_BITS:
            gains = _round(gains, SIGNIFICANT_BITS[precision])
        gains = jnp.where(open_, gains, -jnp.inf)
        best = jnp.argmax(gains)
        ok = gains[best] > 0
        if fault != "stale":
            cov = jnp.where(ok, cov | c[best], cov)
        open_ = open_.at[best].set(open_[best] & ~ok)
        return (cov, open_), (best.astype(jnp.int32), ok)

    (cov, _), (picks, ok) = jax.lax.scan(
        step, (jnp.zeros((w,), jnp.uint32), jnp.asarray(cvalid, bool)),
        None, length=k)
    value_ = jnp.sum(jax.lax.population_count(cov).astype(jnp.int32))
    return picks, ok, value_.astype(jnp.float32)
