"""Plain reference of k-medoid exemplar clustering (GreedyML paper §6.4).

    f(S) = 1/|V| * sum_v ( d(v, e0) - min_{e in S + {e0}} d(v, e) )

d is the Euclidean distance, e0 the origin, V the valid ground rows. The
marginal gain of c given S is 1/|V| * sum_v relu(m_v - d(v, c)), with m_v
the current minimum. Greedy adds the candidate of largest gain while that
gain is positive.

Two implementations, written from the definition and sharing nothing with
the system under test:

- `replay` / `greedy` / `value` on the host in float64 (the comparison).
  On large pools `replay` first ranks candidates on the device in float32
  at full precision and evaluates in float64 every candidate within
  `SCREEN` of the best; the pick itself is always evaluated in float64.
- `device_greedy` in float32 with jax: the reference put in the program's
  place, for the controls and for planted faults. `precision="high"`
  computes the cross products as three bf16 passes, the step below
  float32 at `highest`; `"bf16"` as one pass, the step below that.
"""
from __future__ import annotations

from typing import Optional, Tuple

import numpy as np

NAME = "kmedoid"
# float32 squared distances by the expansion |g|^2 + |c|^2 - 2<g, c> carry
# rounding noise of ~1e-7 of |g|^2 + |c|^2, so a point sits ~1e-3 from
# itself; below this share of the scale a square counts as 0, as it is in
# exact arithmetic (no two distinct points of the data lie that close)
NOISE_FLOOR = 2.0 ** -16
# candidates whose float32 gain lies within this share of the step's best
# are re-evaluated in float64; float32 gains are good to ~1e-5 of it. The
# screen returns the TOP best per step, so it assumes fewer than TOP
# candidates lie that close (the tests hold it equal to the full replay)
SCREEN = 1e-3
TOP = 64
FULL_ENTRIES = 1 << 24           # pools up to this many (N x C) run in f64


def _d64(g: np.ndarray, c: np.ndarray) -> np.ndarray:
    gn = np.einsum("ij,ij->i", g, g)[:, None]
    cn = np.einsum("ij,ij->i", c, c)[None, :]
    return np.sqrt(np.maximum(gn + cn - 2.0 * (g @ c.T), 0.0))


def value(ground, gvalid, sel) -> float:
    """f(S) in float64 on the valid ground rows; `sel` the (m, D) chosen
    payloads."""
    g = np.asarray(ground, np.float64)[np.asarray(gvalid, bool)]
    d0 = np.linalg.norm(g, axis=1)
    m = d0.copy()
    if len(sel):
        m = np.minimum(m, _d64(g, np.asarray(sel, np.float64)).min(axis=1))
    return float(np.mean(d0 - m)) if len(g) else 0.0


class _Full:
    """Exact float64 gains of every candidate, updated per pick."""

    def __init__(self, ground, gvalid, cands, cvalid):
        g = np.asarray(ground, np.float64)[np.asarray(gvalid, bool)]
        self.n_eff = max(len(g), 1)
        self.d = _d64(g, np.asarray(cands, np.float64))      # (N, C)
        self.mind = np.linalg.norm(g, axis=1)
        self.open = np.asarray(cvalid, bool).copy()
        self.gains = np.maximum(self.mind[:, None] - self.d, 0.0).sum(0) \
            / self.n_eff

    def best(self) -> Tuple[float, int]:
        g = np.where(self.open, self.gains, -np.inf)
        i = int(np.argmax(g))
        return float(g[i]), i

    def gain(self, c: int) -> float:
        return float(self.gains[c])

    def add(self, c: int) -> None:
        col = self.d[:, c]
        ch = col < self.mind
        if ch.any():
            old, new = self.mind[ch], col[ch]
            dd = self.d[ch]
            self.gains -= (np.maximum(old[:, None] - dd, 0.0)
                           - np.maximum(new[:, None] - dd, 0.0)).sum(0) \
                / self.n_eff
            self.mind[ch] = new
        self.open[c] = False


def greedy(ground, gvalid, cands, cvalid, k: int):
    """Reference greedy in float64: (picks, their count) — stops at the
    first step whose best gain is not positive."""
    st = _Full(ground, gvalid, cands, cvalid)
    picks = []
    for _ in range(k):
        g, i = st.best()
        if not g > 0:
            break
        picks.append(i)
        st.add(i)
    return picks


def _screen(ground, gvalid, cands, cvalid, picks, pvalid, top: int):
    """Float32 teacher-forced greedy on the device: at each step the `top`
    best gains and their candidates, given the program's earlier picks."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def run(g, gv, c, cv, p, pv):
        hi = jax.lax.Precision.HIGHEST
        gn = jnp.sum(g * g, axis=1)
        cn = jnp.sum(c * c, axis=1)
        d = _dist32(gn, cn, jnp.dot(g, c.T, precision=hi))
        n_eff = jnp.maximum(jnp.sum(gv), 1).astype(jnp.float32)
        m0 = jnp.where(gv, jnp.sqrt(gn), 0.0)

        def step(carry, x):
            m, open_ = carry
            pick, ok = x
            gains = jnp.sum(jnp.maximum(m[:, None] - d, 0.0), axis=0) / n_eff
            vals, idx = jax.lax.top_k(jnp.where(open_, gains, -jnp.inf), top)
            m = jnp.where(ok, jnp.minimum(m, d[:, pick]), m)
            open_ = open_.at[pick].set(open_[pick] & ~ok)
            return (m, open_), (vals, idx)

        _, out = jax.lax.scan(step, (m0, cv), (p, pv))
        return out

    vals, idx = run(jnp.asarray(ground, jnp.float32), jnp.asarray(gvalid),
                    jnp.asarray(cands, jnp.float32), jnp.asarray(cvalid),
                    jnp.asarray(picks, jnp.int32), jnp.asarray(pvalid))
    return np.asarray(vals, np.float64), np.asarray(idx)


def replay(ground, gvalid, cands, cvalid, picks, pvalid) -> dict:
    """Teacher-forced check of a greedy selection: at each step t, the gap
    by which the program's pick's float64 gain lies below the best
    candidate's, given the program's own picks before t; after the
    program stops, the best gain left (which should not be positive).
    Gaps are shares of the first step's best gain. Picks that are out of
    range, repeated or invalid give an infinite gap."""
    picks = [int(p) for p in picks]
    pvalid = [bool(v) for v in pvalid]
    cvalid = np.asarray(cvalid, bool)
    c_n = len(cvalid)
    bad = any(ok and not (0 <= p < c_n and cvalid[p])
              for p, ok in zip(picks, pvalid))
    taken = [p for p, ok in zip(picks, pvalid) if ok]
    if bad or len(set(taken)) != len(taken) or \
            any(pvalid[i + 1] and not pvalid[i]
                for i in range(len(pvalid) - 1)):
        return {"gap": float("inf")}
    gvalid = np.asarray(gvalid, bool)
    if gvalid.sum() * c_n <= FULL_ENTRIES:
        st = _Full(ground, gvalid, cands, cvalid)
        g1, worst = None, 0.0
        for p, ok in zip(picks, pvalid):
            best, _ = st.best()
            if g1 is None:
                g1 = best if best > 0 else 1.0
            worst = max(worst, ((best - st.gain(p)) if ok
                                else max(best, 0.0)) / g1)
            if not ok:
                break
            st.add(p)
        return {"gap": worst}
    return _replay_screened(ground, gvalid, cands, cvalid, picks, pvalid)


def _replay_screened(ground, gvalid, cands, cvalid, picks, pvalid) -> dict:
    safe = [p if ok else 0 for p, ok in zip(picks, pvalid)]
    vals, idx = _screen(ground, gvalid, cands, cvalid, safe, pvalid, TOP)
    steps = []                          # (contenders, pick) per step
    for t, (p, ok) in enumerate(zip(picks, pvalid)):
        v = vals[t]
        live = v > -np.inf
        near = live & (v >= v[0] - SCREEN * abs(v[0])) if live.any() \
            else live
        cols = [int(i) for i in idx[t][near]]
        steps.append((cols + ([p] if ok and p not in cols else []), p, ok))
        if not ok:
            break
    # every float64 distance column the replay needs, in one product
    need = sorted({c for cols, _, _ in steps for c in cols})
    pos = {c: i for i, c in enumerate(need)}
    g = np.asarray(ground, np.float64)[gvalid]
    d = _d64(g, np.asarray(cands, np.float64)[need]) if need else None
    n_eff = max(len(g), 1)
    mind = np.linalg.norm(g, axis=1)
    g1, worst = None, 0.0
    for cols, p, ok in steps:
        if cols:
            gv = np.maximum(mind[:, None] - d[:, [pos[c] for c in cols]],
                            0.0).sum(0) / n_eff
            best = float(gv.max())
            g_pick = float(gv[cols.index(p)]) if ok else 0.0
        else:
            best = g_pick = 0.0
        if g1 is None:
            g1 = best if best > 0 else 1.0
        worst = max(worst, ((best - g_pick) if ok else max(best, 0.0)) / g1)
        if ok:
            mind = np.minimum(mind, d[:, pos[p]])
    return {"gap": worst}


# ---------------------------------------------------------------------------
# the reference in the program's place (control and planted faults)
# ---------------------------------------------------------------------------


def _dist32(gn, cn, cross):
    import jax.numpy as jnp
    scale = gn[:, None] + cn[None, :]
    d2 = scale - 2.0 * cross
    return jnp.sqrt(jnp.where(d2 > NOISE_FLOOR * scale, d2, 0.0))


def _dot3(a, b):
    """a @ b.T as a three-pass bf16 product (hi*hi + hi*lo + lo*hi, f32
    accumulation): the precision `high` gives a float32 matmul on a TPU,
    spelled out so that every backend computes the same."""
    import jax
    import jax.numpy as jnp
    bf, f32 = jnp.bfloat16, jnp.float32
    ah = a.astype(bf)
    al = (a - ah.astype(f32)).astype(bf)
    bh = b.astype(bf)
    bl = (b - bh.astype(f32)).astype(bf)
    dot = lambda x, y: jax.lax.dot_general(
        x, y, (((1,), (1,)), ((), ())), preferred_element_type=f32)
    return dot(ah, bh) + dot(ah, bl) + dot(al, bh)


def device_greedy(cands, cvalid, k: int, ground=None, gvalid=None, *,
                  precision: str = "exact", fault: Optional[str] = None):
    """Greedy on the device in float32: (picks (k,) i32, valid (k,) bool,
    value ()). Picks index `cands`. `fault` plants one defect: 'stale'
    (the state is never updated), 'half' (gains over the first half of the
    ground rows, averaged over them)."""
    import jax
    import jax.numpy as jnp
    if ground is None:
        ground, gvalid = cands, cvalid
    g = jnp.asarray(ground, jnp.float32)
    c = jnp.asarray(cands, jnp.float32)
    gv = jnp.asarray(gvalid, bool)
    if fault == "half":
        half = g.shape[0] // 2
        g, gv = g[:half], gv[:half]
    if precision == "high":
        cross = _dot3(g, c)
    elif precision == "bf16":
        cross = jax.lax.dot_general(
            g.astype(jnp.bfloat16), c.astype(jnp.bfloat16),
            (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32)
    else:
        cross = jnp.dot(g, c.T, precision=jax.lax.Precision.HIGHEST)
    gn, cn = jnp.sum(g * g, axis=1), jnp.sum(c * c, axis=1)
    d = _dist32(gn, cn, cross)
    n_eff = jnp.maximum(jnp.sum(gv), 1).astype(jnp.float32)
    d0 = jnp.where(gv, jnp.sqrt(gn), 0.0)

    def step(carry, _):
        m, open_ = carry
        gains = jnp.sum(jnp.maximum(m[:, None] - d, 0.0), axis=0) / n_eff
        gains = jnp.where(open_, gains, -jnp.inf)
        best = jnp.argmax(gains)
        ok = gains[best] > 0
        if fault != "stale":
            m = jnp.where(ok, jnp.minimum(m, d[:, best]), m)
        open_ = open_.at[best].set(open_[best] & ~ok)
        return (m, open_), (best.astype(jnp.int32), ok)

    (m, _), (picks, ok) = jax.lax.scan(step, (d0, jnp.asarray(cvalid, bool)),
                                       None, length=k)
    return picks, ok, jnp.sum(d0 - m) / n_eff
