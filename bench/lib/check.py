"""The comparison that decides `correct`.

Every checked selection is replayed against the configuration's plain
reference (`bench/references/<objective>.py`): step by step, given the
program's own earlier picks, the reference's float64 (or exact integer)
gain of the program's pick is set against the best gain on offer. Near
ties cost nothing: a pick whose gain equals the best to rounding reads a
gap of that rounding. A wrong pick, a skipped state update, a lost row or
an altered answer reads the size of its error.

The number compared, `pick_gap`, is the widest gap by which any choice
the program made lies below the best on offer: a pick, as a share of the
first step's best gain of its greedy; at a tree node, the solution kept,
as a share of the better value of the node's own earlier solution and
the reference greedy over the gathered union.

The tree's intermediate solutions are read from the checkpoints the
supervisor writes after each level (`<dir>/tree0/step_<N>/arrays.npz`),
and the root it returned must equal lane 0 of the last level.
"""
from __future__ import annotations

import glob
import math
import os
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np


def sample(seed: int, done: int, pools: int, count: int) -> List[int]:
    """`count` selection indices out of `done`, drawn from the seed, from
    as many different pools as there are."""
    rng = np.random.default_rng(int(seed) % 2 ** 64)
    order = rng.permutation(done).tolist()
    out, seen = [], set()
    for i in order:                         # one per pool first
        if len(out) < count and i % pools not in seen:
            out.append(i)
            seen.add(i % pools)
    for i in order:
        if len(out) < count and i not in out:
            out.append(i)
    return sorted(out)


def greedy_gap(ref, pool: np.ndarray, ids, valid) -> Dict[str, float]:
    """Replay one greedy over a whole pool whose ids are 0..n-1."""
    n = pool.shape[0]
    ones = np.ones(n, bool)
    return ref.replay(pool, ones, pool, ones, np.asarray(ids),
                      np.asarray(valid))


# ---------------------------------------------------------------------------
# the accumulation tree
# ---------------------------------------------------------------------------


def lane_digits(lane: int, radices: Sequence[int]) -> List[int]:
    out = []
    for r in radices:
        out.append(lane % r)
        lane //= r
    return out


def group(lane: int, lvl: int, radices: Sequence[int]) -> List[int]:
    """Lanes gathered at level `lvl` into `lane`'s node, in gather order
    (by their level-`lvl` digit)."""
    stride = math.prod(radices[:lvl])
    base = lane - lane_digits(lane, radices)[lvl] * stride
    return [base + j * stride for j in range(radices[lvl])]


def read_stages(ckpt_dir: str, lanes: int, k: int
                ) -> List[Tuple[np.ndarray, np.ndarray]]:
    """(ids (lanes, k), valid (lanes, k)) per stage — leaves, then each
    level — from the supervisor's checkpoints. The arrays are found by
    shape and dtype, not by the names the program gives them."""
    out = []
    for step in sorted(glob.glob(os.path.join(ckpt_dir, "tree0",
                                              "step_*"))):
        if step.endswith(".tmp"):
            continue
        with np.load(os.path.join(step, "arrays.npz")) as z:
            arrs = [z[f] for f in z.files]
        ids = [a for a in arrs if a.shape == (lanes, k)
               and np.issubdtype(a.dtype, np.integer)]
        val = [a for a in arrs if a.shape == (lanes, k) and a.dtype == bool]
        if len(ids) != 1 or len(val) != 1:
            raise ValueError(f"{step}: no unique (ids, valid) pair of "
                             f"shape {(lanes, k)}")
        out.append((ids[0].astype(np.int64), val[0]))
    return out


def tree_gaps(ref, data: np.ndarray, stages, root_ids, root_valid,
              radices: Sequence[int], k: int) -> Dict[str, float]:
    """Check every leaf, every node of every level, and the root."""
    lanes = math.prod(radices)
    n_l = data.shape[0] // lanes
    if len(stages) != len(radices) + 1:
        return {"pick_gap": math.inf}
    pick = 0.0
    ids0, val0 = stages[0]
    for lane in range(lanes):
        pool = data[lane * n_l:(lane + 1) * n_l]
        r = greedy_gap(ref, pool, ids0[lane] - lane * n_l, val0[lane])
        pick = max(pick, r["gap"])
    for lvl in range(len(radices)):
        ids_p, val_p = stages[lvl]
        ids_o, val_o = stages[lvl + 1]
        for lane in range(lanes):
            members = group(lane, lvl, radices)
            u_ids = np.concatenate([ids_p[m][val_p[m]] for m in members])
            u = data[u_ids]
            ones = np.ones(len(u_ids), bool)
            prev = ids_p[lane][val_p[lane]]
            out, ok = ids_o[lane], val_o[lane]
            if np.array_equal(out[ok], prev) and \
                    np.array_equal(ok, val_p[lane]):
                best = ref.greedy(u, ones, u, ones, k)
                v_best = ref.value(u, ones, u[best])
                v_kept = ref.value(u, ones, data[prev])
            else:
                pos = {int(g): i for i, g in enumerate(u_ids)}
                local = [pos.get(int(g), -1) for g in out]
                r = ref.replay(u, ones, u, ones, local, ok)
                pick = max(pick, r["gap"])
                v_best = ref.value(u, ones, data[prev])
                v_kept = ref.value(u, ones, data[out[ok]])
            if v_best > v_kept:
                pick = max(pick, (v_best - v_kept) / v_best)
    ids_l, val_l = stages[-1]
    if not (np.array_equal(np.asarray(root_ids), ids_l[0])
            and np.array_equal(np.asarray(root_valid), val_l[0])):
        pick = math.inf
    return {"pick_gap": pick}


def reference_tree(ref, data, k: int, radices: Sequence[int], *,
                   precision: str = "exact", fault: Optional[str] = None):
    """The tree computed by the reference in the program's place, on one
    device: stages as `read_stages` returns them, and the root. `fault`:
    one of the reference's own, or 'no_exchange' (a node sees only its own
    child's solution)."""
    lanes = math.prod(radices)
    n_l = data.shape[0] // lanes
    leaf_fault = None if fault == "no_exchange" else fault
    ids, val = [], []
    for lane in range(lanes):
        pool = data[lane * n_l:(lane + 1) * n_l]
        p, ok, _ = ref.device_greedy(pool, np.ones(n_l, bool), k,
                                     precision=precision, fault=leaf_fault)
        ids.append(np.asarray(p) + lane * n_l)
        val.append(np.asarray(ok))
    stages = [(np.stack(ids).astype(np.int64), np.stack(val))]
    for lvl in range(len(radices)):
        ids_p, val_p = stages[-1]
        ids, val = [], []
        for lane in range(lanes):
            members = ([lane] if fault == "no_exchange"
                       else group(lane, lvl, radices))
            u_ids = np.concatenate([ids_p[m][val_p[m]] for m in members])
            u = data[u_ids]
            ones = np.ones(len(u_ids), bool)
            p, ok, v_new = ref.device_greedy(u, ones, k, precision=precision,
                                             fault=leaf_fault)
            prev = ids_p[lane][val_p[lane]]
            v_prev = ref.value(u, ones, data[prev])
            if float(v_new) >= v_prev:
                ids.append(np.where(np.asarray(ok), u_ids[np.asarray(p)], -1))
                val.append(np.asarray(ok))
            else:
                ids.append(ids_p[lane])
                val.append(val_p[lane])
        stages.append((np.stack(ids).astype(np.int64), np.stack(val)))
    return stages, stages[-1][0][0], stages[-1][1][0]
