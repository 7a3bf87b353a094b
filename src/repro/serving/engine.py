"""Multi-tenant batched selection query engine (DESIGN §Serving).

Every driver in this repo answers one selection question per process; the
`QueryEngine` is the service surface of ROADMAP item 1: many independent
tenants submit queries — each with its own registered objective, k,
constraint, and seed — into a bounded request queue, and the engine
ADMISSION-BATCHES compatible queries into one shared megakernel dispatch.

Compatibility (plans.serve_key): same KernelRule — name AND cap — same
candidate-bucket shape, same trailing payload axis (features D / universe
words W), same backend. Admitted groups are stacked on a leading query
axis (each pool zero-padded to the shared candidate bucket: pad slots
carry zero payloads, valid=False, id −1 — exactly the padding the solo
kernel wrapper would apply, so stacking is lossless) and executed by
`RuleObjective.megakernel_loop_batched`, a `jax.vmap` of the VMEM-resident
megakernel: the query axis becomes a batch grid dimension of the SAME
pallas_call, i.e. ONE dispatch per rule-compatible sub-batch (jaxpr-
verified per compiled executor via ops.count_pallas_dispatches).
Heterogeneous k rides the kernel's traced ctl operand — each query's step
budget masks steps ≥ k_i, so every query is bit-identical to its solo
`greedy()` run. Heterogeneous objectives simply land in different
sub-batches.

Queries the batched path cannot serve fall back to a solo `greedy()` run
(identical code path to a direct caller): constrained queries and
stochastic-greedy sampling (both need per-step host logic the loop kernel
does not evaluate), explicit engine overrides, and any query whose
working set overflows the resident tier (plans.serve_plan returns None).
The admitted batch size is additionally capped so B stacked per-query
working sets fit REPRO_SERVE_VMEM_MB (plans.serve_plan's budget math) and
by the REPRO_SERVE_BATCH admission cap. All knobs read through
runtime/flags.py typed accessors — never raw environment reads here.
"""
from __future__ import annotations

import collections
import dataclasses
import time
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core import greedy as greedy_mod
from repro.core.objective import make_objective
from repro.kernels import ops, plans
from repro.runtime import flags, telemetry
from repro.serving.metrics import ServeMetrics

F32 = jnp.float32


class QueueFull(RuntimeError):
    """Raised by submit() when the bounded request queue is at
    REPRO_SERVE_QUEUE capacity — backpressure: drain() first."""


@dataclasses.dataclass
class Query:
    """One tenant's selection request.

    objective/universe/params construct the registered objective
    (core.objective.make_objective); ids/payloads/valid are the candidate
    pool exactly as a solo `greedy()` caller would pass them; constraint/
    sample/seed/engine mirror greedy()'s arguments (a non-default value
    of any of them routes the query to the solo fallback — identical
    results, just not co-batched)."""
    objective: str
    k: int
    ids: Any
    payloads: Any
    valid: Any
    tenant: str = "anon"
    universe: int = 0
    params: dict = dataclasses.field(default_factory=dict)
    constraint: Any = None
    sample: int = 0
    seed: int = 0
    engine: str = "auto"


@dataclasses.dataclass
class QueryResult:
    """A completed query: the Solution plus how it was served."""
    qid: int
    tenant: str
    solution: greedy_mod.Solution
    batched: bool
    batch_size: int
    key: Optional[str]
    latency_s: float


def _pad_axis0(x: jax.Array, target: int, value) -> jax.Array:
    pad = target - x.shape[0]
    if pad == 0:
        return x
    widths = [(0, pad)] + [(0, 0)] * (x.ndim - 1)
    return jnp.pad(x, widths, constant_values=value)


class QueryEngine:
    """Bounded queue + admission batcher + batched/solo scheduler."""

    def __init__(self, *, backend: Optional[str] = None,
                 max_batch: Optional[int] = None,
                 queue_cap: Optional[int] = None,
                 metrics: Optional[ServeMetrics] = None):
        self.backend = backend
        self.max_batch = max_batch      # None → flags.serve_batch()
        self.queue_cap = queue_cap      # None → flags.serve_queue()
        self.metrics = metrics if metrics is not None else ServeMetrics()
        self._pending: collections.deque = collections.deque()
        self._next_qid = 0
        self._objs: Dict[tuple, Any] = {}
        # (serve_key, B_pad, k_pad) → (jitted executor, measured dispatches)
        self._exec: Dict[tuple, Tuple[Any, int]] = {}

    # -- submission ----------------------------------------------------------

    @property
    def pending(self) -> int:
        return len(self._pending)

    def submit(self, query: Query) -> int:
        """Enqueue a query; returns its qid (the key into drain()'s
        result dict). Raises QueueFull at the queue bound."""
        cap = (self.queue_cap if self.queue_cap is not None
               else flags.serve_queue())
        if len(self._pending) >= cap:
            raise QueueFull(f"request queue at capacity ({cap})")
        qid = self._next_qid
        self._next_qid += 1
        t0 = self.metrics.submitted(query.tenant)
        self._pending.append((qid, query, t0))
        return qid

    # -- objective + compatibility -------------------------------------------

    def _objective(self, q: Query):
        kp = (q.objective, q.universe, tuple(sorted(q.params.items())))
        obj = self._objs.get(kp)
        if obj is None:
            obj = make_objective(q.objective, universe=q.universe,
                                 backend=self.backend, **q.params)
            self._objs[kp] = obj
        return obj

    def _compat(self, q: Query):
        """(serve_key, admission plan) when the query can co-batch, else
        (None, None) → solo fallback. Constraints and sampling need
        per-step host logic; explicit engine overrides are honored by
        running the query exactly as requested."""
        c = int(q.valid.shape[0])
        if (q.constraint is not None or 0 < q.sample < c
                or q.engine not in ("auto", "mega")):
            return None, None
        obj = self._objective(q)
        rule = obj.rule
        n, d = ((obj.words, None) if rule.is_bitmap
                else (c, int(q.payloads.shape[-1])))
        sp = plans.serve_plan(rule, n, c, d, backend=self.backend)
        if sp is None:
            return None, None               # resident overflow → solo
        return plans.serve_key(rule, n, c, d,
                               plans.resolve_backend(self.backend)), sp

    # -- admission -----------------------------------------------------------

    def _admit(self):
        """Pop the queue head; its compat key defines the batch. Scan the
        remaining queue FIFO for same-key queries up to the admission cap
        (min of the plan's VMEM-budgeted b_max and REPRO_SERVE_BATCH /
        max_batch); everything else keeps its queue position."""
        head = self._pending.popleft()
        skey, sp = self._compat(head[1])
        group = [head]
        if skey is None:
            return None, None, group
        cap = (self.max_batch if self.max_batch is not None
               else flags.serve_batch())
        b_max = max(1, min(sp["b_max"], cap))
        keep: collections.deque = collections.deque()
        while self._pending and len(group) < b_max:
            entry = self._pending.popleft()
            ekey, _ = self._compat(entry[1])
            if ekey == skey:
                group.append(entry)
            else:
                keep.append(entry)
        while self._pending:
            keep.append(self._pending.popleft())
        self._pending = keep
        return skey, sp, group

    # -- execution -----------------------------------------------------------

    def _executor(self, obj, skey: str, plan, b_pad: int, pool_shape,
                  pool_dtype, k_pad: int):
        """The jitted batched executor for one (key, B_pad, k_pad) shape
        bucket, plus its jaxpr-measured pallas dispatch count (built once
        per bucket, replayed from the compile cache after)."""
        ck = (skey, b_pad, k_pad)
        hit = self._exec.get(ck)
        if hit is not None:
            return hit

        def run(pays, vals, ks, lims):
            return obj.megakernel_loop_batched(pays, vals, ks, k_pad,
                                               plan=plan, logical=lims)

        fn = jax.jit(run)
        c_bkt = pool_shape[0]
        sds = jax.ShapeDtypeStruct
        jx = jax.make_jaxpr(run)(
            sds((b_pad,) + tuple(pool_shape), pool_dtype),
            sds((b_pad, c_bkt), jnp.bool_),
            sds((b_pad,), jnp.int32),
            sds((b_pad, 2), jnp.int32))
        nd = ops.count_pallas_dispatches(jx.jaxpr)
        self._exec[ck] = (fn, nd)
        return fn, nd

    def _run_solo(self, entry) -> QueryResult:
        qid, q, t0 = entry
        obj = self._objective(q)
        c = int(q.valid.shape[0])
        key = (jax.random.PRNGKey(q.seed) if 0 < q.sample < c else None)
        with telemetry.span("serve.dispatch", batch=1):
            sol = greedy_mod.greedy(obj, jnp.asarray(q.ids, jnp.int32),
                                    jnp.asarray(q.payloads),
                                    jnp.asarray(q.valid).astype(bool), q.k,
                                    sample=q.sample, key=key,
                                    constraint=q.constraint, engine=q.engine)
        with telemetry.span("serve.wait", batch=1):
            jax.block_until_ready(sol.ids)
        lat = self.metrics.completed(q.tenant, t0, batched=False)
        return QueryResult(qid, q.tenant, sol, False, 1, None, lat)

    def _run_batched(self, skey: str, sp: dict, group) -> List[QueryResult]:
        t_exec = time.monotonic()
        with telemetry.span("serve.dispatch", batch=len(group)):
            plan = sp["plan"]
            obj0 = self._objective(group[0][1])
            rule = obj0.rule
            c_bkt = plans.bucket_len(
                max(int(q.valid.shape[0]) for _, q, _ in group), 128)
            k_pad = plans.bucket_len(max(q.k for _, q, _ in group), 4)
            b_pad = 1
            while b_pad < len(group):
                b_pad *= 2
            b_pad = max(min(b_pad, sp["b_max"]), len(group))
            pays, vals, ks, lims, padded = [], [], [], [], []
            for _, q, _ in group:
                c = int(q.valid.shape[0])
                ids_p = _pad_axis0(jnp.asarray(q.ids, jnp.int32), c_bkt, -1)
                pay_p = _pad_axis0(jnp.asarray(q.payloads), c_bkt, 0)
                val_p = _pad_axis0(jnp.asarray(q.valid).astype(bool), c_bkt,
                                   False)
                padded.append((ids_p, pay_p, val_p))
                pays.append(pay_p)
                vals.append(val_p)
                ks.append(q.k)
                lims.append((obj0.words if rule.is_bitmap else c, c))
            # inert fill queries: k=0, all-invalid
            while len(pays) < b_pad:
                pays.append(jnp.zeros_like(pays[0]))
                vals.append(jnp.zeros_like(vals[0]))
                ks.append(0)
                lims.append((0, 0))
            fn, ndisp = self._executor(obj0, skey, plan, b_pad,
                                       pays[0].shape, pays[0].dtype, k_pad)
            states, bests, gains = fn(jnp.stack(pays), jnp.stack(vals),
                                      jnp.asarray(ks, jnp.int32),
                                      jnp.asarray(lims, jnp.int32))
        with telemetry.span("serve.wait", batch=len(group)):
            jax.block_until_ready(bests)
        self.metrics.batch_executed(skey, len(group), ndisp,
                                    time.monotonic() - t_exec)
        with telemetry.span("serve.unpack", batch=len(group)):
            out = []
            for i, (qid, q, t0) in enumerate(group):
                obj = self._objective(q)
                st = jax.tree.map(lambda x: x[i], states)
                mega = (st, bests[i, :q.k], gains[i, :q.k])
                ids_p, pay_p, val_p = padded[i]
                sol = greedy_mod._finalize_mega(obj, mega, ids_p, pay_p,
                                                val_p, q.k)
                lat = self.metrics.completed(q.tenant, t0, batched=True)
                out.append(QueryResult(qid, q.tenant, sol, True, len(group),
                                       skey, lat))
        return out

    # -- the scheduler loop --------------------------------------------------

    def drain(self) -> Dict[int, QueryResult]:
        """Serve every pending query: repeatedly admit the head's
        compatible group and execute it as one batched dispatch (or run
        the head solo when it cannot co-batch). Returns {qid:
        QueryResult} for everything served. Each batch's host phases are
        `runtime.telemetry` spans: ``serve.admit``, ``serve.dispatch``
        (pad, stack, launch), ``serve.wait`` (the device) and
        ``serve.unpack``."""
        out: Dict[int, QueryResult] = {}
        while self._pending:
            with telemetry.span("serve.admit"):
                skey, sp, group = self._admit()
            if skey is None:
                results = [self._run_solo(e) for e in group]
            else:
                results = self._run_batched(skey, sp, group)
            for r in results:
                out[r.qid] = r
        return out
