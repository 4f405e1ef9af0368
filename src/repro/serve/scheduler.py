"""Host-side continuous-batching scheduler over the slot pool.

One :class:`SolveService` = one admission queue + one :class:`StatePool`
+ one :class:`TenantStateStore` + one :class:`ServeMetrics` registry.
The event loop is deliberately synchronous and deterministic — a *tick*
is one call to :meth:`SolveService.tick`:

1. **Admit**: waiting tenants (pending work, not resident) bind to free
   slots in arrival order.  When no slot is free, the least-recently-
   served *idle* resident (no pending request) is evicted — its
   ``RecycleState`` spills through the store so its warm basis survives
   — and the newcomer takes the slot.  Busy residents are never evicted,
   so admitted work always completes.  A tenant that was evicted earlier
   re-admits from its spilled state (bit-for-bit), not cold.
2. **Serve**: every resident tenant with pending work contributes its
   next request.  With two or more active slots the whole pool runs ONE
   :func:`repro.core.solve_pool_step` (idle/empty slots masked inactive
   — zero rhs, state passed through untouched); with exactly one active
   slot the scheduler gathers that slot and dispatches through plain
   :data:`repro.core.solve_jit` instead, fencing the known B=1 vmap
   regression (masked while-loop lowering tax, see the ``batch/`` bench).
3. **Scatter**: per-tenant solutions and masked
   :class:`repro.core.SolveReport` diagnostics land in the ticket table
   (:meth:`result` collects them), slot last-served ticks and the
   metrics registry update.

Spans (:mod:`repro.runtime.spans`): each tick is a ``serve.tick`` span
(attrs ``tick``, ``serving``, ``syncs``) holding ``serve.admit``,
``serve.build_batch`` (batched ticks), ``serve.pool_step`` (dispatch
only), two ``serve.fetch`` waits (the step's diagnostics) and
``serve.scatter``.  Redeeming a ticket records a ``serve.ticket``
interval from submit to redeem, with the serving tick's number and start
and the scatter time; the same submit and tick start give each tenant's
``queue_wait_s``.

Nothing here blocks on a background thread: "continuous batching" is a
property of the admission/eviction policy, not of concurrency — drive
the loop with ``tick()`` / ``run_until_idle()`` / ``result(drive=True)``
and every run is exactly reproducible (the pool-lifecycle tests depend
on this).

Batching contract: all tenants of one service must share one operator
*family* — identical pytree treedef and identical static aux (e.g. one
kernel-matvec callable for every tenant of a shared-kernel GP service).
The treedef is checked per tick with a targeted error; a fresh callable
per request would silently retrace the batched step every tick, so keep
operator closures module-stable exactly as with the plain front doors.
"""

from __future__ import annotations

import dataclasses
import time
from collections import OrderedDict, deque
from typing import Any, Deque, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import (
    SolveReport,
    SolveSpec,
    solve_jit,
    solve_pool_step_jit,
)
from repro.core import pytree as pt
from repro.runtime import spans
from repro.serve.metrics import ServeMetrics
from repro.serve.pool import PoolFullError, StatePool, TenantStateStore

Pytree = Any

# The tick's device reads wait in spans of this name.
FETCH = "serve.fetch"


@dataclasses.dataclass(frozen=True)
class Ticket:
    """Claim check for one submitted system (tenant key + sequence no)."""

    tenant: str
    seq: int


@dataclasses.dataclass(frozen=True)
class ServedResult:
    """What a ticket redeems for: solution + per-tenant diagnostics."""

    tenant: str
    seq: int
    x: Pytree
    iterations: int
    matvecs: int
    converged: bool
    residual_norm: float
    status: int
    rung: int
    guard_firings: int
    tick: int
    queue_wait_ticks: int
    report: SolveReport

    @property
    def ok(self) -> bool:
        return self.converged and self.status == 0


@dataclasses.dataclass
class _Request:
    ticket: Ticket
    A: Any
    b: Pytree
    submitted_tick: int
    submitted_ns: int


class SolveService:
    """Multi-tenant solve service: submit systems, drive ticks, redeem
    tickets.  See the module docstring for the tick protocol.

    Args:
      spec: the one :class:`SolveSpec` every tenant is served under
        (``method='defcg'`` — the pool carries recycle state).
      slots: pool size B (slots, not tenants — tenants beyond B rotate
        through eviction).
      checkpoint_dir: where evicted tenants' states spill.  ``None``
        keeps host-RAM copies (non-durable); a directory spills through
        :class:`repro.checkpoint.CheckpointManager` with ``keep_last``
        retention GC per tenant key.
      keep_last: spilled-checkpoint retention budget per tenant.
      max_drive_ticks: safety bound for ``result(drive=True)`` /
        ``run_until_idle`` loops.
    """

    def __init__(
        self,
        spec: Optional[SolveSpec] = None,
        *,
        slots: int = 8,
        checkpoint_dir: Optional[str] = None,
        keep_last: int = 4,
        max_drive_ticks: int = 100_000,
    ):
        spec = SolveSpec() if spec is None else spec
        if spec.method != "defcg":
            raise ValueError(
                "SolveService carries per-tenant RecycleState — it needs "
                f"spec.method='defcg', got {spec.method!r}"
            )
        self.spec = spec
        self.pool = StatePool(slots, spec)
        self.store = TenantStateStore(checkpoint_dir, keep_last=keep_last)
        self.metrics = ServeMetrics(slots=slots)
        self.max_drive_ticks = max_drive_ticks
        self.tick_count = 0
        # Tenant -> FIFO of unserved requests; OrderedDict so admission
        # considers waiting tenants in arrival order (first submit wins).
        self._pending: "OrderedDict[str, Deque[_Request]]" = OrderedDict()
        self._results: Dict[Tuple[str, int], ServedResult] = {}
        # Ticket -> (submit, serving tick's start, scatter) on
        # perf_counter_ns, until the ticket is redeemed.
        self._stamps: Dict[Tuple[str, int], Tuple[int, int, int]] = {}
        self._seq: Dict[str, int] = {}

    # -- tenant-facing API -------------------------------------------------
    def session(self, tenant: str):
        """A :class:`repro.serve.Session` handle bound to ``tenant``."""
        from repro.serve.session import Session

        return Session(self, tenant)

    def submit(self, tenant: str, A: Any, b: Pytree) -> Ticket:
        """Enqueue one system for ``tenant``; returns its ticket."""
        tenant = str(tenant)
        seq = self._seq.get(tenant, 0)
        self._seq[tenant] = seq + 1
        ticket = Ticket(tenant=tenant, seq=seq)
        if tenant not in self._pending:
            self._pending[tenant] = deque()
        self._pending[tenant].append(
            _Request(
                ticket=ticket, A=A, b=b, submitted_tick=self.tick_count,
                submitted_ns=time.perf_counter_ns(),
            )
        )
        self.metrics.tenant(tenant).submitted += 1
        return ticket

    def poll(self, ticket: Ticket) -> Optional[ServedResult]:
        """The ticket's result if served, else None (does not tick)."""
        return self._results.get((ticket.tenant, ticket.seq))

    def result(self, ticket: Ticket, *, drive: bool = True) -> ServedResult:
        """Redeem a ticket, driving ticks until it resolves.

        With ``drive=False`` the ticket must already be served (KeyError
        otherwise) — the mode for an external loop that owns ticking.
        """
        key = (ticket.tenant, ticket.seq)
        if key in self._results:
            return self._redeem(key)
        if not drive:
            raise KeyError(
                f"ticket {ticket} not served yet (drive=False does not tick)"
            )
        for _ in range(self.max_drive_ticks):
            self.tick()
            if key in self._results:
                return self._redeem(key)
        raise RuntimeError(
            f"ticket {ticket} unresolved after {self.max_drive_ticks} ticks "
            "— was it submitted to this service?"
        )

    def close(self, tenant: str, *, spill: bool = True) -> None:
        """Depart: free the tenant's slot (spilling its warm state so a
        later session can resume) and forget its empty queue.

        Refuses to close a tenant with unserved requests — drain or
        redeem them first (dropping queued work silently would turn a
        scheduling bug into a hang at ``result``).
        """
        tenant = str(tenant)
        q = self._pending.get(tenant)
        if q:
            raise RuntimeError(
                f"tenant {tenant!r} still has {len(q)} unserved request(s) "
                "— drive them to completion before close()"
            )
        self._pending.pop(tenant, None)
        if self.pool.resident(tenant):
            state = self.pool.release(tenant)
            if spill:
                self.store.spill(tenant, state)

    # -- the event loop ----------------------------------------------------
    def run_until_idle(self) -> int:
        """Tick until no request is pending; returns systems served."""
        served = 0
        for _ in range(self.max_drive_ticks):
            if not any(self._pending.values()):
                return served
            served += self.tick()
        raise RuntimeError(
            f"work still pending after {self.max_drive_ticks} ticks"
        )

    def tick(self) -> int:
        """One scheduler step: admit, serve, scatter.  Returns the number
        of systems served this tick (0 = idle tick)."""
        self.tick_count += 1
        tick = self.tick_count
        with spans.span("serve.tick", tick=tick, serving=0) as this_tick:
            with spans.span("serve.admit"):
                self._admit(tick)

            serving = []  # (slot, request)
            for tenant, q in self._pending.items():
                if not q:
                    continue
                slot = self.pool.slot_of(tenant)
                if slot is not None:
                    serving.append((slot, q.popleft()))
            this_tick.attrs["serving"] = len(serving)
            self.metrics.record_tick(self.pool.occupancy, len(serving))
            self.metrics.record_queue_depth(
                sum(len(q) for q in self._pending.values()) + len(serving)
            )
            if not serving:
                return 0

            if len(serving) == 1:
                # B=1 fence: one active slot loses under the vmapped
                # masked while-loop — gather the slot and run the plain
                # front door.
                slot, req = serving[0]
                with spans.span("serve.pool_step"):
                    res = solve_jit(
                        req.A, req.b, self.spec, self.pool.slot_state(slot)
                    )
                    self.pool.write_slot(slot, res.state)
                self.metrics.single_steps += 1
            else:
                with spans.span("serve.build_batch"):
                    systems, b_batch, active = self._build_batch(serving)
                with spans.span("serve.pool_step"):
                    res = solve_pool_step_jit(
                        systems, b_batch, self.spec, self.pool.state, active
                    )
                    self.pool.state = res.state
                self.metrics.batched_steps += 1
            info = spans.fetch(res.info._replace(residual_norms=None), FETCH)
            report = spans.fetch(res.report, FETCH)
            with spans.span("serve.scatter"):
                if len(serving) == 1:
                    self._scatter(
                        serving[0][1], res.x, info, report, tick,
                        this_tick.start_ns,
                    )
                else:
                    for slot, req in serving:
                        self._scatter(
                            req,
                            jax.tree_util.tree_map(lambda l: l[slot], res.x),
                            jax.tree_util.tree_map(lambda l: l[slot], info),
                            jax.tree_util.tree_map(lambda l: l[slot], report),
                            tick,
                            this_tick.start_ns,
                        )
                self.pool.touch([slot for slot, _ in serving], tick)
        return len(serving)

    # -- internals ---------------------------------------------------------
    def _admit(self, tick: int) -> None:
        for tenant in list(self._pending):
            if not self._pending[tenant] or self.pool.resident(tenant):
                continue
            busy = {t for t, q in self._pending.items() if q}
            if not self.pool.free_slots():
                victim = self.pool.lru_tenant(exclude=busy)
                if victim is None:
                    # Every resident has pending work; the newcomer waits
                    # (queue_wait_ticks accrues until a slot drains).
                    continue
                self.store.spill(victim, self.pool.release(victim))
                self.metrics.record_eviction(victim)
            req = self._pending[tenant][0]
            n, dtype = self._problem_shape(req.b)
            self.pool.ensure_allocated(n, dtype)
            restored = self.store.restore(
                tenant, self.pool.zero_slot_state()
            )
            try:
                self.pool.admit(tenant, restored, n=n, dtype=dtype, tick=tick)
            except PoolFullError:  # pragma: no cover — guarded above
                continue
            self.metrics.record_admission(
                tenant, restored=restored is not None
            )

    @staticmethod
    def _problem_shape(b: Pytree):
        flat, _ = pt.ravel_vector(b)
        return flat.shape[0], flat.dtype

    def _build_batch(self, serving):
        B = self.pool.slots
        fill_req = serving[0][1]
        treedef0 = jax.tree_util.tree_structure(fill_req.A)
        for slot, req in serving[1:]:
            td = jax.tree_util.tree_structure(req.A)
            if td != treedef0:
                raise ValueError(
                    "all tenants of one service must share one operator "
                    f"family: tenant {req.ticket.tenant!r} submitted "
                    f"{td} but the tick's first operator is {treedef0} "
                    "(same pytree structure AND same static aux required "
                    "to stack into one batched step)"
                )
        zero_b = jax.tree_util.tree_map(jnp.zeros_like, fill_req.b)
        ops = [fill_req.A] * B
        bs = [zero_b] * B
        active = np.zeros(B, bool)
        for slot, req in serving:
            ops[slot] = req.A
            bs[slot] = req.b
            active[slot] = True
        systems = jax.tree_util.tree_map(lambda *ls: jnp.stack(ls), *ops)
        b_batch = jax.tree_util.tree_map(lambda *ls: jnp.stack(ls), *bs)
        return systems, b_batch, jnp.asarray(active)

    def _redeem(self, key: Tuple[str, int]) -> ServedResult:
        """Hand out a served result and record its ticket: the
        ``serve.ticket`` interval runs from submit to now, with the serving
        tick's number and start and the scatter time as attrs."""
        served = self._results.pop(key)
        submit_ns, tick_start_ns, scatter_ns = self._stamps.pop(key)
        spans.interval(
            "serve.ticket", submit_ns, time.perf_counter_ns(),
            tick=served.tick, tick_start_ns=tick_start_ns,
            scatter_ns=scatter_ns,
        )
        return served

    def _scatter(
        self, req: _Request, x, info, report, tick: int, tick_start_ns: int
    ) -> None:
        waited = max(tick - 1 - req.submitted_tick, 0)
        served = ServedResult(
            tenant=req.ticket.tenant,
            seq=req.ticket.seq,
            x=x,
            iterations=int(info.iterations),
            matvecs=int(info.matvecs),
            converged=bool(info.converged),
            residual_norm=float(info.residual_norm),
            status=int(info.status),
            rung=int(report.rung),
            guard_firings=int(report.guard_firings),
            tick=tick,
            queue_wait_ticks=waited,
            report=SolveReport(
                status=np.int32(info.status),
                rung=np.int32(report.rung),
                guard_firings=np.int32(report.guard_firings),
                matvecs=np.int32(info.matvecs),
            ),
        )
        key = (req.ticket.tenant, req.ticket.seq)
        self._results[key] = served
        self._stamps[key] = (
            req.submitted_ns, tick_start_ns, time.perf_counter_ns()
        )
        self.metrics.record_served(
            req.ticket.tenant,
            iterations=served.iterations,
            matvecs=served.matvecs,
            guard_firings=served.guard_firings,
            rung=served.rung,
            status=served.status,
            waited_ticks=waited,
            waited_s=(tick_start_ns - req.submitted_ns) * 1e-9,
            tick=tick,
        )

    # -- telemetry ---------------------------------------------------------
    def metrics_snapshot(self) -> dict:
        """Pool + per-tenant counters as one nested plain dict."""
        self.metrics.spill_gc_deleted = self.store.gc_deleted_total
        return self.metrics.snapshot()
