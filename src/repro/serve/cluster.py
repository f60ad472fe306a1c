"""Replica-sharded serving: N ``PagedEngine`` replicas behind one queue.

The :class:`ClusterEngine` scales the single-engine serving path across
the ``data`` mesh axis: each replica owns a disjoint ``BlockPool`` shard
(its own block table, allocator, prefix cache, and GLASS arenas) committed
to the first device of its own slice (``launch.mesh.replica_slices``), so
the replicas' jitted decode programs dispatch concurrently while one
host-side dispatcher drains a single global queue.

**Admission** pops the global queue in policy order (the same FIFO /
PRIORITY / DEADLINE ranks as the per-engine schedulers — a request's rank
is preserved end-to-end) and routes each request to the replica with the
lowest admission cost::

    cost(r, req) = pending_tokens(r)                       # load, token units
                 + overflow_weight * max(0, need_blocks(req) - free_blocks(r))
                 - affinity_weight * prefix_hit(r, req)    # rows served free

``pending_tokens`` measures outstanding work in tokens (not requests —
GLASS's per-request density/draft knobs make requests heterogeneous in
cost, which is exactly why round-robin assignment loses); ``free_blocks``
is net of the watermark reserve and blocks owed to swapped/migrating
requests; ``prefix_hit`` probes each replica's prefix cache through the
side-effect-free ``BlockPool.peek_prefix`` (a probe is not a use: no LRU
reorder, no hit/miss skew), so a request lands on the replica that
already holds the longest matching chain when loads are comparable.
``admission="round_robin"`` is the naive baseline the benchmark beats.

**Migration** rebalances under hot-spot pressure: when the hottest
replica's ``pending_tokens`` exceeds the coldest's by
``MigrationConfig.imbalance_tokens`` and the cold replica can host the
victim *now*, the scheduler's victim choice (mirror of admission order)
moves one running request over the ``SwappedRequest`` wire format — a
FULL swap-out on the source (shared prefix blocks copied like private
ones; physical ids mean nothing across pools), the portable
``SwappedWire`` payload, and a cross-pool splice (blocks + GLASS slot
rows + recurrent-state rows) on the destination::

    RUNNING/SPECULATING/PREFILLING ─▶ PREEMPTED_SWAPPED ─▶ MIGRATING ─▶ RUNNING
      (SPECULATING rolls back first;        (source)      (in flight)  (dest:
       PREFILLING hands off at a chunk                                  splice)
       boundary and resumes PREFILLING)

Migrated streams are bit-identical to an undisturbed single-engine run:
the swap format is proven bit-exact, GLASS rows are copied not rebuilt,
recurrent state rows ride in the same store, sampling is counter-based
(pure function of seed × position × logits), and a mid-prefill handoff
replays nothing — the partial stat left-fold travels with the ticket and
keeps accumulating at the destination over the same chunk boundaries.

Single-process by design: replicas are device-sliced, not host-sharded.
The host-side dispatcher, block accounting, and ticket handoff are plain
Python; a multi-host deployment would serialize ``MigrationTicket`` /
``SwappedWire`` (already host numpy + ints throughout) over the wire and
run one dispatcher process — the device-side machinery is unchanged.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Dict, List, Optional

import jax
import numpy as np

from ..core.fusion import GlassConfig
from ..core.glass import GlassParams
from ..launch.mesh import replica_slices
from .engine import MigrationTicket, PagedEngine
from .lifecycle import ReqState
from .sampling import SamplingParams
from .scheduler import AdmissionPolicy, Request, RequestOutput, Scheduler


@dataclass(frozen=True)
class MigrationConfig:
    """Hot-spot rebalancing knobs.

    ``imbalance_tokens`` is the minimum pending-token gap between the
    hottest and coldest replica before a migration pays for itself (the
    move costs one swap-out + one swap-in of the victim's whole context);
    ``min_remaining`` skips nearly-finished victims (their remaining work
    cannot amortize the move); ``max_per_tick`` bounds the dispatcher's
    per-tick migration work so a pathological imbalance cannot stall the
    serving loop."""

    enabled: bool = True
    imbalance_tokens: int = 48
    min_remaining: int = 4
    max_per_tick: int = 1


class ClusterEngine:
    """N ``PagedEngine`` replicas draining one global queue.

    Replica construction mirrors ``PagedEngine`` (every ``**engine_kw`` is
    per-replica: ``num_blocks`` is each shard's size, so N replicas at
    ``B`` blocks compare against one big engine at ``N*B``).  With a
    ``mesh`` (``make_host_mesh(data=N, model=M)``), replica ``r``'s params,
    GLASS prior, and KV arena are committed to data-slice ``r`` so the
    replicas' device programs overlap; without one, all replicas share the
    default device (correct, serialized — the single-device test fallback).
    """

    def __init__(
        self,
        model,
        params,
        *,
        n_replicas: int,
        mesh=None,
        admission: str = "balanced",  # balanced | round_robin
        migration: Optional[MigrationConfig] = None,
        policy: AdmissionPolicy = AdmissionPolicy.FIFO,
        glass: Optional[GlassConfig] = None,
        global_prior=None,
        overflow_weight: float = 8.0,
        affinity_weight: float = 1.0,
        **engine_kw,
    ):
        if n_replicas < 1:
            raise ValueError(f"n_replicas must be >= 1, got {n_replicas}")
        if admission not in ("balanced", "round_robin"):
            raise ValueError(f"unknown admission mode {admission!r}")
        self.admission = admission
        self.migration = migration if migration is not None else MigrationConfig()
        self.overflow_weight = overflow_weight
        self.affinity_weight = affinity_weight
        slices = (
            replica_slices(mesh, n_replicas) if mesh is not None
            else [None] * n_replicas
        )
        self.replicas: List[PagedEngine] = []
        for r, devs in enumerate(slices):
            # a replica lives on the first device of its slice: params and
            # prior are committed there, and everything the engine allocates
            # (KV pool, state rows) is created there, never staged on the
            # default device first
            dev = devs[0] if devs is not None else None
            put = (lambda t: t) if dev is None else (lambda t: jax.device_put(t, dev))
            with jax.default_device(dev):
                eng = PagedEngine(
                    model,
                    put(params),
                    glass=glass,
                    global_prior=(
                        put(global_prior) if global_prior is not None else None
                    ),
                    policy=policy,
                    **engine_kw,
                )
            # commit the pool where it was allocated, like the params: jit
            # keys its cache on commitment, so an uncommitted first call
            # would compile every pool-reading program twice
            eng.pool.cache = put(eng.pool.cache)
            eng.programs.namespace = f"replica{r}"
            self.replicas.append(eng)
        self.devices = slices
        self.queue = Scheduler(self.replicas[0].scheduler.max_len, policy=policy)
        self.t = 0
        self._rr = 0  # round-robin cursor
        self._auto_uid = 0
        self._owner: Dict[int, int] = {}  # uid -> replica index
        # telemetry
        self.migrations = 0
        self.migration_bytes = 0
        self.occupancy: List[List[int]] = [[] for _ in self.replicas]

    # -- request frontend ---------------------------------------------------

    def add_request(
        self,
        prompt,
        max_new: int,
        *,
        sampling: Optional[SamplingParams] = None,
        glass: Optional[GlassParams] = None,
        uid: Optional[int] = None,
        arrival: Optional[int] = None,
        priority: int = 0,
        deadline: Optional[int] = None,
    ) -> int:
        """Enqueue one request on the GLOBAL queue (arrival in cluster
        ticks); the dispatcher routes it to a replica when it arrives.
        Mirrors ``PagedEngine.add_request``."""
        if uid is None:
            used = self._owner.keys() | {r.uid for r in self.queue.queue}
            while self._auto_uid in used:
                self._auto_uid += 1
            uid = self._auto_uid
            self._auto_uid += 1
        req = Request(
            uid=uid, prompt=np.asarray(prompt, np.int32), max_new=max_new,
            arrival=self.t if arrival is None else arrival,
            priority=priority, deadline=deadline,
            sampling=sampling, glass=glass,
        )
        self.queue.submit(req)
        return uid

    def abort(self, uid: int) -> Optional[RequestOutput]:
        """Cancel a request wherever it lives: still in the global queue,
        queued/live/swapped/MIGRATING on its replica — the replica's abort
        releases exactly what it holds (a migrated-in store pins nothing,
        so aborting mid-migration releases both sides by construction)."""
        owner = self._owner.get(uid)
        if owner is not None:
            return self.replicas[owner].abort(uid)
        r = self.queue.remove(uid)
        if r is None:
            return None
        return RequestOutput(
            uid=uid, prompt=np.asarray(r.prompt, np.int32),
            new_tokens=np.zeros((0,), np.int32), tokens=np.zeros((0,), np.int32),
            finished=True, finish_reason="aborted",
            arrival=r.arrival, admitted_step=-1, finished_step=self.t,
        )

    # -- admission scoring --------------------------------------------------

    def _admission_cost(self, eng: PagedEngine, req: Request) -> float:
        ci = eng.admission_cost_inputs(req.prompt)
        rows = len(req.prompt) + req.max_new - 1 - ci["prefix_hit"]
        need = eng.pool.blocks_needed(rows)
        return (
            ci["pending_tokens"]
            + self.overflow_weight * max(0, need - ci["free_blocks"])
            - self.affinity_weight * ci["prefix_hit"]
        )

    def _route(self, req: Request) -> int:
        if self.admission == "round_robin":
            i = self._rr
            self._rr = (self._rr + 1) % len(self.replicas)
            return i
        costs = [self._admission_cost(eng, req) for eng in self.replicas]
        return int(np.argmin(costs))  # ties -> lowest replica index

    def _dispatch_tick(self) -> None:
        for req in self.queue.drain_arrived(self.t):
            i = self._route(req)
            # the replica clocks arrivals in ITS ticks; the request is due
            # now, so it becomes admissible on the replica immediately (the
            # cluster-level admission wait is measured in cluster ticks
            # against the original arrival)
            req.arrival = self.replicas[i].t
            self.replicas[i]._submit(req)
            self._owner[req.uid] = i

    # -- migration ----------------------------------------------------------

    def migrate(self, uid: int, dst: int) -> None:
        """Move one live request to replica ``dst`` over the portable swap
        wire.  Public so tests (and external balancers) can force a
        migration; ``_migrate_tick`` drives it under hot-spot pressure."""
        src = self._owner[uid]
        if src == dst:
            return
        ticket = self.replicas[src].migrate_out(uid)
        self.migrations += 1
        self.migration_bytes += ticket.wire.nbytes
        self.replicas[dst].migrate_in(ticket)
        self._owner[uid] = dst

    def _can_host(self, eng: PagedEngine, rows: int) -> bool:
        """Destination fit check BEFORE detaching the victim: a migrated
        request that cannot splice would strand in MIGRATING."""
        if not eng.pool.n_free_slots:
            return False
        if not eng.pool.has_paged:
            return True
        reserved = sum(
            e.swap.n_blocks
            for e in eng.lc.in_state(ReqState.PREEMPTED_SWAPPED, ReqState.MIGRATING)
        )
        need = eng.pool.blocks_needed(rows)
        return need + reserved + eng.pool.watermark <= eng.pool.n_available_blocks

    def _migrate_tick(self) -> None:
        cfg = self.migration
        if not cfg.enabled or len(self.replicas) < 2:
            return
        for _ in range(cfg.max_per_tick):
            loads = [eng.pending_tokens for eng in self.replicas]
            hot = int(np.argmax(loads))
            cold = int(np.argmin(loads))
            if loads[hot] - loads[cold] < cfg.imbalance_tokens:
                return
            eng = self.replicas[hot]
            cands = [
                e for e in eng.lc.in_state(ReqState.RUNNING)
                if e.req.max_new - len(e.outputs) >= cfg.min_remaining
            ]
            vr = eng.scheduler.select_victim([e.req for e in cands])
            if vr is None:
                return
            victim = next(e for e in cands if e.req is vr)
            rows = int(eng.pool.lengths[victim.slot])
            if not self._can_host(self.replicas[cold], rows):
                return
            self.migrate(victim.uid, cold)

    # -- serving loop -------------------------------------------------------

    def step(self) -> List[RequestOutput]:
        """One cluster tick: dispatch arrivals (policy order, cost-scored
        routing), rebalance under hot-spot pressure, then step every
        replica that has work.  Returns the concatenated ``RequestOutput``
        stream — a migrated request keeps streaming under its uid with no
        duplicated deltas (its ``emitted`` cursor travels in the ticket)."""
        self._dispatch_tick()
        self._migrate_tick()
        outs: List[RequestOutput] = []
        for i, eng in enumerate(self.replicas):
            if eng._work_remaining():
                outs.extend(eng.step())
            self.occupancy[i].append(eng.pool.blocks_in_use)
        self.t += 1
        return outs

    def _work_remaining(self) -> bool:
        return bool(len(self.queue)) or any(
            eng._work_remaining() for eng in self.replicas
        )

    def run(self, max_steps: Optional[int] = None) -> Dict[int, RequestOutput]:
        """Serve until the global queue and every replica drain; returns
        ``{uid: final RequestOutput}`` (streaming deltas filtered)."""
        if max_steps is None:
            queued = list(self.queue.queue)
            pending = [r for eng in self.replicas for r in eng._inflight_requests()]
            chunks = self.replicas[0].chunk_tokens
            base = sum(
                r.max_new + -(-len(r.prompt) // chunks) for r in queued + pending
            )
            arrivals = [r.arrival for r in queued] + [0]
            max_steps = self.t + max(arrivals) + base * 4 + 16 + len(queued) + 8
        done: Dict[int, RequestOutput] = {}
        while self._work_remaining():
            if self.t > max_steps:
                raise RuntimeError(f"ClusterEngine did not drain in {max_steps} steps")
            for f in self.step():
                if f.finished:
                    done[f.uid] = f
        return done

    # -- telemetry ----------------------------------------------------------

    @property
    def admission_waits(self) -> List[int]:
        """First-admission latencies aggregated across replicas, in ENGINE
        ticks (directly comparable with a single ``PagedEngine``'s): a
        request's arrival is stamped with its replica's clock at dispatch,
        so the replica-recorded wait is the queue-to-prefill latency the
        routing decision produced.  Migrated requests never re-record (the
        destination adopts them pre-admitted)."""
        return [w for eng in self.replicas for w in eng.admission_waits]

    def admission_wait_p99(self) -> float:
        waits = self.admission_waits
        if not waits:
            return 0.0
        return float(np.percentile(np.asarray(waits, np.float64), 99))

    def occupancy_variance(self) -> float:
        """Variance across replicas of mean blocks-in-use per tick — the
        balance headline (0 for a perfectly even cluster)."""
        means = [float(np.mean(o)) if o else 0.0 for o in self.occupancy]
        return float(np.var(means))

    def telemetry(self) -> Dict[str, object]:
        return dict(
            drain_ticks=self.t,
            admission_wait_p99=self.admission_wait_p99(),
            admission_waits=list(self.admission_waits),
            migrations=self.migrations,
            migration_bytes=self.migration_bytes,
            occupancy_variance=self.occupancy_variance(),
            per_replica=[
                dict(
                    swap_ins=eng.swap_ins,
                    preemptions=eng.preempt_count,
                    migrations_in=eng.migrations_in,
                    migrations_out=eng.migrations_out,
                    prefix_hits=(
                        eng.pool.prefix_cache.hits
                        if eng.pool.prefix_cache is not None else 0
                    ),
                    mean_blocks=float(np.mean(o)) if (o := self.occupancy[i]) else 0.0,
                )
                for i, eng in enumerate(self.replicas)
            ],
        )
