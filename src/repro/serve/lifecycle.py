"""Per-request lifecycle state machine for the paged serving engine.

Every request the :class:`~repro.serve.engine.PagedEngine` touches owns one
:class:`LiveRequest` entry that moves through an explicit state machine::

    WAITING ──▶ PREFILLING ──▶ RUNNING ◀──▶ SPECULATING
                   │   ▲ │        │  ▲      (draft k + verify k+1; commit
                   │   │ │        │  │       or rollback returns to RUNNING)
                   │   │ │        │  │ (swap-in restores KV bit-exact)
                   │   │ │        ▼  │
                   │   │ └─▶ PREEMPTED_SWAPPED ──▶ MIGRATING
                   │   │          │       (host store handed to another
                   │   │          │        engine; swap-in there resumes
                   │   │          │        RUNNING / PREFILLING bit-exact)
                   │   │          ▼ (requeue; replay prompt + generated
                   │   └── PREEMPTED_RECOMPUTE     prefix through prefill)
                   └──────────────▲            RUNNING ──▶ FINISHED

``SPECULATING`` is the self-speculative decode sub-phase: the slot holds
*unverified* draft KV rows, provisionally extended outputs, and possibly
blocks allocated past the accepted frontier.  It can only exit back to
RUNNING — the engine rolls the speculation back to the last accepted token
(restore the pre-draft state carry, un-scatter rejected rows, release
speculative blocks, slice provisional outputs) before any preemption or
finish, so swap/recompute resume paths never see speculated state.

A request can now also reach FINISHED *early*: per-slot EOS/stop-token
detection in the decode scan (``finish_reason`` "eos"/"stop") or an
explicit ``engine.abort`` ("aborted") — from PREFILLING, RUNNING,
PREEMPTED_SWAPPED (the host swap store is dropped) or PREEMPTED_RECOMPUTE
(the queued replay is cancelled).  The same rule as preemption applies to
a SPECULATING request: it must roll back its pending drafts (releasing
speculative blocks and provisional tokens) and pass through RUNNING first
— the FINISHED-via-stop transition enforces the early-finish leak class
away.

All resource transitions (slot binding, block allocation, swap stores,
GLASS per-slot rows) happen *at* a state transition, never ad hoc: the
engine tick asks the lifecycle for this tick's swap-in / admission /
prefill / decode work and the :class:`Lifecycle` enforces that only legal
transitions occur.  Illegal transitions raise — a preempted request that
was never swapped out cannot be swapped in, a finished request cannot be
preempted, and so on.

Preemption comes in two flavors, chosen per victim by a cost model
(:func:`preemption_kind`):

* **swap** — the request's KV blocks are copied to a host-side store and
  freed (:meth:`BlockPool.swap_out`); resuming copies them back into
  freshly allocated blocks, bit-identical, so decode continues as if
  nothing happened.  Cost ∝ blocks held (bytes moved twice).
* **recompute** — the blocks are dropped and the request re-queued; on
  re-admission the prompt is replayed through the existing chunked
  prefill (running-sum GLASS stats reproduce the *identical* fused mask,
  because the replay uses the same chunk boundaries over the same prompt
  tokens) and the already-generated prefix is re-fed through the decode
  path as forced tokens (bit-identical KV, no new sampling).  Cost ∝
  tokens to replay.

Resumed streams are token-identical to preemption-free serving for greedy
AND seeded-sampled requests (the tested guarantee): per-request sampling
is counter-based — every draw is a pure function of (request seed,
generated position, logits) — so a replayed position regenerates the same
token and there is no engine-global RNG stream for preemption to shift.

**Shared-block ownership (prefix caching).**  With the pool's prefix
cache enabled, admission consults the cache first: a hit binds the cached
chain's blocks into the request's table under *shared* ownership
(refcounted; copy-on-write — every write lands past the fork point in
private blocks) and prefill resumes at ``cached_rows`` from the entry's
stat-sum / state-row snapshot.  Preemption respects sharing: swap-out
SKIPS shared blocks (the swapped request keeps its reference; only
private blocks move to host), recompute's ``pool.free`` decrefs shared
blocks instead of freeing them, speculative rollback never un-scatters
into a block with other owners (rollback rows live strictly past the
prompt — the pool raises if that invariant is ever violated), and an
abort in any state — including mid-prefill while holding shared blocks,
or while swapped out — releases exactly the references the request holds.

**Cross-engine migration (replica-sharded serving).**  ``MIGRATING`` is
the leg of the PREEMPTED_SWAPPED path a request takes when its host swap
store is in flight between two engines: the source performs a *full*
swap-out (shared prefix blocks are copied out too — physical block ids
are meaningless in another pool), records ``PREEMPTED_SWAPPED →
MIGRATING``, and detaches the entry; the destination adopts the entry in
MIGRATING and its swap-in tick splices the blocks + GLASS slot rows +
recurrent-state rows into its own pool, resuming at RUNNING (decode) or
PREFILLING (a chunk-boundary-aligned mid-prefill handoff whose partial
GLASS stat left-fold rides along and keeps accumulating).  An abort while
MIGRATING drops the host store — by construction it pins nothing on
either device, so both sides are already released.

The swap path also enforces a host-side *store cap*
(:attr:`PreemptionConfig.swap_store_cap_bytes`): when the resident bytes
of all swap stores would exceed it, the oldest swapped request degrades
``PREEMPTED_SWAPPED → PREEMPTED_RECOMPUTE`` — its host copy is dropped
and the replay path (identical by the recompute guarantee above) serves
the resume instead.
"""
from __future__ import annotations

from dataclasses import dataclass, field
from enum import Enum
from typing import Any, Dict, List, Optional

from .scheduler import Request


class ReqState(str, Enum):
    WAITING = "waiting"
    PREFILLING = "prefilling"
    RUNNING = "running"
    SPECULATING = "speculating"
    PREEMPTED_SWAPPED = "preempted_swapped"
    PREEMPTED_RECOMPUTE = "preempted_recompute"
    MIGRATING = "migrating"  # host swap store in flight between engines
    FINISHED = "finished"


_LEGAL = {
    ReqState.WAITING: {
        ReqState.PREFILLING,
        ReqState.FINISHED,  # abort before first admission
    },
    ReqState.PREFILLING: {
        ReqState.RUNNING,  # even max_new == 1 passes through RUNNING to finish
        ReqState.PREEMPTED_RECOMPUTE,  # partial prefill is cheaper to redo than to swap
        # migration-only: a chunk-boundary handoff swaps the partial prefill
        # out (KV blocks + state rows; the stat left-fold travels host-side)
        # so the destination engine resumes it without replaying — the cost
        # model's own preemption still always recomputes prefill victims
        ReqState.PREEMPTED_SWAPPED,
        ReqState.FINISHED,  # abort mid-prefill (slot + blocks released first)
    },
    ReqState.RUNNING: {
        ReqState.FINISHED,  # length / eos / stop / abort
        ReqState.SPECULATING,
        ReqState.PREEMPTED_SWAPPED,
        ReqState.PREEMPTED_RECOMPUTE,
    },
    # SPECULATING is a sub-phase of RUNNING: the slot carries unverified
    # draft rows / provisional outputs.  The ONLY legal exit is back to
    # RUNNING (after commit or a full speculation rollback) — preempting,
    # finishing (including EOS/stop/abort), or swapping a mid-speculation
    # request directly would leak speculated KV rows, blocks, and
    # provisional tokens into the resume path, so the engine must roll the
    # speculation back first.  This is the early-finish leak-class guard:
    # a stop-finishing SPECULATING request takes SPECULATING -> RUNNING ->
    # FINISHED, with the rollback releasing its pending drafts in between.
    ReqState.SPECULATING: {ReqState.RUNNING},
    ReqState.PREEMPTED_SWAPPED: {
        ReqState.RUNNING,
        ReqState.MIGRATING,  # host store handed to another engine
        # swap-store cap overflow: the oldest store is dropped and the
        # request degrades to the recompute-replay resume path
        ReqState.PREEMPTED_RECOMPUTE,
        ReqState.FINISHED,  # abort: the host-side swap store is dropped
    },
    ReqState.PREEMPTED_RECOMPUTE: {
        ReqState.PREFILLING,
        ReqState.FINISHED,  # abort: the queued replay is cancelled
    },
    ReqState.MIGRATING: {
        ReqState.RUNNING,  # destination swap-in: decode resumes
        ReqState.PREFILLING,  # destination swap-in: mid-prefill handoff resumes
        ReqState.FINISHED,  # abort in flight: the host store pins nothing
    },
    ReqState.FINISHED: set(),
}


@dataclass
class SpecCheckpoint:
    """Everything needed to roll a request back to its last *accepted*
    token: taken when the request enters SPECULATING, dropped at commit.

    ``rows``/``out_len``/``pending`` snapshot the host-side progress;
    ``ensured`` is the KV-row capacity the speculative round reserved (the
    rollback zeroes ``[rows, ensured)`` and shrinks holdings back to
    ``rows``); ``state_rows`` is the device copy of the recurrent-state
    rows (the pre-draft state carry — None for pure-KV families)."""

    rows: int  # pool lengths[slot] at speculation entry
    ensured: int  # KV rows the round ensured capacity for (rows + k + 1)
    out_len: int  # len(outputs) at speculation entry
    pending: int  # next token to feed at speculation entry
    state_rows: Any = None


@dataclass(eq=False)
class LiveRequest:
    """One request's lifecycle entry: scheduling state + everything needed
    to resume it after preemption (host-side; device state lives in the
    pool / GLASS arenas and is re-bound at each transition).

    ``eq=False``: entries are identity objects (the engine keeps them in
    lists and sets); the default dataclass ``__eq__`` would compare ndarray
    prompts and raise."""

    req: Request
    state: ReqState = ReqState.WAITING
    slot: int = -1  # pool slot while PREFILLING / RUNNING, else -1
    prefill_pos: int = 0  # prompt tokens already prefilled
    # prefix-cache fork point of the CURRENT admission: prompt rows served
    # from shared cached blocks (prefill started at this position, with
    # stat sums / state rows restored from the cache entry's snapshot).
    # Reset at every admission — a recompute re-admission may fork at a
    # different depth than the first pass and still build the identical
    # fused mask (cached snapshots are left-folds of the same chunk sums).
    cached_rows: int = 0
    outputs: List[int] = field(default_factory=list)  # generated token ids
    pending: int = 0  # next token to feed into decode
    replay_left: int = 0  # forced re-feeds outstanding after a recompute resume
    pstats: Any = None  # running-sum GLASS stats while PREFILLING
    glass_rows: Any = None  # saved per-slot GLASS rows while PREEMPTED_SWAPPED
    glass_key: Optional[bytes] = None  # host block ids + tile scales (block_sparse)
    ffn_tiles: Any = None  # (L, n_tiles) bool tiles the key keeps (block_sparse)
    swap: Any = None  # BlockPool SwappedRequest while PREEMPTED_SWAPPED / MIGRATING
    swap_seq: int = -1  # swap-out order (cap overflow degrades the oldest store)
    admitted_step: int = -1  # latest admission (for prefill ordering)
    first_admitted_step: int = -1  # first admission (admission-latency metric)
    preemptions: int = 0
    # speculative decode: provisional draft tokens currently appended to
    # ``outputs`` (unverified — anything reading outputs as ground truth,
    # e.g. recompute's forced-token replay, must slice them off first) and
    # the rollback checkpoint while SPECULATING
    spec_len: int = 0
    spec_ckpt: Optional[SpecCheckpoint] = None
    # per-request generation policy, resolved against the engine defaults at
    # submit (sp: SamplingParams; gp: GlassParams with every field concrete)
    sp: Any = None
    gp: Any = None
    finish_reason: Optional[str] = None  # length | stop | eos | aborted
    emitted: int = 0  # accepted tokens already reported through step()
    # counter-based PRNG position: the next sampled token's counter.  The
    # engine maintains the invariant rng_pos == len(outputs) whenever the
    # entry is not mid-speculation — provisional draft tokens do NOT
    # advance it until the target tier accepts them, and rollback rewinds
    # it with outputs (the state-churn determinism tests assert this
    # counter against an undisturbed engine's).
    rng_pos: int = 0

    @property
    def uid(self) -> int:
        return self.req.uid


class Lifecycle:
    """Registry of live entries + the legal-transition checker.

    ``counts[(from, to)]`` tallies every transition taken — the engine's
    preemption telemetry and the tests' flow assertions both read it.
    """

    def __init__(self):
        self.entries: Dict[int, LiveRequest] = {}
        self.counts: Dict[tuple, int] = {}

    def add(self, req: Request) -> LiveRequest:
        if req.uid in self.entries and self.entries[req.uid].state is not ReqState.FINISHED:
            raise ValueError(f"request {req.uid} is already live")
        e = LiveRequest(req=req)
        self.entries[req.uid] = e
        return e

    def to(self, e: LiveRequest, new: ReqState) -> None:
        if new not in _LEGAL[e.state]:
            raise ValueError(f"illegal transition {e.state.value} -> {new.value} (uid={e.uid})")
        self.counts[(e.state.value, new.value)] = self.counts.get((e.state.value, new.value), 0) + 1
        e.state = new
        if new is ReqState.FINISHED and self.entries.get(e.uid) is e:
            # finished entries are dead weight (prompt + full token list):
            # prune so a long-lived engine stays O(in-flight), not O(served)
            del self.entries[e.uid]

    def detach(self, e: LiveRequest) -> None:
        """Remove a MIGRATING entry from this lifecycle: its host store (and
        with it the request) now belongs to another engine's lifecycle.  The
        PREEMPTED_SWAPPED → MIGRATING transition must already be recorded —
        detaching any other state would bypass the legality checker."""
        if e.state is not ReqState.MIGRATING:
            raise ValueError(f"detach of non-migrating entry (uid={e.uid}, {e.state.value})")
        if self.entries.get(e.uid) is e:
            del self.entries[e.uid]

    def adopt(self, e: LiveRequest) -> None:
        """Install a MIGRATING entry detached from another engine's
        lifecycle.  The entry arrives mid-machine (its transition history
        lives with the source), so adoption only checks liveness and state —
        every later move goes through :meth:`to` as usual."""
        if e.state is not ReqState.MIGRATING:
            raise ValueError(f"adopt of non-migrating entry (uid={e.uid}, {e.state.value})")
        if e.uid in self.entries and self.entries[e.uid].state is not ReqState.FINISHED:
            raise ValueError(f"request {e.uid} is already live")
        self.entries[e.uid] = e

    def in_state(self, *states: ReqState) -> List[LiveRequest]:
        return [e for e in self.entries.values() if e.state in states]

    def by_slot(self, slot: int) -> LiveRequest:
        for e in self.entries.values():
            if e.slot == slot and e.state in (
                ReqState.PREFILLING, ReqState.RUNNING, ReqState.SPECULATING
            ):
                return e
        raise KeyError(f"no live entry bound to slot {slot}")

    def preempted(self, *, kind: Optional[str] = None) -> int:
        """Total preemption transitions taken (optionally one kind).  The
        swap-cap degrade (PREEMPTED_SWAPPED → PREEMPTED_RECOMPUTE) is not a
        new preemption event — that victim was already counted at swap-out
        — so it is excluded here (the engine tallies it separately)."""
        total = 0
        for (src, dst), n in self.counts.items():
            if dst == ReqState.PREEMPTED_SWAPPED.value and kind in (None, "swap"):
                total += n
            elif (dst == ReqState.PREEMPTED_RECOMPUTE.value
                  and src != ReqState.PREEMPTED_SWAPPED.value
                  and kind in (None, "recompute")):
                total += n
        return total


@dataclass(frozen=True)
class PreemptionConfig:
    """Knobs for the swap-vs-recompute decision and the allocation reserve.

    ``mode="auto"`` picks per victim by comparing
    ``blocks_held * swap_cost_per_block`` (bytes copied out and back)
    against ``tokens_to_replay * recompute_cost_per_token`` (prompt +
    generated prefix re-run through prefill/forced decode).  The defaults
    make swap win for long contexts with little generated text and
    recompute win for short contexts — the vLLM-style tradeoff.
    ``watermark_blocks`` is the free-block reserve that *admissions* must
    leave untouched (running requests may grow into it), so a fresh
    admission cannot instantly force a preemption.

    ``swap_store_cap_bytes`` bounds the host-side residency of swap
    stores: when a new swap-out would push the engine's total resident
    store bytes past the cap, the OLDEST swapped request degrades to
    recompute (its store is dropped, it re-queues for the replay resume —
    streams stay identical by the recompute guarantee).  ``None`` (the
    default) leaves the store unbounded.
    """

    mode: str = "auto"  # auto | swap | recompute
    swap_cost_per_block: float = 2.0
    recompute_cost_per_token: float = 1.0
    watermark_blocks: int = 1
    swap_store_cap_bytes: Optional[int] = None

    def __post_init__(self):
        if self.mode not in ("auto", "swap", "recompute"):
            raise ValueError(f"unknown preemption mode {self.mode!r}")
        if self.swap_store_cap_bytes is not None and self.swap_store_cap_bytes < 0:
            raise ValueError(
                f"swap_store_cap_bytes must be >= 0 or None, got {self.swap_store_cap_bytes}"
            )


def preemption_kind(cfg: PreemptionConfig, blocks_held: int, tokens_to_replay: int) -> str:
    """Cost-model decision for one victim: ``"swap"`` or ``"recompute"``."""
    if cfg.mode != "auto":
        return cfg.mode
    swap_cost = blocks_held * cfg.swap_cost_per_block
    recompute_cost = tokens_to_replay * cfg.recompute_cost_per_token
    return "swap" if swap_cost < recompute_cost else "recompute"
