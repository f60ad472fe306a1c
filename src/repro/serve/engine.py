"""Serving engines with first-class GLASS integration.

Two engines share the same model API and GLASS pipeline:

``Engine`` — static batch (the original demo path): every request arrives
together, shares one prompt padding, and finishes together; masks are built
once for the whole batch.

``ContinuousEngine`` — continuous batching (the production path): a
``Scheduler`` queues requests, a ``KVPool`` holds a fixed slot arena, and
each request owns *per-slot* GLASS state — its own prefill-local stats,
fused mask, and compact-or-masked FFN weights, exactly the paper's
per-prompt dynamic selection.  Prefill is interleaved with ongoing decode;
finished sequences are evicted and their slots reused without recompiling
(decode is one jitted step over the full arena, per-slot lengths mask the
frontier).

Request lifecycle (paper Fig. 2 right), per slot in the continuous case:

  1. prefill the prompt, collecting local activation stats;
  2. fuse local stats with the offline global prior -> per-layer masks;
  3. gather compact FFN weights once, into the slot's row;
  4. steady-state decode with the compact weights (density * FLOPs/bytes).

``PagedEngine`` — the paged refactor of the continuous engine: a
``BlockPool`` block table replaces the slot arena (a request's KV footprint
is ``ceil(rows / block_size)`` blocks, not ``max_len``), prompts are
prefilled in bounded-token *chunks* interleaved with decode ticks (GLASS
local stats accumulate across chunks; the fused mask is finalized at the
last chunk), and admission follows a selectable ``AdmissionPolicy``.

``glass=None`` serves dense.  ``mode="masked"`` keeps full weights and
multiplies the mask in; ``"compact"`` gathers (the fast-memory-residency
deployment); ``"block_sparse"`` (with ``selection="block"``) feeds each
slot's active block list to the pallas ``glass_ffn`` kernel — the TPU-native
execution of the mask, reading only active weight tiles from HBM.
"""
from __future__ import annotations

import itertools
import warnings
from dataclasses import dataclass, replace
from functools import partial
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation

from ..core.fusion import GlassConfig, merge_stat_sums
from ..core.glass import (
    GlassParams,
    build_masks,
    build_tiered_masks,
    compact_params,
    reselect_at_density,
    restore_stat_sums,
    snapshot_stat_sums,
)
from ..kernels.ops import ffn_union
from ..models.api import Model
from ..models.transformer import layer_windows
from .kv_pool import (
    BlockPool,
    KVPool,
    SwappedWire,
    clear_slot_leaf,
    pow2_bucket as _pow2_bucket,
)
from .lifecycle import (
    Lifecycle,
    LiveRequest,
    PreemptionConfig,
    ReqState,
    SpecCheckpoint,
    preemption_kind,
)
from .programs import ProgramCache
from .sampling import MAX_STOP_IDS, SamplingParams, sample, sample_positional
from .scheduler import (
    AdmissionPolicy,
    FinishedRequest,
    Request,
    RequestOutput,
    Scheduler,
)


@dataclass
class GenerationResult:
    tokens: np.ndarray  # (B, max_new)
    logits_seq: Optional[np.ndarray]  # (B, max_new, V) when requested
    masks: Optional[object]


class Engine:
    def __init__(
        self,
        model: Model,
        params,
        *,
        glass: Optional[GlassConfig] = None,
        global_prior=None,
        glass_mode: str = "compact",  # compact | masked | block_sparse
    ):
        self.model = model
        # jitted callables keyed by static call signature: repeated generate()
        # calls with the same shapes must NOT re-trace (masks/compact weights
        # are traced arguments, so per-request GLASS state reuses the cache)
        self._jits: Dict[tuple, object] = {}
        self.params = params  # via the setter: owns _jits invalidation
        self.glass = glass
        self.prior = global_prior
        self.glass_mode = glass_mode
        if glass is not None:
            assert global_prior is not None, "GLASS needs the offline prior"
        if glass_mode == "block_sparse":
            assert glass is None or glass.selection == "block", \
                "block_sparse mode needs block-structured selection"
        if glass is not None and glass_mode == "compact" and glass.selection == "block":
            raise ValueError(
                "block selection yields block ids, not unit indices — "
                "use glass_mode='masked' or 'block_sparse' with it"
            )

    @property
    def params(self):
        return self._params

    @params.setter
    def params(self, new):
        # evict the jit cache when the weights change identity: entries are
        # keyed only on call signature, so a stale executable could otherwise
        # keep serving donated/retained buffers from the previous weights
        if new is not getattr(self, "_params", None):
            self._jits.clear()
        self._params = new

    def _prefill_fn(self, B: int, S: int, max_len: int):
        key = ("prefill", B, S, max_len)
        if key not in self._jits:
            model = self.model
            self._jits[key] = jax.jit(lambda p, t: model.prefill(p, {"tokens": t}, max_len))
        return self._jits[key]

    def _decode_fn(self, B: int, S: int, max_new: int, temperature: float, top_k: int,
                   return_logits: bool):
        key = ("decode", B, S, max_new, temperature, top_k, return_logits)
        if key not in self._jits:
            model = self.model

            bsz = self.glass.block_size if self.glass is not None else 128

            def pick(r, lg):
                if temperature <= 0.0:
                    return jnp.argmax(lg, axis=-1).astype(jnp.int32)
                return sample(r, lg, temperature=temperature, top_k=top_k).astype(jnp.int32)

            def decode_loop(params, cache, first_tok, rng, ffn_masks, compact, block_idx):
                def body(carry, i):
                    cache, tok, rng = carry
                    rng, krng = jax.random.split(rng)
                    lg, cache = model.decode_step(
                        params, tok[:, None], cache, S + i,
                        ffn_masks=ffn_masks, compact_layers=compact,
                        ffn_block_idx=block_idx, ffn_block_size=bsz,
                    )
                    nxt = pick(krng, lg[:, -1].astype(jnp.float32))
                    return (cache, nxt, rng), (nxt, lg[:, -1] if return_logits else jnp.zeros((B, 0)))

                (_, _, _), (toks, lgs) = jax.lax.scan(
                    body, (cache, first_tok, rng), jnp.arange(max_new, dtype=jnp.int32)
                )
                return toks.T, jnp.swapaxes(lgs, 0, 1)

            self._jits[key] = jax.jit(decode_loop)
        return self._jits[key]

    def generate(
        self,
        prompts: jax.Array,  # (B, S) int32, right-aligned/padded by caller
        max_new: int,
        *,
        rng: Optional[jax.Array] = None,
        temperature: float = 0.0,  # 0 => greedy
        top_k: int = 0,
        return_logits: bool = False,
    ) -> GenerationResult:
        model, params = self.model, self.params
        B, S = prompts.shape
        logits, cache, stats = self._prefill_fn(B, S, S + max_new)(params, prompts)

        masks = None
        compact = None
        ffn_masks = None
        block_idx = None
        if self.glass is not None:
            masks = build_masks(stats, self.prior, self.glass)
            if self.glass_mode == "compact":
                compact = compact_params(model, params, masks.idx)
            elif self.glass_mode == "block_sparse":
                block_idx = masks.idx  # (L, nb_keep) active block ids
            else:
                ffn_masks = masks.mask

        rng = rng if rng is not None else jax.random.key(0)
        rng, krng = jax.random.split(rng)
        if temperature <= 0.0:
            first = jnp.argmax(logits[:, -1].astype(jnp.float32), axis=-1).astype(jnp.int32)
        else:
            first = sample(krng, logits[:, -1].astype(jnp.float32),
                           temperature=temperature, top_k=top_k).astype(jnp.int32)
        decode_loop = self._decode_fn(B, S, max_new, temperature, top_k, return_logits)
        toks, lgs = decode_loop(params, cache, first, rng, ffn_masks, compact, block_idx)
        out_tokens = np.asarray(jnp.concatenate([first[:, None], toks[:, :-1]], axis=1))
        return GenerationResult(
            tokens=out_tokens,
            logits_seq=np.asarray(lgs) if return_logits else None,
            masks=masks,
        )


# ---------------------------------------------------------------------------
# Continuous batching
# ---------------------------------------------------------------------------


class GlassSlotState:
    """Per-slot GLASS state arenas for the continuous engine.

    ``masked`` keeps a float mask arena ((L, max_slots, m); MoE adds the
    expert axis, the hybrid shared block drops L).  ``compact`` keeps the
    per-slot stacked compact-weight pytree from ``compact_params`` with the
    slot axis sized ``max_slots``.  Arenas are created lazily on the first
    admission (that fixes every shape) and rows are overwritten/zeroed as
    slots turn over.  Multiple admissions in one step are fused into a
    single ``build_masks(..., slot_axis=True)`` + ``compact_params`` call.
    """

    def __init__(self, model: Model, params, gcfg: GlassConfig, prior, mode: str, max_slots: int):
        if mode not in ("masked", "compact", "block_sparse"):
            raise ValueError(mode)
        if mode == "block_sparse":
            if model.cfg.family not in ("dense", "vlm"):
                raise NotImplementedError("block-sparse decode targets dense-FFN families")
            if gcfg.selection != "block":
                raise ValueError("block_sparse mode needs GlassConfig(selection='block')")
        if mode == "compact" and gcfg.selection == "block":
            raise ValueError(
                "block selection yields block ids, not unit indices — "
                "use glass_mode='masked' or 'block_sparse' with it"
            )
        self.model = model
        self.params = params
        self.gcfg = gcfg
        self.prior = prior
        self.mode = mode
        self.max_slots = max_slots
        # tiered (self-speculative) serving: a second arena holds the DRAFT
        # tier's rows — same selection machinery at density * draft_ratio,
        # built from the same fused scores so draft units nest in the target
        self.tiered = gcfg.draft_ratio is not None
        # slot axis in both the stacked rows and the arena: after the leading
        # L axis everywhere except hybrid compact weights (no L axis at all)
        self.slot_axis = 0 if (model.cfg.family == "hybrid" and mode == "compact") else 1
        self.arena = None
        self.draft_arena = None
        ax = self.slot_axis
        tiered = self.tiered

        def write(arena, rows, slots):
            # one scatter for ALL slots admitted this tick (slots (B,) int32)
            def one(a, r):
                r = r.astype(a.dtype)
                return a.at[slots].set(r) if ax == 0 else a.at[:, slots].set(r)

            return jax.tree.map(one, arena, rows)

        def clear(arena, slot):
            return jax.tree.map(lambda a: clear_slot_leaf(a, ax, slot), arena)

        def tier_rows(params, ms):
            if mode == "masked":
                # hybrid keeps the (1, B, m) MaskSet layout: rank (not shape)
                # distinguishes per-slot from the legacy shared (1, m) mask
                return ms.mask  # (L, B, m) / (L, B, E, f) / hybrid (1, B, m)
            if mode == "block_sparse":
                # (L, B, nb_keep) active block ids + per-(row, tile) f32
                # contribution multipliers: all-ones at the engine density
                # (1.0 * tile is bitwise the unscaled tile), zeros on tiles a
                # lower per-request density drops — see _override_fn
                return {
                    "idx": ms.idx,
                    "scale": jnp.ones(ms.idx.shape, jnp.float32),
                }
            return compact_params(model, params, ms.idx)

        def rows(params, prior, stacked):
            if tiered:
                ms, ds = build_tiered_masks(stacked, prior, gcfg, slot_axis=True)
                return tier_rows(params, ms), tier_rows(params, ds)
            ms = build_masks(stacked, prior, gcfg, slot_axis=True)
            return tier_rows(params, ms), None

        def save(arena, slot):
            return jax.tree.map(
                lambda a: jax.lax.dynamic_slice_in_dim(a, slot, 1, axis=ax), arena
            )

        # jitted like KVPool's writers: admission-path mask fusion and
        # compaction, and slot writes/clears, must not dispatch eagerly; the
        # arena argument is dead after each call, so donate it
        self._rows = jax.jit(rows)
        self._write = jax.jit(write, donate_argnums=(0,))
        self._clear = jax.jit(clear, donate_argnums=(0,))
        self._save = jax.jit(save)
        # per-request density variants (GlassParams): jit cache keyed on the
        # (density, draft_density) pair — bounded by the distinct densities
        # the engine actually serves
        self._override_jits: Dict[tuple, object] = {}

    def _override_fn(self, density: float, draft_density: Optional[float]):
        """Row builder for a request whose densities differ from the engine
        config.  The engine config is the CAPACITY tier: per-request
        selections at a lower density nest inside it (same fused scores,
        same stable tie-break), so

          * ``masked`` builds the float mask directly at the request's own
            density (the arena is density-agnostic);
          * ``compact`` gathers at the capacity tier and ZEROES the
            down-projection rows (``w_down`` / rwkv ``wv``) of units
            outside the request's own selection — the unit's contribution
            becomes exactly zero, so the fixed-``k`` arena row computes the
            request's lower-density FFN bit-for-bit;
          * ``block_sparse`` keeps the capacity tier's block LIST (the
            kernel grid width is fixed per arena) and sets the per-(row,
            tile) ``scale`` of blocks outside the request's nested
            reselection to exactly 0.0 — a zero contribution added to the
            kernel accumulator is bitwise a no-op, so the row computes the
            lower-density FFN exactly while the tiles are still streamed
            (I/O is traded for not recompiling per request).
        """
        key = (density, draft_density)
        fn = self._override_jits.get(key)
        if fn is not None:
            return fn
        model, gcfg, mode, tiered = self.model, self.gcfg, self.mode, self.tiered
        hybrid = model.cfg.family == "hybrid"

        def restrict(rows_dict, valid):
            # zero the down-projection rows of gathered units outside the
            # request's nested selection; every other leaf may stay — any
            # path through the unit ends in the zeroed projection
            if hybrid:
                valid = valid[0]  # compact_params drops the shared L=1 axis
            return {
                k2: (v * valid[..., None].astype(v.dtype)
                     if k2 in ("w_down", "wv") else v)
                for k2, v in rows_dict.items()
            }

        def one_compact_tier(params, ms_cap, cap_density, req_density):
            rows_t = compact_params(model, params, ms_cap.idx)
            if req_density < cap_density - 1e-12:
                req_mask = reselect_at_density(ms_cap, gcfg, req_density).mask
                valid = jnp.take_along_axis(req_mask, ms_cap.idx, axis=-1)
                rows_t = restrict(rows_t, valid)
            return rows_t

        def one_block_tier(ms_cap, cap_density, req_density):
            # the capacity tier's block ids keep the arena (and the kernel
            # grid) fixed-width; the request's own lower-density selection
            # NESTS inside it (same consensus scores, same stable
            # tie-break), so reading the request's unit mask at each listed
            # block's first unit yields exactly {0.0, 1.0} tile multipliers
            idx = ms_cap.idx
            scale = jnp.ones(idx.shape, jnp.float32)
            if req_density < cap_density - 1e-12:
                req_mask = reselect_at_density(ms_cap, gcfg, req_density).mask
                scale = jnp.take_along_axis(
                    req_mask, idx * gcfg.block_size, axis=-1
                ).astype(jnp.float32)
            return {"idx": idx, "scale": scale}

        def rows(params, prior, stacked):
            if mode == "masked":
                ms = build_masks(
                    stacked, prior,
                    replace(gcfg, density=density, draft_ratio=None),
                    slot_axis=True,
                )
                dmask = None
                if tiered:
                    dmask = reselect_at_density(ms, gcfg, draft_density).mask
                return ms.mask, dmask
            one_tier = (
                one_block_tier if mode == "block_sparse"
                else partial(one_compact_tier, params)
            )
            if tiered:
                ms_cap, ds_cap = build_tiered_masks(stacked, prior, gcfg,
                                                    slot_axis=True)
                tgt = one_tier(ms_cap, gcfg.density, density)
                dft = one_tier(
                    ds_cap, gcfg.density * gcfg.draft_ratio, draft_density
                )
                return tgt, dft
            ms_cap = build_masks(stacked, prior, gcfg, slot_axis=True)
            return one_tier(ms_cap, gcfg.density, density), None

        fn = jax.jit(rows)
        self._override_jits[key] = fn
        return fn

    def _init_arena(self, rows):
        # the arena lives with the engine's params, committed where they are
        # (a replica's arenas never stage on the default device; migrated-in
        # rows arrive as host numpy and carry no placement of their own).
        # Uncommitted params keep it uncommitted: jit keys its cache on
        # commitment, so a mixed arena would compile every program twice
        ax = self.slot_axis
        leaf = jax.tree.leaves(self.params)[0]
        dev = leaf.sharding if leaf.committed else None
        return jax.tree.map(
            lambda r: jnp.zeros(
                r.shape[:ax] + (self.max_slots,) + r.shape[ax + 1 :], r.dtype,
                device=dev,
            ),
            rows,
        )

    def admit(self, slots: List[int], stats_list, overrides=None):
        """Fuse stats -> per-slot rows (both tiers when ``draft_ratio`` is
        set), scatter them into the arena(s), and return the freshly built
        TARGET rows (slot axis length ``len(slots)``) so the engine can
        keep host-side copies (e.g. the active-block lists behind its FFN
        tile counters) without re-reading the arena.

        ``overrides`` (optional, one entry per slot) carries a request's
        ``(density, draft_density)`` when it differs from the engine
        config — see :meth:`_override_fn` for how a lower density shares
        the fixed-capacity arena.  ``None`` entries take the engine-default
        (bit-identical to the pre-override build path).  The override path
        is single-slot (the paged engine finalizes one request per prefill
        chunk); batch admission with overrides would need per-slot row
        stacking to honor the return contract."""
        if overrides is not None and any(o is not None for o in overrides):
            assert len(overrides) == len(slots) == 1, "override admits are single-slot"
            (slot,), (st,), (ov,) = slots, stats_list, overrides
            stacked = jax.tree.map(lambda *xs: jnp.stack(xs), *[st])
            rows, drows = self._override_fn(*ov)(self.params, self.prior, stacked)
            idx = jnp.asarray([slot], jnp.int32)
            if self.arena is None:
                self.arena = self._init_arena(rows)
            self.arena = self._write(self.arena, rows, idx)
            if self.tiered:
                if self.draft_arena is None:
                    self.draft_arena = self._init_arena(drows)
                self.draft_arena = self._write(self.draft_arena, drows, idx)
            return rows
        stacked = jax.tree.map(lambda *xs: jnp.stack(xs), *stats_list)
        rows, drows = self._rows(self.params, self.prior, stacked)
        idx = jnp.asarray(slots, jnp.int32)
        if self.arena is None:
            self.arena = self._init_arena(rows)
        self.arena = self._write(self.arena, rows, idx)
        if self.tiered:
            if self.draft_arena is None:
                self.draft_arena = self._init_arena(drows)
            self.draft_arena = self._write(self.draft_arena, drows, idx)
        return rows

    def save(self, slot: int):
        """Device copy of the slot's rows, both tiers (swap-out keeps GLASS
        state)."""
        if self.arena is None:
            return None
        draft = self._save(self.draft_arena, jnp.int32(slot)) if self.tiered else None
        return (self._save(self.arena, jnp.int32(slot)), draft)

    def restore(self, slot: int, rows) -> None:
        """Write back rows captured by :meth:`save` at a (new) slot.  The
        arenas are lazily initialized from the rows' own shapes: a migrated
        request may land on an engine that has not admitted anything yet."""
        if rows is None:
            return
        target, draft = rows
        if self.arena is None:
            self.arena = self._init_arena(target)
        self.arena = self._write(self.arena, target, jnp.asarray([slot], jnp.int32))
        if draft is not None:
            if self.draft_arena is None:
                self.draft_arena = self._init_arena(draft)
            self.draft_arena = self._write(self.draft_arena, draft, jnp.asarray([slot], jnp.int32))

    def clear(self, slot: int) -> None:
        """Zero the slot's row in every tier's arena.  A zero mask / zero
        compact gather makes the FFN contribution of an inactive slot
        exactly zero — cheap hygiene on top of the engine never reading
        inactive slots' logits."""
        if self.arena is not None:
            self.arena = self._clear(self.arena, jnp.int32(slot))
        if self.draft_arena is not None:
            self.draft_arena = self._clear(self.draft_arena, jnp.int32(slot))


@dataclass
class MigrationTicket:
    """One request's complete host-side serving state in flight between two
    engines (cross-replica migration).

    Everything device-side travels in ``wire`` (KV blocks + recurrent-state
    rows, pool-independent — :class:`~repro.serve.kv_pool.SwappedWire`) and
    ``glass_rows`` (the GLASS slot rows, device_get to host numpy).
    Everything host-side is the request's lifecycle bookkeeping: the token
    stream, the forced-replay cursor, the counter-based PRNG position, and
    the resolved per-request policies — exactly the fields the destination
    needs to continue the stream bit-identically.  ``mid_prefill`` tickets
    carry ``pstats`` (the partial GLASS stat left-fold, host numpy) instead
    of ``glass_rows``: the mask is not finalized yet, so the destination
    resumes the chunked prefill at ``prefill_pos`` (always a chunk
    boundary — migration runs between ticks) and keeps folding.

    In-process this is a plain dataclass; a multi-process transport would
    serialize exactly these fields (the arrays are host numpy throughout).
    """

    req: Request
    sp: SamplingParams
    gp: GlassParams
    wire: SwappedWire
    outputs: List[int]
    pending: int
    replay_left: int
    rng_pos: int
    emitted: int
    preemptions: int
    prefill_pos: int
    mid_prefill: bool
    glass_rows: Any = None  # host copy of GlassSlotState.save(slot), or None
    glass_key: Optional[bytes] = None  # block_sparse block ids + tile scales
    pstats: Any = None  # host stat-sum snapshot (mid-prefill tickets only)


class _QueueEngineBase:
    """Shared host-side plumbing for the queue-driven engines: submission,
    first-token sampling, finish bookkeeping, and the drain loop.
    Subclasses provide ``step()`` (one tick group) and ``_drain_budget()``
    (a safe upper bound on ticks to drain the current workload), and may
    hook ``_on_free`` for extra per-slot teardown."""

    def submit(self, req: Request) -> None:
        self.scheduler.submit(req)

    @property
    def n_active(self) -> int:
        return int(self.pool.active.sum())

    def _first_token(self, logits_last: np.ndarray) -> int:
        if self.temperature <= 0.0:
            return int(np.argmax(logits_last))
        self._rng, krng = jax.random.split(self._rng)
        return int(
            sample(krng, jnp.asarray(logits_last)[None], temperature=self.temperature,
                   top_k=self.top_k)[0]
        )

    def _on_free(self, slot: int) -> None:
        pass

    def _finish(self, slot: int, finished: List[FinishedRequest]) -> None:
        r = self.live[slot]
        finished.append(
            FinishedRequest(
                uid=r.uid,
                prompt=np.asarray(r.prompt, np.int32),
                tokens=np.asarray(self.outputs[slot], np.int32),
                arrival=r.arrival,
                admitted_step=self.admitted_step[slot],
                finished_step=self.t,
            )
        )
        self.pool.free(slot)
        if self.glass_slots is not None:
            self.glass_slots.clear(slot)
        self.live[slot] = None
        self.outputs[slot] = None
        self.pending[slot] = 0
        self._on_free(slot)

    def _inflight_requests(self) -> List[Request]:
        return [r for r in self.live if r is not None]

    def _work_remaining(self) -> bool:
        return bool(len(self.scheduler) or self.pool.active.any())

    def run(self, requests=(), max_steps: Optional[int] = None) -> Dict[int, FinishedRequest]:
        """Serve until queue and slots drain; returns {uid: finished output}
        (legacy ``FinishedRequest``, or the structurally-superset final
        ``RequestOutput`` from the streaming paged engine — streaming
        deltas are filtered out here)."""
        for r in requests:
            self.submit(r)  # the subclass's validation applies
        if max_steps is None:
            queued = list(self.scheduler.queue)
            live = self._inflight_requests()
            budget = self._drain_budget(queued, live)
            arrivals = [r.arrival for r in queued] + [0]
            max_steps = self.t + max(arrivals) + budget + len(queued) + self.pool.max_slots + 8
        done: Dict[int, FinishedRequest] = {}
        while self._work_remaining():
            if self.t > max_steps:
                raise RuntimeError(
                    f"{type(self).__name__} did not drain in {max_steps} steps"
                )
            for f in self.step():
                if getattr(f, "finished", True):
                    done[f.uid] = f
        return done


class ContinuousEngine(_QueueEngineBase):
    """Continuous-batching server: admit-as-slots-free, decode over a fixed
    arena, evict on completion.

    Greedy by default (``temperature=0``); with a temperature the sampled
    stream is deterministic given ``rng`` but not token-compatible with the
    static ``Engine`` (different rng consumption order).
    """

    def __init__(
        self,
        model: Model,
        params,
        *,
        max_slots: int = 8,
        max_len: int = 256,
        glass: Optional[GlassConfig] = None,
        global_prior=None,
        glass_mode: str = "compact",  # compact | masked
        temperature: float = 0.0,
        top_k: int = 0,
        rng: Optional[jax.Array] = None,
        decode_chunk: int = 8,  # max ticks fused into one jitted scan
    ):
        if glass is not None:
            assert global_prior is not None, "GLASS needs the offline prior"
        if model.cfg.is_encoder_decoder:
            raise NotImplementedError("continuous batching targets decoder LMs")
        self.model = model
        self.params = params
        self.temperature = temperature
        self.top_k = top_k
        self.pool = KVPool(model, max_slots, max_len)
        self.scheduler = Scheduler(max_len)
        self.glass_slots = (
            GlassSlotState(model, params, glass, global_prior, glass_mode, max_slots)
            if glass is not None
            else None
        )
        self.pending = np.zeros((max_slots,), np.int32)  # next token to feed, per slot
        self.outputs: List[Optional[List[int]]] = [None] * max_slots
        self.live: List[Optional[Request]] = [None] * max_slots
        self.admitted_step = [0] * max_slots
        self.t = 0  # engine step counter == decode ticks
        self.slot_steps = 0  # decode ticks x active slots (scheduling telemetry)
        self._rng = rng if rng is not None else jax.random.key(0)

        # prefill at the request's exact length (jit caches per length); the
        # cache is sized to the prompt so the pool insert stays minimal
        self._prefill = jax.jit(lambda pr, tk: model.prefill(pr, {"tokens": tk}, tk.shape[1]))

        mode = self.glass_slots.mode if self.glass_slots is not None else None
        # fused-decode horizon: whenever the scheduler can prove no admission
        # or eviction can happen for H ticks, H decode steps run as ONE jitted
        # scan — the host round-trip (the dominant per-token cost at small
        # scale) is paid once per chunk instead of once per token.  H is
        # bucketed to powers of two so at most log2(chunk)+1 variants compile.
        self.decode_chunk = max(1, decode_chunk)

        bsz = glass.block_size if glass is not None else 128
        n_tiles = model.cfg.d_ff // bsz

        def dec(pr, cache, lengths, toks, extra, rng, H):
            kw = {}
            if mode == "masked":
                kw["ffn_masks"] = extra
            elif mode == "compact":
                kw["compact_layers"] = extra
            elif mode == "block_sparse":
                # one union per call: every scan step decodes the same lists
                # (cleared slots hold 0.0 scales and drop out of it)
                ids, count, scale = ffn_union(extra["idx"], extra["scale"],
                                              n_tiles=n_tiles)
                kw["ffn_block_idx"] = ids
                kw["ffn_block_scale"] = scale
                kw["ffn_block_count"] = count
                kw["ffn_block_size"] = bsz

            def body(carry, _):
                cache, lengths, toks, rng = carry
                lg, cache = model.decode_step(pr, toks[:, None], cache, lengths, **kw)
                lg = lg[:, -1].astype(jnp.float32)
                rng, krng = jax.random.split(rng)
                if temperature > 0.0:
                    nxt = sample(krng, lg, temperature=temperature, top_k=top_k)
                else:
                    nxt = jnp.argmax(lg, axis=-1)
                nxt = nxt.astype(jnp.int32)
                return (cache, lengths + 1, nxt, rng), nxt

            (cache, _, _, rng), seq = jax.lax.scan(
                body, (cache, lengths, toks, rng), None, length=H
            )
            return seq, cache, rng  # seq (H, B)

        # the arena is dead after each chunk — donate it so XLA updates the
        # KV cache in place instead of copying max_slots * max_len every tick
        self._decode = jax.jit(dec, static_argnums=(6,), donate_argnums=(1,))

    # -- public API ---------------------------------------------------------

    def _horizon(self) -> int:
        """Largest safe fused-decode length: bounded by the first possible
        eviction (min remaining tokens of any active slot) and — when a free
        slot could accept it — the next queued arrival.  Bucketed to a power
        of two so the chunked decode compiles O(log chunk) variants."""
        active = np.nonzero(self.pool.active)[0]
        h = min(self.live[int(s)].max_new - len(self.outputs[int(s)]) for s in active)
        if self.pool.n_free and len(self.scheduler):
            na = self.scheduler.next_arrival()
            if na is not None:  # all remaining arrivals are in the future
                h = min(h, na - self.t)
        h = min(h, self.decode_chunk)
        p = 1
        while p * 2 <= h:
            p *= 2
        return p

    def step(self) -> List[FinishedRequest]:
        """One engine tick group: admit arrived requests into free slots
        (prefill interleaved with decode), then decode the largest provably
        safe chunk of tokens for every active slot.  Returns requests
        finished in this group."""
        finished: List[FinishedRequest] = []
        reqs = self.scheduler.pop_admissible(self.t, self.pool.n_free)
        if reqs:
            self._admit(reqs, finished)
        if self.pool.active.any():
            H = self._horizon()
            extra = self.glass_slots.arena if self.glass_slots is not None else None
            seq, cache, self._rng = self._decode(
                self.params,
                self.pool.cache,
                jnp.asarray(self.pool.lengths),
                jnp.asarray(self.pending),
                extra,
                self._rng,
                H,
            )
            self.pool.cache = cache
            seq = np.asarray(seq)  # (H, B)
            self.slot_steps += H * int(self.pool.active.sum())
            for s in np.nonzero(self.pool.active)[0]:
                s = int(s)
                self.pool.lengths[s] += H
                self.outputs[s].extend(int(x) for x in seq[:, s])
                self.pending[s] = seq[-1, s]
                if len(self.outputs[s]) >= self.live[s].max_new:
                    self._finish(s, finished)
            self.t += H
        else:
            na = self.scheduler.next_arrival()
            # idle: fast-forward to the next arrival instead of spinning
            self.t = max(self.t + 1, na if na is not None else self.t + 1)
        return finished

    def _drain_budget(self, queued: List[Request], live: List[Request]) -> int:
        return sum(r.max_new for r in queued) + sum(r.max_new for r in live)

    # -- internals ----------------------------------------------------------

    def _admit(self, reqs: List[Request], finished: List[FinishedRequest]) -> None:
        slots, stats_list = [], []
        for r in reqs:
            slot = self.pool.alloc()
            toks = jnp.asarray(np.asarray(r.prompt, np.int32))[None]
            logits, cache, stats = self._prefill(self.params, toks)
            first = self._first_token(np.asarray(logits[0, -1], np.float32))
            self.pool.write_prefill(slot, cache, len(r.prompt))
            self.pending[slot] = first
            self.outputs[slot] = [first]
            self.live[slot] = r
            self.admitted_step[slot] = self.t
            slots.append(slot)
            stats_list.append(stats)
        if self.glass_slots is not None:
            self.glass_slots.admit(slots, stats_list)
        for slot in slots:  # max_new == 1 completes without a decode tick
            if len(self.outputs[slot]) >= self.live[slot].max_new:
                self._finish(slot, finished)


# ---------------------------------------------------------------------------
# Paged continuous batching (block table + chunked prefill)
# ---------------------------------------------------------------------------


def ffn_tile_fetches(tiles: Sequence[np.ndarray]) -> int:
    """Weight-tile fetches of one decode step's ``glass_ffn`` grids, summed
    over layers.  ``tiles`` holds each decoding row's (L, n_tiles) bool map
    of the tiles it keeps at scale > 0.  Each layer's grid walks the union
    of those maps (``kernels.ops.ffn_union``) and streams each of its tiles
    once for all rows and query positions; its padding steps repeat the
    last id and fetch nothing, but an empty union still fetches the one
    tile its first step names."""
    union = np.logical_or.reduce(list(tiles))
    return int(np.maximum(union.sum(-1), 1).sum())


def attn_live_blocks(lengths: np.ndarray, steps: int, windows, block_size: int) -> int:
    """KV blocks holding live, window-capped rows that the queries of one
    decode call attend, summed over rows, queries and layers: row ``r``'s
    ``j``-th query sits at position ``lengths[r] + j`` and sees the rows
    within the layer's window of it, its own included.  ``windows`` is
    ``(window, layers)`` pairs."""
    q = np.asarray(lengths, np.int64)[:, None] + np.arange(steps)
    last = q // block_size
    total = 0
    for w, n in windows:
        total += n * int((last - np.maximum(q - w + 1, 0) // block_size).sum())
    return total + q.size * sum(n for _, n in windows)


class PagedEngine(_QueueEngineBase):
    """Continuous batching over a paged KV block table, driven by an
    explicit per-request lifecycle state machine (``serve.lifecycle``).

    Differences vs :class:`ContinuousEngine` (which is kept as the
    slot-arena reference — both are greedy-token-identical to single-request
    serving):

      * **memory** — a :class:`BlockPool` with *allocate-on-boundary*
        (``alloc_mode="incremental"``, the default): admission allocates
        only the first prefill chunk's blocks, and a request grows one
        block at a time as it crosses block boundaries, with a small
        watermark reserve kept free for growth.  ``alloc_mode="full"``
        restores the PR-2 behavior (the request's entire worst-case
        footprint reserved at admission) for comparison.
      * **preemption** — when growth fails under pressure, the scheduler
        picks a victim (lowest priority / latest deadline / newest first)
        and a cost model picks *swap* (KV blocks copied to a host store,
        restored bit-identical on swap-in) or *recompute* (blocks dropped,
        request re-queued; the prompt replays through chunked prefill —
        running-sum GLASS stats rebuild the identical fused mask — and the
        generated prefix re-feeds through decode as forced tokens).  Both
        paths resume with zero token-stream divergence for greedy AND
        seeded-sampled requests: sampling is counter-based (every draw is
        a pure function of (request seed, generated position, logits) —
        see ``serve.sampling.sample_positional``), so replay regenerates
        the stream bit-identically and no engine-global RNG state exists
        to shift.
      * **prefill** — prompts are processed in chunks of at most
        ``chunk_tokens`` per engine tick, interleaved with decode; the
        fused mask is built once, at the final chunk.
      * **decode** — one jitted step over the fixed ``max_slots`` decode
        batch reading through the block table, gather width bucketed to
        the longest active request.  In ``block_sparse`` mode each decode
        call merges the decoding rows' block lists into one union per
        layer, on the device, and the shared-list ``glass_ffn`` kernel
        streams each tile of it once for all rows, under per-row scales.
      * **admission** — ``AdmissionPolicy`` (FIFO / priority / deadline),
        best-effort under block availability net of the watermark reserve
        and the blocks owed to swapped-out requests awaiting swap-in.
      * **speculative decode** (``spec_k > 0``, per request) — the same
        weights under a more aggressive GLASS tier
        (``GlassConfig(draft_ratio=...)``, per-slot tiered masks built once
        at prefill finalize) draft ``k`` tokens per round in one fused
        scan; the target tier verifies all ``k + 1`` positions through the
        forced-token (ftoks/fmask) scan — the pre-override verdict at each
        step (argmax, or the positional sample for seeded requests) IS the
        target verdict — and the longest matching prefix plus one bonus
        token is accepted.  Rejected rows are un-scattered, speculative
        block growth is released in reverse order, and recurrent-state
        carries are replayed from the pre-draft checkpoint, so the pool is
        BIT-identical to never having speculated (the state-invariant
        suite in ``tests/test_speculative_decode.py`` enforces exactly
        that, including through mid-speculation preemption).  Requests
        with ``GlassParams(spec_k=0)`` interleave with speculating ones in
        the same tick via a plain decode over the non-participants.
      * **attention path** (``attn_mode``) — ``"gather"`` materializes the
        logical KV view through the block table before a reference
        attention (the fallback and correctness oracle);
        ``"paged_pallas"`` runs the fused paged-attention kernel
        (``kernels/paged_attention.py``): block-table indirection,
        causal/window masking, and online softmax in one pass, streaming
        only live blocks.  Greedy token streams are identical either way.
      * **speculative verify** (``verify_mode``) — ``"sequential"`` walks
        the ``k + 1`` verify positions through the unrolled decode scan;
        ``"parallel"`` scores all positions in ONE ``T``-wide forward
        (``Model.verify_steps``), bit-identical on every live KV row by
        construction (every KV-writing program is inline-compiled, never
        a ``lax.scan`` body — see the comment in the decode builder).
        ``"auto"`` picks parallel exactly when the family is stateless
        and ``attn_mode="paged_pallas"``.

    **Per-request generation API** (the streaming frontend): submit with
    :meth:`add_request` under request-scoped :class:`SamplingParams`
    (counter-based seeded sampling, EOS/stop sets detected inside the
    fused scan) and :class:`GlassParams` (density / draft_ratio / spec_k
    against the engine's capacity tier); consume
    :class:`~repro.serve.scheduler.RequestOutput` deltas from every
    :meth:`step`; cancel with :meth:`abort`.  The legacy
    ``submit(Request)`` / ``run(requests)`` pair keeps working (greedy at
    engine defaults) behind a DeprecationWarning.

    ``PagedEngine.step`` itself is a thin driver: each tick it asks the
    lifecycle for this tick's swap-in, admission, prefill, and decode
    work, in that order; all resource movement happens inside the state
    transitions.
    """

    def __init__(
        self,
        model: Model,
        params,
        *,
        max_slots: int = 8,
        max_len: int = 256,
        block_size: int = 16,
        num_blocks: Optional[int] = None,
        chunk_tokens: int = 32,
        glass: Optional[GlassConfig] = None,
        global_prior=None,
        glass_mode: str = "compact",  # compact | masked | block_sparse
        policy: AdmissionPolicy = AdmissionPolicy.FIFO,
        alloc_mode: str = "incremental",  # incremental | full
        preemption: Optional[PreemptionConfig] = None,
        spec_k: int = 0,  # default draft tokens per speculative round (0 = off)
        temperature: float = 0.0,  # legacy engine-global default (see sampling)
        top_k: int = 0,
        rng: Optional[jax.Array] = None,  # unused: sampling is counter-based
        decode_chunk: int = 8,  # max ticks fused into one jitted scan
        sampling: Optional[SamplingParams] = None,  # default SamplingParams
        prefix_cache: bool = False,  # content-addressed KV prefix reuse
        attn_mode: str = "gather",  # gather | paged_pallas (fused kernel)
        verify_mode: str = "auto",  # auto | sequential | parallel spec verify
    ):
        if glass is not None:
            assert global_prior is not None, "GLASS needs the offline prior"
        if model.cfg.is_encoder_decoder:
            raise NotImplementedError("continuous batching targets decoder LMs")
        if attn_mode not in ("gather", "paged_pallas"):
            raise ValueError(f"unknown attn_mode {attn_mode!r}")
        if verify_mode not in ("auto", "sequential", "parallel"):
            raise ValueError(f"unknown verify_mode {verify_mode!r}")
        if chunk_tokens < 1:
            raise ValueError(f"chunk_tokens must be >= 1, got {chunk_tokens}")
        if alloc_mode not in ("incremental", "full"):
            raise ValueError(f"unknown alloc_mode {alloc_mode!r}")
        if spec_k < 0:
            raise ValueError(f"spec_k must be >= 0, got {spec_k}")
        if spec_k and (glass is None or glass.draft_ratio is None):
            raise ValueError(
                "speculative decode needs GlassConfig(draft_ratio=...) — "
                "the draft model IS the same weights under the draft tier"
            )
        self.model = model
        self.params = params
        self.temperature = temperature
        self.top_k = top_k
        # the default per-request sampling policy: requests submitted without
        # SamplingParams inherit it.  The legacy engine-global
        # (temperature, top_k) pair maps onto it — with a temperature, each
        # request gets a stable uid-derived seed, so the "global" setting is
        # served by per-request counter-based streams (reproducible through
        # preemption/replay, unlike the old shared RNG stream).
        if sampling is not None:
            self.default_sampling = sampling
        elif temperature <= 0.0:
            self.default_sampling = SamplingParams.make_greedy()
        else:
            self.default_sampling = None  # per-uid seed derived at submit
        if rng is not None:
            warnings.warn(
                "PagedEngine(rng=...) is ignored: sampling is counter-based "
                "per request — pass SamplingParams(seed=...) (per request or "
                "as the engine `sampling` default) to vary streams",
                DeprecationWarning, stacklevel=2,
            )
        self._auto_uid = itertools.count()
        self._used_uids: set = set()  # every uid ever submitted (auto-uid guard)
        # per-uid (SamplingParams, GlassParams) resolved at submit; consumed
        # at admission, dropped at finish/abort
        self._policies: Dict[int, Tuple[SamplingParams, GlassParams]] = {}
        self.chunk_tokens = chunk_tokens
        self.alloc_mode = alloc_mode
        self.preempt_cfg = preemption if preemption is not None else PreemptionConfig()
        watermark = self.preempt_cfg.watermark_blocks if alloc_mode == "incremental" else 0
        # the cache namespace folds the model config (and the GLASS config,
        # which shapes the stat snapshots) into every chain key: prefix
        # chains are content-addressed by (token ids, model config)
        self.pool = BlockPool(model, max_slots, max_len, block_size, num_blocks,
                              watermark=watermark, prefix_cache=prefix_cache,
                              cache_namespace=repr((model.cfg, glass)))
        self.scheduler = Scheduler(max_len, policy=policy)
        self.glass = glass
        self.glass_slots = (
            GlassSlotState(model, params, glass, global_prior, glass_mode, max_slots)
            if glass is not None
            else None
        )
        self.lc = Lifecycle()
        self.t = 0
        self.slot_steps = 0  # decode ticks x decoding slots (scheduling telemetry)
        self.kv_row_ticks = 0  # allocated KV rows x ticks (memory telemetry)
        self.max_prefill_tokens_per_tick = 0
        # preemption / admission telemetry
        self.swap_bytes = 0  # bytes copied device -> host by swap-outs
        self.swap_ins = 0
        self.recompute_tokens = 0  # tokens dropped by recompute preemptions
        # host swap-store residency (PreemptionConfig.swap_store_cap_bytes)
        self.swap_store_bytes = 0  # resident host bytes across swapped entries
        self.swap_cap_evictions = 0  # swapped requests degraded to recompute
        self._swap_seq = itertools.count()  # swap-out order (cap evicts oldest)
        # cross-engine migration telemetry (driven by serve.cluster)
        self.migrations_out = 0
        self.migrations_in = 0
        self.migration_bytes = 0  # wire bytes exported by migrate_out
        # decode waste, summed over decode steps and layers (``counters``):
        # the tile fetches the glass_ffn grids make against the distinct
        # tiles the decoding rows keep, and the blocks the attention walks
        # (max_slots x the block table's width) against those holding the
        # decoding rows' live, window-capped K/V.  Every call over the
        # target tier counts; a speculative draft scan (whose tier's lists
        # have no host copy) does not
        self.ffn_tiles_read = 0
        self.ffn_tiles_union = 0
        self.attn_blocks_walked = 0
        self.attn_blocks_live = 0
        self.admission_waits: List[int] = []  # first-admission latency per request
        self.decode_chunk = max(1, decode_chunk)
        # speculative-decode knob + telemetry
        self.spec_k = spec_k
        self.spec_ticks = 0  # speculative rounds run
        self.spec_slot_ticks = 0  # speculative rounds x participating slots
        self.spec_drafted = 0  # draft tokens proposed
        self.spec_accepted = 0  # draft tokens accepted by the target tier
        self.spec_emitted = 0  # tokens emitted by speculative rounds (accepted + bonus)
        self.spec_rollbacks = 0  # per-slot rounds that rejected >= 1 draft token
        self.spec_rolled_back_rows = 0  # KV rows un-scattered by rollbacks

        mode = self.glass_slots.mode if self.glass_slots is not None else None
        self._mode = mode
        bsz = glass.block_size if glass is not None else 128
        has_paged = self.pool.has_paged
        axes_t, paged_t = self.pool.axes, self.pool.paged
        has_state = not all(jax.tree.leaves(self.pool.paged))
        if attn_mode == "paged_pallas" and not has_paged:
            raise ValueError(
                "attn_mode='paged_pallas' needs a paged KV cache — this "
                "family has no attention block table to fuse over"
            )
        self.attn_mode = attn_mode
        # per-layer windows of the attention the transformer decode step
        # walks (the hybrid's shared block and recurrent families: none)
        walks = has_paged and model.cfg.family not in ("hybrid", "ssm")
        self._attn_windows = (
            [(int(w), int(n)) for w, n in
             zip(*np.unique(np.asarray(layer_windows(model.cfg)), return_counts=True))]
            if walks else []
        )
        self._attn_layers = sum(n for _, n in self._attn_windows)
        if verify_mode == "parallel" and has_state:
            raise ValueError(
                "verify_mode='parallel' targets attention-backed families; "
                "recurrent state must advance token-by-token to stay "
                "bit-identical to sequential decode"
            )
        # auto: the fused kernel's query-on-grid construction is what makes
        # a T = k+1 verify forward bitwise equal to k+1 sequential ticks, so
        # the one-forward verify rides with attn_mode="paged_pallas" on
        # stateless families and stays sequential otherwise
        self._verify_parallel = verify_mode == "parallel" or (
            verify_mode == "auto" and not has_state and attn_mode == "paged_pallas"
        )
        self.verify_mode = verify_mode
        self.programs = ProgramCache()

        # the fused horizon H is carried by the (H, B) leading axis of
        # ftoks/fmask — the scan length and the per-H jit variants key off
        # that shape, so no separate static argument is needed.  All
        # per-request policy rides in traced (B,) vectors: pos0 (the
        # counter-based PRNG position of each slot's first emission this
        # scan), seeds/temp/topk/gmask (SamplingParams), and stop_ids
        # (the per-slot early-finish stop set, -1 padded).  ``sampled``
        # is the only policy static: an all-greedy batch compiles without
        # any sampling ops, preserving the PR-4 greedy program exactly.
        n_tiles = model.cfg.d_ff // bsz

        def mk_kw(extra, btab, dmask):
            kw = {}
            if mode == "masked":
                kw["ffn_masks"] = extra
            elif mode == "compact":
                kw["compact_layers"] = extra
            elif mode == "block_sparse":
                # the union of the decoding rows' lists, once per call (every
                # step of it decodes the same lists): each kept tile is read
                # once a step for all rows
                ids, count, scale = ffn_union(extra["idx"], extra["scale"], dmask,
                                              n_tiles=n_tiles)
                kw["ffn_block_idx"] = ids
                kw["ffn_block_scale"] = scale
                kw["ffn_block_count"] = count
                kw["ffn_block_size"] = bsz
            if has_paged:
                kw["block_table"] = btab
                kw["attn_mode"] = attn_mode
            return kw

        def dec(pr, arena, lengths, toks, btab, dmask, extra, ftoks, fmask,
                pos0, seeds, temp, topk, topp, minp, gmask, stop_ids, sampled):
            kw = mk_kw(extra, btab, dmask)

            def guard(old, new, ax, pg):
                # recurrent-state rows of non-decoding slots (free, or holding
                # a mid-prefill request whose state IS the live prefill carry)
                # must not absorb the dummy-token recurrence; paged KV writes
                # are already scoped to live blocks by the trash-block table
                if pg:
                    return new
                m = dmask.reshape((1,) * ax + (-1,) + (1,) * (old.ndim - ax - 1))
                return jnp.where(m, new, old)

            def body(carry, xs):
                ft, fm = xs
                arena, lengths, pos, toks = carry
                lg, new = model.decode_step(pr, toks[:, None], arena, lengths, **kw)
                arena = jax.tree.map(guard, arena, new, axes_t, paged_t) if has_state else new
                lg = lg[:, -1].astype(jnp.float32)
                # the pre-override verdict: what the model WOULD emit at this
                # position — greedy argmax, or (for seeded slots) the
                # counter-based positional sample, a pure function of
                # (seed, position, logits).  Under forced re-feeds this is
                # exactly the target-tier verdict the speculative verify
                # pass accepts draft tokens against — greedy and sampled
                # requests alike.
                greedy = jnp.argmax(lg, axis=-1).astype(jnp.int32)
                if sampled:
                    samp = sample_positional(
                        lg, seeds, pos, temp, topk, top_p=topp, min_p=minp
                    )
                    verdict = jnp.where(gmask, greedy, samp)
                else:
                    verdict = greedy
                # recompute replay / speculative verify: re-feed the recorded
                # token instead of the fresh verdict — KV rebuilds
                # bit-identical (the positional draw would regenerate the
                # same token anyway; the override makes it structural)
                nxt = jnp.where(fm, ft, verdict)
                # early-finish detection inside the scan: the emitted token
                # against the slot's stop set (eos + stop ids, -1 padded);
                # forced re-feeds never re-trigger a stop
                hit = jnp.any(nxt[:, None] == stop_ids, axis=-1) & ~fm
                return (arena, lengths + 1, pos + 1, nxt), (nxt, verdict, hit)

            # UNROLLED, not lax.scan: XLA compiles a while-loop body with
            # different fusion choices than the same ops inlined, and the
            # two disagree at the last ulp deep in the layer stack.  Every
            # KV-writing program (this scan, the T-wide parallel verify, the
            # chunked prefill) must be inline-compiled so their stored rows
            # are bit-identical across programs — that is the invariant the
            # speculative state suite asserts.  H is pow2-bucketed by the
            # callers, so the unroll cost is bounded by the horizon buckets.
            carry = (arena, lengths, pos0, toks)
            outs = []
            for j in range(ftoks.shape[0]):
                carry, y = body(carry, (ftoks[j], fmask[j]))
                outs.append(y)
            arena = carry[0]
            seq, tgt, hits = (jnp.stack(z) for z in zip(*outs))
            return seq, tgt, hits, arena  # seq/tgt/hits (H, B)

        # the arena is dead after each call — donate so the block pool (and
        # state rows) update in place instead of copying every tick
        self._decode = self.programs.register(
            "decode", dec, static_argnums=(17,), donate_argnums=(1,)
        )

        # the parallel speculative verify: every feed of a verify round is
        # already known (pending + the k drafts, all forced), so stateless
        # families answer all k+1 positions with ONE T-wide forward instead
        # of a k+1-step scan.  The verdict math per position is byte-for-byte
        # the scan body's; the fused attention kernel runs each query as its
        # own grid program, so logits — and therefore verdicts and the KV
        # rows the round scatters — are BIT-identical to the sequential path
        # (the speculative state-invariant suite asserts it).
        def pver(pr, arena, lengths, feed, btab, dmask, extra, pos0, seeds,
                 temp, topk, topp, minp, gmask, sampled):
            kw = mk_kw(extra, btab, dmask)
            lg, arena = model.decode_step(pr, feed, arena, lengths, **kw)
            lg = lg.astype(jnp.float32)  # (B, T, V)
            greedy = jnp.argmax(lg, axis=-1).astype(jnp.int32)
            if sampled:
                Bf, Tf = feed.shape
                rep = lambda a: jnp.repeat(a, Tf, axis=0)
                pos = (
                    pos0[:, None] + jnp.arange(Tf, dtype=jnp.int32)[None]
                ).reshape(-1)
                samp = sample_positional(
                    lg.reshape(Bf * Tf, -1), rep(seeds), pos, rep(temp),
                    rep(topk), top_p=rep(topp), min_p=rep(minp),
                ).reshape(Bf, Tf)
                verdict = jnp.where(gmask[:, None], greedy, samp)
            else:
                verdict = greedy
            return verdict.swapaxes(0, 1), arena  # verdicts (k+1, B)

        self._pverify = self.programs.register(
            "verify_parallel", pver, static_argnums=(14,), donate_argnums=(1,)
        )

        axes, paged = self.pool.axes, self.pool.paged

        def chunk(pr, arena, toks, clen, btab, slot):
            # state leaves: slice this slot's rows out of the arena; paged
            # leaves pass through whole (the block table scopes the access)
            def take(a, ax, pg):
                return a if pg else jax.lax.dynamic_slice_in_dim(a, slot, 1, axis=ax)

            rows = jax.tree.map(take, arena, axes, paged)
            ckw = {"attn_mode": attn_mode} if has_paged else {}
            logits, new, stats = model.prefill_chunk(
                pr, toks, rows, clen,
                block_table=btab if has_paged else None, **ckw,
            )

            def put(a, n, ax, pg):
                if pg:
                    return n
                starts = [jnp.int32(0)] * a.ndim
                starts[ax] = slot
                return jax.lax.dynamic_update_slice(a, n.astype(a.dtype), starts)

            arena = jax.tree.map(put, arena, new, axes, paged)
            return logits[:, -1], arena, stats

        self._chunk = self.programs.register("chunk", chunk, donate_argnums=(1,))

    # -- public API ---------------------------------------------------------

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
        """The streaming frontend entry: enqueue one request under its own
        :class:`SamplingParams` (temperature / top-k / seed / stop set —
        ``None`` inherits the engine default, greedy unless configured) and
        :class:`GlassParams` (density / draft_ratio / spec_k — ``None``
        fields inherit the engine :class:`GlassConfig`).  Returns the
        request's uid (auto-assigned when not given).

        Consume results incrementally: every :meth:`step` returns
        :class:`RequestOutput` deltas for live requests (``new_tokens``)
        and a final ``finished=True`` output with a ``finish_reason``
        (``length | stop | eos | aborted``); :meth:`abort` cancels a
        request in any state, releasing its blocks/slot/GLASS rows through
        the lifecycle."""
        if uid is None:
            # _used_uids covers FINISHED requests too (Lifecycle prunes
            # their entries): an auto uid must never alias an earlier
            # request in a uid-keyed consumer's results, even a drained one
            uid = next(self._auto_uid)
            while uid in self._used_uids:  # covers queued + in-flight too
                uid = next(self._auto_uid)
        req = Request(
            uid=uid, prompt=np.asarray(prompt, np.int32), max_new=max_new,
            arrival=self.t if arrival is None else arrival,
            priority=priority, deadline=deadline,
            sampling=sampling, glass=glass,
        )
        self._submit(req)
        return uid

    def submit(self, req: Request) -> None:
        """Legacy frontend: a bare :class:`Request` decodes greedy (or the
        engine-global temperature) at the engine's GLASS config.  Kept as a
        deprecation shim over :meth:`add_request`."""
        warnings.warn(
            "PagedEngine.submit(Request) / run(requests) are the legacy "
            "frontend; use add_request(...) with SamplingParams/GlassParams "
            "and consume RequestOutput deltas from step()",
            DeprecationWarning, stacklevel=2,
        )
        self._submit(req)

    def _submit(self, req: Request) -> None:
        need = self.pool.blocks_needed(self._rows_needed(req))
        if self.pool.has_paged and need > self.pool.num_blocks - 1:
            raise ValueError(
                f"request {req.uid} needs {need} blocks > pool capacity "
                f"{self.pool.num_blocks - 1}"
            )
        # uids key the lifecycle entries, so a resubmission while the first
        # request is still queued or in flight must fail HERE, not crash at
        # admission (entries exist only from admission on, hence both checks)
        if req.uid in self.lc.entries or any(q.uid == req.uid for q in self.scheduler.queue):
            raise ValueError(f"request uid {req.uid} is already in flight")
        # resolve + validate per-request policy WITHOUT mutating the
        # caller's Request (the same object may be re-served through a
        # differently-configured engine); the admission tick binds the
        # resolved pair onto the LiveRequest entry
        self._policies[req.uid] = self._resolve_policy(req)
        self._used_uids.add(req.uid)
        _QueueEngineBase.submit(self, req)

    def _resolve_policy(self, req: Request) -> Tuple[SamplingParams, GlassParams]:
        """Resolve + validate the request's per-request policy against the
        engine defaults (the engine GlassConfig is the *capacity* tier)."""
        sp = req.sampling
        if sp is None:
            if self.default_sampling is not None:
                sp = self.default_sampling
            else:
                # legacy engine-global temperature: a stable uid-derived seed
                # keeps the stream reproducible through preemption/replay
                sp = SamplingParams(
                    temperature=self.temperature, top_k=self.top_k,
                    seed=(req.uid * 2654435761 + 97) % (2**31 - 1),
                )
        gp = (req.glass if req.glass is not None else GlassParams()).resolve(
            self.glass, self.spec_k
        )
        if self.glass is None:
            if gp.density is not None or gp.draft_ratio is not None:
                raise ValueError(
                    f"request {req.uid}: per-request GLASS params need an "
                    "engine-level GlassConfig (the engine serves dense)"
                )
            if gp.spec_k:
                raise ValueError(
                    f"request {req.uid}: spec_k > 0 needs an engine "
                    "GlassConfig(draft_ratio=...) draft tier"
                )
            return sp, gp
        eps = 1e-9
        if gp.density > self.glass.density + eps:
            raise ValueError(
                f"request {req.uid}: density {gp.density} exceeds the engine "
                f"capacity tier {self.glass.density} (per-request selections "
                "must nest inside the engine config's)"
            )
        if (req.glass is not None and req.glass.draft_ratio is not None
                and self.glass.draft_ratio is None):
            # consistent with density: a per-request knob the engine cannot
            # honor must raise, not silently do nothing
            raise ValueError(
                f"request {req.uid}: draft_ratio needs an engine "
                "GlassConfig(draft_ratio=...) draft arena"
            )
        if gp.spec_k:
            if self.glass.draft_ratio is None or gp.draft_ratio is None:
                raise ValueError(
                    f"request {req.uid}: spec_k > 0 needs an engine "
                    "GlassConfig(draft_ratio=...) draft tier"
                )
            if (gp.density * gp.draft_ratio
                    > self.glass.density * self.glass.draft_ratio + eps):
                raise ValueError(
                    f"request {req.uid}: draft density "
                    f"{gp.density * gp.draft_ratio} exceeds the engine draft "
                    f"capacity {self.glass.density * self.glass.draft_ratio}"
                )
        return sp, gp

    def abort(self, uid: int) -> Optional[RequestOutput]:
        """Cancel a request in any state, releasing every resource it holds
        through the lifecycle: a queued request is removed, a PREFILLING /
        RUNNING one frees its slot + blocks + GLASS rows, a SPECULATING one
        first rolls back its pending drafts (the only legal exit), a
        swapped one drops its host store, and a recompute-queued one is
        de-queued.  Returns the final aborted :class:`RequestOutput` (with
        whatever tokens were accepted so far), or None if the uid is not
        live."""
        e = self.lc.entries.get(uid)
        if e is None:
            r = self.scheduler.remove(uid)
            if r is None:
                return None
            e = self.lc.add(r)
            self.lc.to(e, ReqState.FINISHED)
            self._policies.pop(uid, None)
            e.finish_reason = "aborted"
            return self._output(e, finished=True, reason="aborted")
        if e.state is ReqState.FINISHED:
            return None
        if e.state is ReqState.SPECULATING:
            self._rollback_speculation(e)
        if e.state in (ReqState.PREFILLING, ReqState.RUNNING):
            self.pool.free(e.slot)
            if self.glass_slots is not None:
                self.glass_slots.clear(e.slot)
            e.slot = -1
            e.pstats = None
        elif e.state is ReqState.PREEMPTED_SWAPPED:
            # a swapped request keeps ownership refs on shared prefix
            # blocks it never copied to host — drop them or they leak
            self.pool.release_swapped(e.swap)
            self.swap_store_bytes -= e.swap.nbytes
            e.swap = None
            e.swap_seq = -1
            e.glass_rows = None
        elif e.state is ReqState.MIGRATING:
            # abort-while-migrating: a migration store is a FULL swap (no
            # kept refs on either pool) and was never charged to this
            # engine's host store — dropping it releases both sides
            e.swap = None
            e.glass_rows = None
            e.pstats = None
        elif e.state is ReqState.PREEMPTED_RECOMPUTE:
            self.scheduler.remove(uid)
        self.lc.to(e, ReqState.FINISHED)
        self._policies.pop(uid, None)
        e.finish_reason = "aborted"
        return self._output(e, finished=True, reason="aborted")

    # -- cross-engine migration (replica-sharded serving) --------------------

    def migrate_out(self, uid: int) -> MigrationTicket:
        """Detach a live request into a :class:`MigrationTicket` another
        engine can adopt (:meth:`migrate_in`), leaving nothing of it here.

        A SPECULATING victim rolls back to its last accepted token first
        (the only legal exit).  RUNNING requests carry their GLASS slot
        rows; PREFILLING ones are handed off at the current chunk boundary
        with the partial stat left-fold instead (migration runs between
        ticks, so ``prefill_pos`` is always chunk-aligned).  An already
        PREEMPTED_SWAPPED request migrates only when its store is fully
        private — a store with ``kept`` shared blocks pins physical ids in
        THIS pool and raises.

        The device state leaves via a FULL swap-out: shared prefix blocks
        are copied out like private ones (their ids mean nothing in the
        destination pool) and this request's references released — the
        source's prefix cache keeps serving other requests unaffected."""
        e = self.lc.entries.get(uid)
        if e is None:
            raise KeyError(f"request {uid} is not live on this engine")
        if e.state is ReqState.SPECULATING:
            self._rollback_speculation(e)
        mid_prefill = e.state is ReqState.PREFILLING
        glass_rows = None
        pstats = None
        if e.state in (ReqState.RUNNING, ReqState.PREFILLING):
            slot = e.slot
            if mid_prefill:
                pstats = jax.device_get(snapshot_stat_sums(e.pstats))
            elif self.glass_slots is not None:
                glass_rows = jax.device_get(self.glass_slots.save(slot))
            if self.glass_slots is not None:
                self.glass_slots.clear(slot)
            e.preemptions += 1
            sw = self.pool.swap_out(slot, full=True)
            self.swap_bytes += sw.nbytes
            self.lc.to(e, ReqState.PREEMPTED_SWAPPED)
            e.slot = -1
        elif e.state is ReqState.PREEMPTED_SWAPPED:
            mid_prefill = e.prefill_pos < len(e.req.prompt)
            sw = e.swap
            glass_rows = jax.device_get(e.glass_rows) if e.glass_rows is not None else None
            pstats = jax.device_get(snapshot_stat_sums(e.pstats)) if mid_prefill else None
            self.swap_store_bytes -= sw.nbytes
            e.swap_seq = -1
        else:
            raise ValueError(
                f"request {uid} is {e.state.value} — only RUNNING / "
                "SPECULATING / PREFILLING / PREEMPTED_SWAPPED requests migrate"
            )
        wire = self.pool.export_swap(sw)  # raises on kept (non-portable) stores
        self.lc.to(e, ReqState.MIGRATING)
        self.lc.detach(e)
        self._policies.pop(uid, None)
        e.swap = None
        e.glass_rows = None
        e.pstats = None
        self.migrations_out += 1
        self.migration_bytes += wire.nbytes
        return MigrationTicket(
            req=e.req, sp=e.sp, gp=e.gp, wire=wire,
            outputs=list(e.outputs), pending=e.pending,
            replay_left=e.replay_left, rng_pos=e.rng_pos, emitted=e.emitted,
            preemptions=e.preemptions, prefill_pos=e.prefill_pos,
            mid_prefill=mid_prefill, glass_rows=glass_rows,
            glass_key=e.glass_key, pstats=pstats,
        )

    def migrate_in(self, ticket: MigrationTicket) -> None:
        """Adopt a migrated request: rebuild its swap store against this
        pool (cross-pool splice) and install a MIGRATING entry.  The next
        :meth:`step`'s swap-in tick — where migrated requests compete in
        the same policy order as ordinary swap-ins, with the same first
        claim on capacity — splices the blocks and resumes RUNNING (decode)
        or PREFILLING (mid-prefill handoff)."""
        r = ticket.req
        e = LiveRequest(req=r)
        e.state = ReqState.MIGRATING
        e.sp, e.gp = ticket.sp, ticket.gp
        e.outputs = list(ticket.outputs)
        e.pending = ticket.pending
        e.replay_left = ticket.replay_left
        e.rng_pos = ticket.rng_pos
        e.emitted = ticket.emitted
        e.preemptions = ticket.preemptions
        e.prefill_pos = ticket.prefill_pos
        e.cached_rows = 0  # no shared blocks survive a cross-pool move
        e.glass_key = ticket.glass_key
        if e.glass_key is not None:
            e.ffn_tiles = self._tile_map(e.glass_key)
        e.swap = self.pool.adopt_wire(ticket.wire)
        e.glass_rows = ticket.glass_rows
        e.pstats = restore_stat_sums(ticket.pstats) if ticket.mid_prefill else None
        # admission-latency telemetry stays with the source engine: the
        # request was already admitted once, so the destination records
        # neither a wait nor a first admission
        e.admitted_step = self.t
        e.first_admitted_step = 0
        self.lc.adopt(e)
        self._policies[r.uid] = (e.sp, e.gp)
        self._used_uids.add(r.uid)
        self.migrations_in += 1

    # -- cluster admission inputs -------------------------------------------

    @property
    def pending_tokens(self) -> int:
        """Outstanding work in token units: un-prefilled prompt rows plus
        un-generated tokens, across the engine queue and every live entry.
        The cluster dispatcher's load estimate — token counts (not request
        counts) because GLASS per-request density/draft knobs make requests
        heterogeneous in cost."""
        w = 0
        for r in self.scheduler.queue:
            w += len(r.prompt) + r.max_new
        for e in self.lc.entries.values():
            if e.state is ReqState.FINISHED:
                continue
            done = len(e.outputs) - (e.spec_len if e.state is ReqState.SPECULATING else 0)
            w += max(0, len(e.req.prompt) - e.prefill_pos)
            w += max(0, e.req.max_new - done)
        return w

    def admission_cost_inputs(self, prompt=None) -> Dict[str, int]:
        """The per-replica signals the cluster dispatcher scores admissions
        with: free blocks net of the watermark reserve and the blocks owed
        to swapped/migrating requests, queue depth, outstanding token work,
        and (when ``prompt`` is given) the prefix-cache affinity probe —
        via the side-effect-free :meth:`BlockPool.peek_prefix`, so probing
        N replicas neither reorders any LRU nor skews hit-rate stats."""
        reserved = sum(
            e.swap.n_blocks
            for e in self.lc.in_state(ReqState.PREEMPTED_SWAPPED, ReqState.MIGRATING)
        )
        free = max(0, self.pool.n_available_blocks - self.pool.watermark - reserved)
        return dict(
            free_blocks=free,
            free_slots=self.pool.n_free_slots,
            queue_depth=len(self.scheduler),
            n_active=self.n_active,
            pending_tokens=self.pending_tokens,
            prefix_hit=(
                self.pool.peek_prefix(prompt, self.chunk_tokens)
                if prompt is not None else 0
            ),
        )

    @property
    def preempt_count(self) -> int:
        return self.lc.preempted()

    def _drain_budget(self, queued: List[Request], live: List[Request]) -> int:
        chunks = self.chunk_tokens
        base = sum(r.max_new + -(-len(r.prompt) // chunks) for r in queued + live)
        # preemption headroom: every swap/recompute round re-pays prefill
        # chunks and forced re-feeds; progress is still guaranteed (the
        # non-victim advances every tick) so a small multiple suffices
        return base * 4 + 16

    def _inflight_requests(self) -> List[Request]:
        return [
            e.req
            for e in self.lc.in_state(
                ReqState.PREFILLING, ReqState.RUNNING, ReqState.SPECULATING,
                ReqState.PREEMPTED_SWAPPED, ReqState.PREEMPTED_RECOMPUTE,
                ReqState.MIGRATING,
            )
        ]

    def _work_remaining(self) -> bool:
        return bool(
            len(self.scheduler)
            or self.pool.active.any()
            or self.lc.in_state(ReqState.PREEMPTED_SWAPPED, ReqState.MIGRATING)
        )

    def _rows_needed(self, r: Request) -> int:
        return len(r.prompt) + r.max_new - 1

    def _first_rows(self, r: Request) -> int:
        """Rows to allocate at admission: the first prefill chunk under
        incremental allocation, the full worst case under ``full``."""
        if self.alloc_mode == "full":
            return self._rows_needed(r)
        return min(self.chunk_tokens, len(r.prompt))

    def _fits(self, r: Request) -> bool:
        """Admission filter (satellite fix): under incremental allocation a
        request fits when its *first-chunk* blocks fit net of the watermark
        reserve and the blocks owed to swapped-out requests awaiting
        swap-in — not its full static need, which over-rejects, but also
        not raw free blocks, which would over-commit the pool."""
        if not self.pool.has_paged:
            return True
        if self.alloc_mode == "full":
            return self.pool.fits(self._rows_needed(r))
        reserved = sum(
            e.swap.n_blocks
            for e in self.lc.in_state(ReqState.PREEMPTED_SWAPPED, ReqState.MIGRATING)
        )
        return self.pool.fits_admission(self._first_rows(r), reserved)

    # -- per-request policy plumbing ----------------------------------------

    def _first_token_for(self, e: LiveRequest, logits_last: np.ndarray) -> int:
        """First post-prefill token under the request's own SamplingParams:
        greedy argmax, or the counter-based positional draw at position 0.
        Sampled exactly once per request — resume paths re-feed the
        recorded token instead of redrawing."""
        sp = e.sp
        if sp.is_greedy:
            return int(np.argmax(logits_last))
        return int(sample_positional(
            jnp.asarray(logits_last, jnp.float32)[None],
            jnp.asarray([np.int32(np.uint32(sp.seed))]),
            jnp.asarray([0], jnp.int32),
            jnp.asarray([sp.temperature], jnp.float32),
            jnp.asarray([sp.top_k], jnp.int32),
            top_p=jnp.asarray([sp.top_p], jnp.float32),
            min_p=jnp.asarray([sp.min_p], jnp.float32),
        )[0])

    def _glass_override(self, e: LiveRequest):
        """The (density, draft_density) pair for GlassSlotState.admit when
        the request's GLASS densities differ from the engine config's, else
        None (the engine-default build path, bit-identical to PR 4)."""
        if self.glass is None:
            return None
        gp = e.gp
        d = gp.density if gp.density is not None else self.glass.density
        dd = None
        cap_dd = None
        if self.glass.draft_ratio is not None:
            cap_dd = self.glass.density * self.glass.draft_ratio
            dr = gp.draft_ratio if gp.draft_ratio is not None else self.glass.draft_ratio
            dd = d * dr
        eps = 1e-9
        if abs(d - self.glass.density) <= eps and (
            dd is None or abs(dd - cap_dd) <= eps
        ):
            return None
        return (d, dd)

    def _policy_inputs(self, run: List[LiveRequest], *, with_stops: bool,
                       H_offset_ckpt: bool = False):
        """Fixed-width (``max_slots``) per-request policy vectors for one
        fused scan: the counter-based PRNG position of each slot's first
        emission, the SamplingParams fields, and the early-finish stop set.
        ``with_stops=False`` blanks the stop sets (draft/verify/fix-up
        scans handle stops host-side on the *accepted* tokens only).
        ``H_offset_ckpt=True`` takes positions from the speculative
        checkpoint (the verify scan runs after outputs were provisionally
        extended)."""
        B = self.pool.max_slots
        pos0 = np.zeros((B,), np.int32)
        seeds = np.zeros((B,), np.int32)
        temp = np.ones((B,), np.float32)
        topk = np.zeros((B,), np.int32)
        topp = np.ones((B,), np.float32)
        minp = np.zeros((B,), np.float32)
        gmask = np.ones((B,), bool)
        stop_ids = np.full((B, MAX_STOP_IDS), -1, np.int32)
        sampled = False
        for e in run:
            s = e.slot
            sp = e.sp
            if H_offset_ckpt:
                pos0[s] = e.spec_ckpt.out_len
            else:
                pos0[s] = len(e.outputs) - e.replay_left
            if not sp.is_greedy:
                sampled = True
                gmask[s] = False
                seeds[s] = np.int32(np.uint32(sp.seed))
                temp[s] = sp.temperature
                topk[s] = sp.top_k
                topp[s] = sp.top_p
                minp[s] = sp.min_p
            if with_stops:
                for j, t in enumerate(sp.stop_set):
                    stop_ids[s, j] = t
        return pos0, seeds, temp, topk, topp, minp, gmask, stop_ids, sampled

    # -- lifecycle transitions ----------------------------------------------

    def _output(self, e: LiveRequest, *, finished: bool,
                reason: Optional[str] = None) -> RequestOutput:
        """Build one streaming update for ``e`` and advance its ``emitted``
        cursor (``new_tokens`` is everything not yet reported)."""
        out = RequestOutput(
            uid=e.uid,
            prompt=np.asarray(e.req.prompt, np.int32),
            new_tokens=np.asarray(e.outputs[e.emitted:], np.int32),
            tokens=np.asarray(e.outputs, np.int32),
            finished=finished,
            finish_reason=reason,
            arrival=e.req.arrival,
            admitted_step=e.first_admitted_step,
            finished_step=self.t if finished else -1,
        )
        e.emitted = len(e.outputs)
        return out

    def _stop_reason(self, e: LiveRequest, tok: int) -> str:
        return "eos" if (e.sp is not None and tok == e.sp.eos_token_id) else "stop"

    def _finish(self, slot: int, finished: List[RequestOutput],
                reason: str = "length") -> None:
        e = self.lc.by_slot(slot)
        if e.state is ReqState.SPECULATING:
            # early-finish leak-class guard: pending drafts (provisional
            # tokens, speculative blocks, unverified KV rows) must roll
            # back before FINISHED — SPECULATING's only legal exit is
            # RUNNING, and the lifecycle enforces it
            self._rollback_speculation(e)
        e.finish_reason = reason
        finished.append(self._output(e, finished=True, reason=reason))
        self.pool.free(slot)
        if self.glass_slots is not None:
            self.glass_slots.clear(slot)
        self.lc.to(e, ReqState.FINISHED)
        self._policies.pop(e.uid, None)
        e.slot = -1
        e.pstats = None

    def _preempt(self, e: LiveRequest, kind: Optional[str] = None) -> None:
        """RUNNING/PREFILLING/SPECULATING -> PREEMPTED_{SWAPPED,RECOMPUTE}:
        release the slot and its blocks; swap keeps a bit-exact host copy,
        recompute re-queues for a prompt+prefix replay.

        A mid-speculation victim is first rolled back to its last ACCEPTED
        token.  Without that, ``Scheduler.requeue`` would carry the
        provisional draft tokens in ``outputs`` into the recompute resume,
        which replays ``outputs`` as *forced* decode tokens — the stream
        would contain speculated tokens the target tier never verified (and
        a swap would capture unverified KV rows + over-held blocks)."""
        if e.state is ReqState.SPECULATING:
            self._rollback_speculation(e)
        slot = e.slot
        if e.state is ReqState.PREFILLING:
            kind = "recompute"  # partial prefill: replaying is strictly cheaper
        if kind is None:
            kind = preemption_kind(
                self.preempt_cfg,
                self.pool.held_blocks(slot),
                int(self.pool.lengths[slot]),
            )
        e.preemptions += 1
        if kind == "swap":
            if self.glass_slots is not None:
                e.glass_rows = self.glass_slots.save(slot)
                self.glass_slots.clear(slot)
            e.swap = self.pool.swap_out(slot)
            self.swap_bytes += e.swap.nbytes
            self.swap_store_bytes += e.swap.nbytes
            e.swap_seq = next(self._swap_seq)
            self.lc.to(e, ReqState.PREEMPTED_SWAPPED)
            self._enforce_swap_cap()
        else:
            # tokens whose computation is dropped and must be replayed
            # (prompt progress + generated prefix written so far)
            self.recompute_tokens += int(self.pool.lengths[slot])
            if self.glass_slots is not None:
                self.glass_slots.clear(slot)
            self.pool.free(slot)
            e.pstats = None
            e.prefill_pos = 0
            e.glass_key = e.ffn_tiles = None
            e.replay_left = 0
            self.lc.to(e, ReqState.PREEMPTED_RECOMPUTE)
            self.scheduler.requeue(e.req)
        e.slot = -1

    def _enforce_swap_cap(self) -> None:
        """Host swap-store byte cap: while the resident store bytes exceed
        ``PreemptionConfig.swap_store_cap_bytes``, the OLDEST swapped
        request degrades to recompute.  Oldest-first because its store has
        waited longest without a swap-in slot — under sustained pressure it
        is the most likely to be re-queued behind newer work anyway, and
        dropping it frees the most bytes for the least expected re-read."""
        cap = self.preempt_cfg.swap_store_cap_bytes
        if cap is None:
            return
        while self.swap_store_bytes > cap:
            swapped = self.lc.in_state(ReqState.PREEMPTED_SWAPPED)
            if not swapped:
                break
            self._degrade_swapped(min(swapped, key=lambda x: x.swap_seq))

    def _degrade_swapped(self, e: LiveRequest) -> None:
        """PREEMPTED_SWAPPED -> PREEMPTED_RECOMPUTE: drop the host store
        and re-queue for the replay resume (prompt through chunked prefill,
        generated prefix as forced decode tokens — token-identical by the
        recompute guarantee).  Shared device blocks the store kept pinned
        are released like an abort would."""
        self.swap_store_bytes -= e.swap.nbytes
        self.recompute_tokens += e.swap.length
        self.pool.release_swapped(e.swap)
        e.swap = None
        e.swap_seq = -1
        e.glass_rows = None
        e.pstats = None
        e.prefill_pos = 0
        e.glass_key = e.ffn_tiles = None
        e.replay_left = 0
        self.lc.to(e, ReqState.PREEMPTED_RECOMPUTE)
        self.scheduler.requeue(e.req)
        self.swap_cap_evictions += 1

    def _preempt_for_capacity(self, protect: Optional[LiveRequest] = None) -> bool:
        """Pick one victim (scheduler policy, mirror of admission order)
        and preempt it.  Returns False when no victim is available."""
        victims = [
            v
            for v in self.lc.in_state(
                ReqState.RUNNING, ReqState.PREFILLING, ReqState.SPECULATING
            )
            if v is not protect
        ]
        vr = self.scheduler.select_victim([v.req for v in victims])
        if vr is None:
            return False
        self._preempt(next(v for v in victims if v.req is vr))
        return True

    def _swap_in_tick(self) -> None:
        """PREEMPTED_SWAPPED / MIGRATING -> RUNNING (or PREFILLING for a
        mid-prefill migration), policy order, as capacity allows.  Swapped
        requests have first claim on freed capacity (the admission filter
        reserves their blocks), and a swap-in keeps the watermark free
        unless nothing is running (then waiting would deadlock)."""
        waiting = sorted(
            self.lc.in_state(ReqState.PREEMPTED_SWAPPED, ReqState.MIGRATING),
            key=lambda e: self.scheduler.admission_key(e.req),
        )
        for e in waiting:
            if not self.pool.n_free_slots:
                return
            reserve = self.pool.watermark if self.pool.active.any() else 0
            if self.pool.has_paged and e.swap.n_blocks + reserve > self.pool.n_available_blocks:
                return
            migrating = e.state is ReqState.MIGRATING
            nbytes = e.swap.nbytes
            slot = self.pool.swap_in(e.swap)
            if slot is None:
                return
            if self.glass_slots is not None:
                self.glass_slots.restore(slot, e.glass_rows)
            e.glass_rows = None
            e.swap = None
            e.slot = slot
            if migrating and e.prefill_pos < len(e.req.prompt):
                # mid-prefill handoff: the splice restored the partial KV /
                # state rows and lengths[slot] == prefill_pos (a chunk
                # boundary); e.pstats carries the partial stat left-fold, so
                # the ordinary prefill tick continues the fold exactly where
                # the source stopped
                e.admitted_step = self.t
                self.lc.to(e, ReqState.PREFILLING)
            else:
                self.lc.to(e, ReqState.RUNNING)
            if not migrating:
                # migration tickets were never charged to this engine's
                # host store (they are transient, first-claim residents)
                self.swap_store_bytes -= nbytes
                e.swap_seq = -1
            self.swap_ins += 1

    def _admit_tick(self) -> None:
        """WAITING / PREEMPTED_RECOMPUTE -> PREFILLING, policy order,
        best-effort under ``_fits``."""
        while self.pool.n_free_slots:
            got = self.scheduler.pop_admissible(self.t, 1, fits=self._fits)
            if not got:
                return
            r = got[0]
            # an existing entry is a PREEMPTED_RECOMPUTE re-admission (its
            # generated prefix rides along for the replay); finished entries
            # are pruned at the FINISHED transition and can't appear here
            e = self.lc.entries.get(r.uid)
            if e is None:
                e = self.lc.add(r)
                # per-request policy, resolved at submit (legacy Requests
                # take the engine defaults); the caller's Request object is
                # never mutated
                e.sp, e.gp = self._policies[r.uid]
            # admission consults the prefix cache: a hit binds the cached
            # chain shared (CoW) and prefill resumes at the fork point from
            # the entry's stat-sum / state-row snapshot.  fork alignment to
            # chunk_tokens keeps resumed chunk boundaries identical to a
            # cold prefill's, so the stat left-fold (and the fused mask it
            # finalizes into) is bit-identical — recompute re-admissions
            # included.
            fork, entries = self.pool.lookup_prefix(r.prompt, self.chunk_tokens)
            slot = None
            if fork:
                rows = (
                    self._rows_needed(r) if self.alloc_mode == "full"
                    else fork + min(self.chunk_tokens, len(r.prompt) - fork)
                )
                slot = self.pool.admit_prefix(rows, entries)
                if slot is None:
                    # ``_fits`` counted the hit chain's own refcount-0 blocks
                    # as reclaimable supply, but binding the chain pins them
                    # — when the private remainder then cannot be allocated,
                    # degrade to a cold admission, whose first-chunk need is
                    # exactly what ``_fits`` verified (its allocation may
                    # evict the very chain we failed to pin)
                    self.pool.cancel_prefix_hit(fork)
                    fork = 0
            if slot is not None:
                e.prefill_pos = fork
                e.cached_rows = fork
                self.pool.lengths[slot] = fork
                tail = entries[-1]
                e.pstats = restore_stat_sums(tail.pstats)
                self.pool.restore_state_rows(slot, tail.state_rows)
            else:
                slot = self.pool.admit(self._first_rows(r))
                if slot is None:
                    # ``_fits`` held, so this is belt-and-braces: requeue
                    # (policy order preserved) and retry on a later tick
                    # rather than corrupting pool state
                    self.scheduler.requeue(r)
                    return
                e.prefill_pos = 0
                e.cached_rows = 0
                e.pstats = None
            self.lc.to(e, ReqState.PREFILLING)
            e.slot = slot
            e.admitted_step = self.t
            if e.first_admitted_step < 0:
                e.first_admitted_step = self.t
                self.admission_waits.append(self.t - r.arrival)

    # -- tick work ----------------------------------------------------------

    def _prefill_tick(self, finished: List[RequestOutput]) -> bool:
        """Run ONE bounded chunk for the oldest mid-prefill request."""
        pre = self.lc.in_state(ReqState.PREFILLING)
        if not pre:
            return False
        e = min(pre, key=lambda e: (e.admitted_step, e.uid))
        # chunks never cross the prompt boundary: GLASS running-sum stats
        # must cover EXACTLY the prompt tokens so a recompute replay (same
        # boundaries, same tokens) reproduces the identical fused mask
        T = min(self.chunk_tokens, len(e.req.prompt) - e.prefill_pos)
        with TraceAnnotation("engine.prefill", uid=e.uid, tokens=T):
            return self._prefill_chunk(e, T, finished)

    def _prefill_chunk(self, e: LiveRequest, T: int,
                       finished: List[RequestOutput]) -> bool:
        r = e.req
        slot = e.slot
        pos = e.prefill_pos
        while not self.pool.ensure_capacity(slot, pos + T):
            if not self._preempt_for_capacity(protect=e):
                # sole in-flight request: cannot happen (submit validates the
                # full need) — recompute-preempt as a safe fallback
                self._preempt(e, "recompute")
                return False
        toks = jnp.asarray(np.asarray(r.prompt[pos : pos + T], np.int32))[None]
        # gather width covers the *prefilled prefix* (every page written so
        # far plus this chunk), not the request's full allocation — early
        # chunks of a long-generation request must not attend max_len rows
        nb = _pow2_bucket(-(-(pos + T) // self.pool.block_size), self.pool.nb_max)
        btab = jnp.asarray(self.pool.block_table[slot : slot + 1, :nb])
        last, arena, stats = self._chunk(
            self.params, self.pool.cache, toks, jnp.asarray([pos], jnp.int32),
            btab, jnp.int32(slot),
        )
        self.pool.cache = arena
        self.pool.lengths[slot] = pos + T
        e.prefill_pos = pos + T
        # e.pstats is the FULL left-fold over [0, pos+T): on a cache hit the
        # restored snapshot already covers [0, fork), so merging each chunk
        # keeps the fold identical to a cold prefill's (same additions, same
        # association — merge_stat_sums docstring)
        e.pstats = merge_stat_sums(e.pstats, stats)
        end = pos + T
        # register the prefilled prefix: full blocks become cache entries
        # immediately (concurrent arrivals may hit a still-prefilling
        # request's prefix).  An entry is resumable only at a block+chunk
        # aligned boundary — there the stat fold and recurrent state match
        # what a cold prefill would hold at the same position.
        if self.pool.prefix_cache is not None:
            resumable = (
                end % self.pool.block_size == 0 and end % self.chunk_tokens == 0
            )
            self.pool.register_prefix(
                slot, r.prompt, end,
                resumable=resumable,
                pstats=snapshot_stat_sums(e.pstats) if resumable else None,
                state_rows=self.pool.save_state_rows(slot) if resumable else None,
            )
        self.max_prefill_tokens_per_tick = max(self.max_prefill_tokens_per_tick, T)
        if pos + T == len(r.prompt):  # final chunk: finalize GLASS + first token
            with TraceAnnotation("engine.prefill.finalize"):
                self._finalize_prefill(e, last, finished)
        return True

    def _finalize_prefill(self, e: LiveRequest, last, finished: List[RequestOutput]) -> None:
        """After the final chunk: the request's GLASS rows, then its first
        token (or the replay of its generated prefix)."""
        slot = e.slot
        if self.glass_slots is not None:
            rows = self.glass_slots.admit(
                [slot], [e.pstats], overrides=[self._glass_override(e)]
            )
            if self._mode == "block_sparse":
                # host copy of the (L, nb_keep) active-block list AND its
                # tile scales, and the tiles it keeps at scale > 0, for the
                # waste counters
                e.glass_key = (
                    np.asarray(rows["idx"][:, 0]).tobytes()
                    + np.asarray(rows["scale"][:, 0]).tobytes()
                )
                e.ffn_tiles = self._tile_map(e.glass_key)
        e.pstats = None
        self.lc.to(e, ReqState.RUNNING)
        if e.outputs:
            # recompute resume: the generated prefix is replayed through
            # decode as forced tokens — nothing is re-sampled (and the
            # counter-based draws would regenerate it bit-identically
            # anyway)
            e.pending = e.outputs[0]
            e.replay_left = len(e.outputs) - 1
        else:
            first = self._first_token_for(e, np.asarray(last[0], np.float32))
            e.outputs = [first]
            e.pending = first
            e.rng_pos = 1
            if first in e.sp.stop_set:
                self._finish(slot, finished, self._stop_reason(e, first))
            elif len(e.outputs) >= e.req.max_new:
                self._finish(slot, finished, "length")

    def _horizon(self, prefill_pending: bool) -> int:
        """Largest safe fused-decode length: 1 while any prefill is pending
        (chunks must interleave), else bounded by the first possible eviction
        and — when capacity could accept it — the next queued arrival."""
        if prefill_pending:
            return 1
        run = self.lc.in_state(ReqState.RUNNING)
        h = min(e.req.max_new - len(e.outputs) + e.replay_left for e in run)
        if self.pool.n_free_slots and len(self.scheduler):
            # only arrivals that could actually be admitted bound the chunk:
            # an arrived-but-unfitting request (block pressure) can only be
            # admitted after an eviction, and h is already bounded by the
            # first eviction — clamping on it would degrade decode to H=1
            na = min(
                (r.arrival for r in self.scheduler.queue if self._fits(r)),
                default=None,
            )
            if na is not None:
                h = min(h, max(1, na - self.t))
        h = min(h, self.decode_chunk)
        p = 1
        while p * 2 <= h:
            p *= 2
        return p

    def _growth_need(self, run: List[LiveRequest], H: int) -> int:
        """Blocks the pool must supply for every running slot to advance H
        tokens (allocate-on-boundary growth past current holdings)."""
        return sum(
            max(
                0,
                self.pool.blocks_needed(int(self.pool.lengths[e.slot]) + H)
                - self.pool.held_blocks(e.slot),
            )
            for e in run
        )

    def _scan_inputs(self, run: List[LiveRequest], H: int):
        """Fixed-width (``max_slots``) batch arrays for one fused scan over
        ``run``: decoding mask, per-slot lengths and first tokens, and a
        gather-width-bucketed block table covering every participant's rows
        plus ``H`` new ones (non-participants trash-redirected)."""
        B = self.pool.max_slots
        decoding = np.zeros((B,), bool)
        lengths = np.zeros((B,), np.int32)
        toks = np.zeros((B,), np.int32)
        for e in run:
            s = e.slot
            decoding[s] = True
            lengths[s] = self.pool.lengths[s]
            toks[s] = e.pending
        if self.pool.has_paged:
            need = int(max(lengths[e.slot] + H for e in run))
            nb = _pow2_bucket(-(-need // self.pool.block_size), self.pool.nb_max)
            btab = np.where(
                decoding[:, None], self.pool.block_table[:, :nb], 0
            ).astype(np.int32)
        else:
            btab = np.zeros((B, 1), np.int32)
        return decoding, lengths, toks, btab

    # -- decode waste counters ----------------------------------------------

    def counters(self) -> Dict[str, int]:
        """Every cumulative counter a measurement diffs over a window."""
        return dict(
            t=self.t, slot_steps=self.slot_steps, kv_row_ticks=self.kv_row_ticks,
            ffn_tiles_read=self.ffn_tiles_read, ffn_tiles_union=self.ffn_tiles_union,
            attn_blocks_walked=self.attn_blocks_walked,
            attn_blocks_live=self.attn_blocks_live,
        )

    @staticmethod
    def _key_lists(key: bytes) -> Tuple[np.ndarray, np.ndarray]:
        """The (L, nb_keep) block ids and tile scales a ``glass_key`` holds."""
        half = len(key) // 2
        return np.frombuffer(key[:half], np.int32), np.frombuffer(key[half:], np.float32)

    def _tile_map(self, key: bytes) -> np.ndarray:
        """(L, n_tiles) bool: the FFN tiles a request keeps (scale > 0)."""
        ids, scale = self._key_lists(key)
        L = self.model.cfg.n_layers
        m = np.zeros((L, self.model.cfg.d_ff // self.glass.block_size), bool)
        r, c = np.nonzero(scale.reshape(L, -1))
        m[r, ids.reshape(L, -1)[r, c]] = True
        return m

    def _count_decode(self, run: List[LiveRequest], lengths: np.ndarray, H: int,
                      T: int, nb: int) -> None:
        """Add one decode call to the waste counters: ``run`` decodes H
        steps of ``T`` queries from ``lengths`` through a block table
        ``nb`` wide.  Host copies only; nothing waits on the device."""
        B = self.pool.max_slots
        if self._attn_layers:
            self.attn_blocks_walked += H * T * self._attn_layers * B * nb
            self.attn_blocks_live += attn_live_blocks(
                lengths, H * T, self._attn_windows, self.pool.block_size)
        if self._mode != "block_sparse":
            return
        tiles = [e.ffn_tiles for e in run]
        self.ffn_tiles_read += H * ffn_tile_fetches(tiles)
        union = np.logical_or.reduce(tiles)
        self.ffn_tiles_union += H * T * int(np.count_nonzero(union))

    # -- speculative decode (draft tier -> multi-token verify -> rollback) ---

    def _spec_round(self, run: List[LiveRequest]) -> Tuple[List[LiveRequest], int]:
        """Participants + draft length for this tick's speculative round.

        Requests opt in per their own ``GlassParams.spec_k`` (engine
        ``spec_k`` is just the default), so ``spec_k=0`` requests — and
        recompute replays still re-feeding forced tokens, and requests
        within one token of finishing — simply sit the round out and take
        a plain H=1 decode in the SAME tick.  The round's draft length is
        the minimum over participants of ``min(spec_k, remaining - 1)``: a
        round emits up to k+1 tokens per slot and its verify writes k+1 KV
        rows, which must stay inside the request's row need
        (``len(prompt) + max_new - 1`` rows, validated at submit, also
        bounds the block table)."""
        if self.glass_slots is None or not self.glass_slots.tiered or not run:
            return [], 0
        parts = [
            e for e in run
            if e.gp.spec_k and not e.replay_left
            and e.req.max_new - len(e.outputs) >= 2
        ]
        if not parts:
            return [], 0
        k = min(
            min(e.gp.spec_k, e.req.max_new - len(e.outputs) - 1) for e in parts
        )
        return parts, max(0, k)

    def _spec_possible(self, run: List[LiveRequest]) -> int:
        """Compat helper (the state-invariant suite drives rounds by hand):
        the round's draft length when EVERY member of ``run`` participates,
        else 0 — the pre-partition semantics of :meth:`_spec_round`."""
        parts, k = self._spec_round(run)
        return k if len(parts) == len(run) else 0

    def _spec_capacity(self, run: List[LiveRequest], k: int) -> int:
        """Reserve ``k + 1`` KV rows of growth for every participant,
        halving ``k`` under block pressure (mirroring the fused-decode
        horizon shrink).  Never preempts: if even ``k = 1`` (2 rows of
        growth per slot) does not fit, it returns 0 and this tick falls
        back to plain decode, whose H=1 needs HALF the growth — evicting a
        victim here would drop work the non-speculative engine would have
        kept running (the plain path escalates to preemption itself only
        when 1 row per slot still does not fit)."""
        if not (self.pool.has_paged and self.alloc_mode == "incremental"):
            return k  # full-need admission reserved the worst case
        while k > 1 and self._growth_need(run, k + 1) > self.pool.n_available_blocks:
            k //= 2
        if self._growth_need(run, k + 1) > self.pool.n_available_blocks:
            return 0
        for e in run:
            if not self.pool.ensure_capacity(e.slot, int(self.pool.lengths[e.slot]) + k + 1):
                # the fit was measured against reclaimable slack that can
                # transiently exceed what eviction can drain (see
                # n_reclaimable_blocks) — fall back to plain decode, whose
                # growth path preempts if even H=1 cannot be supplied
                return 0
        return k

    def _spec_draft(self, run: List[LiveRequest], k: int) -> None:
        """Checkpoint every participant (RUNNING -> SPECULATING) and propose
        ``k`` draft tokens per slot under the DRAFT tier in one fused scan.

        Draft KV rows land in the request's real blocks — the verify pass
        overwrites every one of them with target-tier values, so no draft
        numerics survive — and draft-advanced recurrent state is restored
        from the checkpoint before verification.  Draft tokens are appended
        to ``outputs`` PROVISIONALLY (``spec_len`` marks them): nothing may
        read them as ground truth until the target tier accepts them."""
        with TraceAnnotation("engine.decode.prepare", H=k, rows=len(run)):
            for e in run:
                n = int(self.pool.lengths[e.slot])
                e.spec_ckpt = SpecCheckpoint(
                    rows=n, ensured=n + k + 1, out_len=len(e.outputs),
                    pending=e.pending, state_rows=self.pool.save_state_rows(e.slot),
                )
                self.lc.to(e, ReqState.SPECULATING)
            decoding, lengths, toks, btab = self._scan_inputs(run, k + 1)
            pos0, seeds, temp, topk, topp, minp, gmask, stop_ids, sampled = (
                self._policy_inputs(run, with_stops=False)
            )
            B = self.pool.max_slots
            # sampled slots draft with the SAME counter-based keys the target
            # verdict will use — proposal j for position out_len + j draws key
            # (seed, out_len + j) from the DRAFT logits, so a proposal matches
            # the verdict exactly when both tiers would emit the same token
            args = (
                self.params, self.pool.cache, jnp.asarray(lengths), jnp.asarray(toks),
                jnp.asarray(btab), jnp.asarray(decoding), self.glass_slots.draft_arena,
                jnp.zeros((k, B), jnp.int32), jnp.zeros((k, B), bool),
                jnp.asarray(pos0), jnp.asarray(seeds), jnp.asarray(temp),
                jnp.asarray(topk), jnp.asarray(topp), jnp.asarray(minp),
                jnp.asarray(gmask), jnp.asarray(stop_ids), sampled,
            )
        with TraceAnnotation("engine.decode.dispatch"):
            seq, _, _, arena = self._decode(*args)
        self.pool.cache = arena
        with TraceAnnotation("engine.decode.wait"):
            seq = np.asarray(seq)  # (k, B) draft proposals d_1..d_k
        with TraceAnnotation("engine.decode.commit"):
            for e in run:
                # provisional: rng_pos intentionally does NOT advance until
                # the target tier accepts
                e.outputs.extend(int(x) for x in seq[:, e.slot])
                e.spec_len = k

    def _spec_verify(self, run: List[LiveRequest], k: int,
                     finished: List[RequestOutput]) -> None:
        """Target-tier verification of all ``k + 1`` positions in ONE
        forced-token scan — the recompute-replay machinery re-purposed:
        step ``j`` feeds the round's j-th input token (``pending`` then the
        drafts) and the scan's pre-override verdict IS the target verdict
        ``t_j`` — the greedy argmax, or for seeded requests the
        counter-based positional sample from the pre-override logits (a
        pure function of (seed, position, logits), so draft/target
        exactness holds under sampling exactly as under greedy).  Accept
        the longest prefix with ``d_{j+1} == t_j`` plus the bonus token
        ``t_a``, then roll back everything past the accepted frontier: fix
        up recurrent state from the pre-draft carry, un-scatter rejected
        KV rows, release speculative blocks.  Accepted tokens that hit the
        request's stop set finish it early (truncated at the stop token,
        blocks freed this tick)."""
        with TraceAnnotation("engine.decode.prepare", H=k + 1, rows=len(run)):
            if self.pool.has_state:
                # the draft advanced recurrent state k steps under the draft
                # tier; verification must start from the pre-draft carry
                for e in run:
                    self.pool.restore_state_rows(e.slot, e.spec_ckpt.state_rows)
            decoding, lengths, toks, btab = self._scan_inputs(run, k + 1)
            pos0, seeds, temp, topk, topp, minp, gmask, stop_ids, sampled = (
                self._policy_inputs(run, with_stops=False, H_offset_ckpt=True)
            )
            B = self.pool.max_slots
            ftoks = np.zeros((k + 1, B), np.int32)
            fmask = np.zeros((k + 1, B), bool)
            for e in run:
                ck = e.spec_ckpt
                toks[e.slot] = ck.pending  # unchanged during draft, but explicit
                for j in range(k):
                    ftoks[j, e.slot] = e.outputs[ck.out_len + j]
                    fmask[j, e.slot] = True
            H, T = (1, k + 1) if self._verify_parallel else (k + 1, 1)
            self._count_decode(run, lengths[decoding], H, T, btab.shape[1])
            if self._verify_parallel:
                # ONE T = k+1 forward instead of the k+1-step scan: the feed
                # is fully known up front (pending + drafts, all forced), and
                # the per-query kernel grid keeps logits bitwise equal to the
                # scan
                feed = np.zeros((B, k + 1), np.int32)
                feed[:, 0] = toks
                for e in run:
                    ck = e.spec_ckpt
                    for j in range(k):
                        feed[e.slot, j + 1] = e.outputs[ck.out_len + j]
                args = (
                    self.params, self.pool.cache, jnp.asarray(lengths),
                    jnp.asarray(feed), jnp.asarray(btab), jnp.asarray(decoding),
                    self.glass_slots.arena, jnp.asarray(pos0), jnp.asarray(seeds),
                    jnp.asarray(temp), jnp.asarray(topk), jnp.asarray(topp),
                    jnp.asarray(minp), jnp.asarray(gmask), sampled,
                )
            else:
                args = (
                    self.params, self.pool.cache, jnp.asarray(lengths), jnp.asarray(toks),
                    jnp.asarray(btab), jnp.asarray(decoding), self.glass_slots.arena,
                    jnp.asarray(ftoks), jnp.asarray(fmask),
                    jnp.asarray(pos0), jnp.asarray(seeds), jnp.asarray(temp),
                    jnp.asarray(topk), jnp.asarray(topp), jnp.asarray(minp),
                    jnp.asarray(gmask), jnp.asarray(stop_ids), sampled,
                )
        with TraceAnnotation("engine.decode.dispatch"):
            if self._verify_parallel:
                tgt, arena = self._pverify(*args)
            else:
                _, tgt, _, arena = self._decode(*args)
        self.pool.cache = arena
        with TraceAnnotation("engine.decode.wait"):
            tgt = np.asarray(tgt)  # (k+1, B) target-tier verdicts
        with TraceAnnotation("engine.decode.commit"):
            self._spec_accept(run, k, tgt, finished)

    def _spec_accept(self, run: List[LiveRequest], k: int, tgt: np.ndarray,
                     finished: List[RequestOutput]) -> None:
        """The host half of :meth:`_spec_verify`: accept, roll back, fix
        up and finish each participant against the verdicts ``tgt``."""
        has_state = self.pool.has_state
        self.spec_ticks += 1
        self.spec_slot_ticks += len(run)
        self.spec_drafted += k * len(run)
        fixups: Dict[int, List[Tuple[int, SpecCheckpoint, List[int]]]] = {}
        to_finish: List[Tuple[int, str]] = []
        for e in run:
            s = e.slot
            ck = e.spec_ckpt
            drafts = e.outputs[ck.out_len :]
            a = 0
            while a < k and drafts[a] == int(tgt[a, s]):
                a += 1
            accepted = [int(tgt[j, s]) for j in range(a + 1)]
            if a < k:
                self.spec_rollbacks += 1
                self.spec_rolled_back_rows += ck.ensured - (ck.rows + a + 1)
                if has_state:
                    fixups.setdefault(a + 1, []).append((s, ck, accepted))
            self.pool.rollback_rows(s, ck.rows + a + 1, ck.ensured)
            if self.alloc_mode == "incremental":
                # full-need admission reserved (and keeps) the whole
                # footprint — shrinking would free blocks nothing ever
                # re-allocates, sending later KV writes to the trash block
                self.pool.shrink_to(s, ck.rows + a + 1)
            self.pool.lengths[s] = ck.rows + a + 1
            del e.outputs[ck.out_len :]
            e.outputs.extend(accepted)
            e.pending = accepted[-1]
            e.rng_pos = len(e.outputs)  # drafts committed: counter catches up
            e.spec_len = 0
            e.spec_ckpt = None
            self.lc.to(e, ReqState.RUNNING)
            stop_i = next(
                (i for i, t2 in enumerate(accepted) if t2 in e.sp.stop_set), None
            )
            # telemetry counts tokens that actually reach the stream: a
            # stop hit discards the accepted tail, so it must not inflate
            # the acceptance rate (accepted[a] is the bonus token)
            kept = len(accepted) if stop_i is None else stop_i + 1
            self.spec_accepted += min(a, kept)
            self.spec_emitted += kept
            if stop_i is not None:
                del e.outputs[ck.out_len + stop_i + 1 :]
                e.rng_pos = len(e.outputs)
                to_finish.append((s, self._stop_reason(e, e.outputs[-1])))
            elif len(e.outputs) >= e.req.max_new:
                to_finish.append((s, "length"))
        # state fix-ups BEFORE finishes: a stop-finishing rolled-back slot
        # must not have its (freed, zeroed) state row written afterwards
        for H, group in sorted(fixups.items()):
            self._spec_state_fixup(H, group)
        for s, reason in to_finish:
            self._finish(s, finished, reason)

    def _spec_state_fixup(
        self, H: int, group: List[Tuple[int, SpecCheckpoint, List[int]]]
    ) -> None:
        """Recurrent families only: the verify scan advanced the state
        ``k + 1`` steps but a rolled-back slot only had ``H = a + 1`` real
        feeds.  Restore each slot's pre-draft carry and replay exactly the
        accepted feeds (forced) through the same scan body — the state
        lands bit-identical to never having speculated.  Slots that share
        an accepted length batch into ONE scan; the scan length must equal
        the feed count, so the jit variants are bounded by ``spec_k + 1``
        (they cannot be pow2-bucketed like the gather widths — padding
        would advance the state past the accepted frontier).  The replay
        rewrites accepted KV rows with identical values (the rejected rows
        it would have read are excluded by the ``kv_len`` mask, so the
        earlier un-scatter does not perturb it); every other slot's table
        entry is trash-redirected and its state row is guarded by the
        decoding mask, so nothing else moves."""
        with TraceAnnotation("engine.decode.prepare", H=H, rows=len(group)):
            B = self.pool.max_slots
            decoding = np.zeros((B,), bool)
            lengths = np.zeros((B,), np.int32)
            toks = np.zeros((B,), np.int32)
            ftoks = np.zeros((H, B), np.int32)
            fmask = np.zeros((H, B), bool)
            rows_max = 1
            for slot, ck, accepted in group:
                self.pool.restore_state_rows(slot, ck.state_rows)
                decoding[slot] = True
                lengths[slot] = ck.rows
                toks[slot] = ck.pending
                rows_max = max(rows_max, ck.rows + H)
                for j in range(H - 1):
                    ftoks[j, slot] = accepted[j]
                    fmask[j, slot] = True
            if self.pool.has_paged:
                nb = _pow2_bucket(-(-rows_max // self.pool.block_size), self.pool.nb_max)
                btab = np.where(
                    decoding[:, None], self.pool.block_table[:, :nb], 0
                ).astype(np.int32)
            else:
                btab = np.zeros((B, 1), np.int32)
            # sampled=False: the replay's emissions are discarded (every real
            # feed is forced), so the greedy-compiled variant serves it
            args = (
                self.params, self.pool.cache, jnp.asarray(lengths), jnp.asarray(toks),
                jnp.asarray(btab), jnp.asarray(decoding), self.glass_slots.arena,
                jnp.asarray(ftoks), jnp.asarray(fmask),
                jnp.zeros((B,), jnp.int32), jnp.zeros((B,), jnp.int32),
                jnp.ones((B,), jnp.float32), jnp.zeros((B,), jnp.int32),
                jnp.ones((B,), jnp.float32), jnp.zeros((B,), jnp.float32),
                jnp.ones((B,), bool), jnp.full((B, MAX_STOP_IDS), -1, jnp.int32),
                False,
            )
        with TraceAnnotation("engine.decode.dispatch"):
            _, _, _, arena = self._decode(*args)
        self.pool.cache = arena

    def _rollback_speculation(self, e: LiveRequest) -> None:
        """SPECULATING -> RUNNING by discarding the round entirely: restore
        the pre-draft state carry, un-scatter every row the round wrote,
        release speculative block growth (reverse order, so the allocator
        stack is exactly pre-speculation), and slice the provisional draft
        tokens off ``outputs`` — downstream consumers (swap stores,
        recompute's forced-token replay) must only ever see accepted
        tokens."""
        ck = e.spec_ckpt
        self.pool.restore_state_rows(e.slot, ck.state_rows)
        self.pool.rollback_rows(e.slot, ck.rows, ck.ensured)
        if self.alloc_mode == "incremental":
            # see _spec_verify: full-need reservations must stay allocated
            self.pool.shrink_to(e.slot, ck.rows)
        self.pool.lengths[e.slot] = ck.rows
        self.spec_rolled_back_rows += ck.ensured - ck.rows
        self.spec_rollbacks += 1
        del e.outputs[ck.out_len :]
        e.pending = ck.pending
        e.rng_pos = len(e.outputs)  # counter rewinds with the outputs
        e.spec_len = 0
        e.spec_ckpt = None
        self.lc.to(e, ReqState.RUNNING)

    @property
    def spec_telemetry(self) -> Dict[str, float]:
        """Speculative-decode acceptance and rollback counters."""
        return dict(
            spec_ticks=self.spec_ticks,
            drafted_tokens=self.spec_drafted,
            accepted_tokens=self.spec_accepted,
            emitted_tokens=self.spec_emitted,
            draft_acceptance_rate=self.spec_accepted / max(self.spec_drafted, 1),
            accepted_tokens_per_tick=self.spec_emitted / max(self.spec_slot_ticks, 1),
            rollbacks=self.spec_rollbacks,
            rolled_back_rows=self.spec_rolled_back_rows,
        )

    def _fit_growth(self, run: List[LiveRequest], H: int
                    ) -> Tuple[List[LiveRequest], int]:
        """Allocate-on-boundary growth for one fused chunk: shrink H before
        shrinking the working set (a smaller H needs fewer boundary
        crossings than a preemption), then preempt victims until the
        remaining ``run`` fits.  Returns the surviving run and H.

        The fit check measures supply against reclaimable cache slack,
        which can transiently exceed what eviction can actually drain
        (see ``n_reclaimable_blocks``) — so a failed allocation after a
        passing check is recoverable pressure, answered by preempting
        another victim and re-fitting, not an invariant violation."""
        if not (self.pool.has_paged and self.alloc_mode == "incremental"):
            return run, H
        while H > 1 and self._growth_need(run, H) > self.pool.n_available_blocks:
            H //= 2
        while True:
            while self._growth_need(run, H) > self.pool.n_available_blocks:
                if not self._preempt_for_capacity():
                    break
                run = [e for e in run if e.state is ReqState.RUNNING]
                if not run:
                    return [], H
            if all(
                self.pool.ensure_capacity(e.slot, int(self.pool.lengths[e.slot]) + H)
                for e in run
            ):
                return run, H
            # partial growth is harmless (extra held blocks serve the next
            # tick); each retry preempts one victim, so this terminates
            if not self._preempt_for_capacity():
                return [], H
            run = [e for e in run if e.state is ReqState.RUNNING]
            if not run:
                return [], H

    def _decode_args(self, run: List[LiveRequest], H: int) -> tuple:
        """The decode program's arguments for one fused H-step scan over
        ``run`` (growth already ensured): per-slot sampling policy and forced
        replay re-feeds, copied to the device.  Counts the call into the
        decode telemetry."""
        B = self.pool.max_slots
        decoding, lengths, toks, btab = self._scan_inputs(run, H)
        pos0, seeds, temp, topk, topp, minp, gmask, stop_ids, sampled = (
            self._policy_inputs(run, with_stops=True)
        )
        ftoks = np.zeros((H, B), np.int32)
        fmask = np.zeros((H, B), bool)
        for e in run:
            s = e.slot
            f = min(H, e.replay_left)
            if f:  # forced re-feeds: outputs[n - replay_left : ...]
                start = len(e.outputs) - e.replay_left
                for j in range(f):
                    ftoks[j, s] = e.outputs[start + j]
                    fmask[j, s] = True
        self._count_decode(run, lengths[decoding], H, 1, btab.shape[1])
        extra = self.glass_slots.arena if self.glass_slots is not None else None
        return (
            self.params, self.pool.cache, jnp.asarray(lengths), jnp.asarray(toks),
            jnp.asarray(btab), jnp.asarray(decoding), extra,
            jnp.asarray(ftoks), jnp.asarray(fmask),
            jnp.asarray(pos0), jnp.asarray(seeds), jnp.asarray(temp),
            jnp.asarray(topk), jnp.asarray(topp), jnp.asarray(minp),
            jnp.asarray(gmask), jnp.asarray(stop_ids), sampled,
        )

    def _plain_decode(self, run: List[LiveRequest], H: int, args: tuple,
                      finished: List[RequestOutput]) -> None:
        """One fused H-step decode scan over ``run`` with the arguments
        :meth:`_decode_args` built: in-scan stop detection — a slot whose
        emitted token hits its stop set is truncated at the hit and
        finished (blocks freed) this tick."""
        with TraceAnnotation("engine.decode.dispatch"):
            seq, _, hits, arena = self._decode(*args)
        self.pool.cache = arena
        with TraceAnnotation("engine.decode.wait"):
            seq = np.asarray(seq)  # (H, B)
            hits = np.asarray(hits)  # (H, B) in-scan stop detections
        with TraceAnnotation("engine.decode.commit"):
            self.slot_steps += H * len(run)
            for e in run:
                s = e.slot
                self.pool.lengths[s] += H
                f = min(H, e.replay_left)
                e.replay_left -= f
                new = [int(x) for x in seq[f:, s]]
                hit_steps = np.nonzero(hits[f:, s])[0]
                if hit_steps.size:
                    new = new[: int(hit_steps[0]) + 1]
                e.outputs.extend(new)
                e.pending = int(seq[-1, s])
                e.rng_pos = len(e.outputs)
                if hit_steps.size:
                    self._finish(s, finished, self._stop_reason(e, e.outputs[-1]))
                elif len(e.outputs) >= e.req.max_new:
                    self._finish(s, finished, "length")

    def _decode_tick(self, finished: List[RequestOutput], prefill_pending: bool) -> bool:
        run = self.lc.in_state(ReqState.RUNNING)
        if not run:
            return False
        spec_run, k = self._spec_round(run)
        if k:
            k = self._spec_capacity(spec_run, k)
        if k:
            self._spec_draft(spec_run, k)
            self._spec_verify(spec_run, k, finished)
            # occupancy telemetry: a speculative round runs 2k+1 scan steps
            # (k draft + k+1 verify) per participating slot; memory
            # integrates post-rollback holdings for this tick
            self.slot_steps += (2 * k + 1) * len(spec_run)
            self.kv_row_ticks += self.pool.blocks_in_use * self.pool.block_size
            # spec_k=0 requests (and replays, and requests one token from
            # finishing) interleave in the SAME tick: a plain H=1 decode
            # over the non-participants
            spec_ids = {id(e) for e in spec_run}
            others = [
                e for e in self.lc.in_state(ReqState.RUNNING)
                if id(e) not in spec_ids
            ]
            if others:
                with TraceAnnotation("engine.decode.prepare") as span:
                    others, _ = self._fit_growth(others, 1)
                    span.set_metadata(H=1, rows=len(others))
                    args = self._decode_args(others, 1) if others else None
                if others:
                    self._plain_decode(others, 1, args, finished)
            self.t += 1
            return True
        with TraceAnnotation("engine.decode.prepare") as span:
            H = self._horizon(prefill_pending)
            run, H = self._fit_growth(run, H)
            if not run:
                return False
            span.set_metadata(H=H, rows=len(run))
            # memory telemetry: POST-growth holdings — blocks allocated for
            # this chunk's boundary crossings count for every tick they are
            # held
            self.kv_row_ticks += H * self.pool.blocks_in_use * self.pool.block_size
            args = self._decode_args(run, H)
        self._plain_decode(run, H, args, finished)
        self.t += H
        return True

    def step(self) -> List[RequestOutput]:
        """One engine tick: a thin driver over the lifecycle — swap-ins
        first (they have first claim on freed capacity), then admissions
        (policy order, best-effort under the watermark-aware filter), at
        most one bounded prefill chunk, then the largest provably safe
        fused decode chunk (speculative round + plain decode for the
        non-participants), preempting victims if growth outruns the pool.

        Returns the tick's :class:`RequestOutput` stream: one
        ``finished=True`` entry per request that completed (``length |
        stop | eos``; :meth:`abort` returns its own), plus one live delta
        (``new_tokens``) per request that accepted tokens this tick —
        consume them as they arrive for streaming generation."""
        with TraceAnnotation("engine.step"):
            return self._step()

    def _step(self) -> List[RequestOutput]:
        finished: List[RequestOutput] = []
        t0 = self.t
        with TraceAnnotation("engine.admit"):
            self._swap_in_tick()
            self._admit_tick()
        prefilled = self._prefill_tick(finished)
        with TraceAnnotation("engine.admit"):
            self._swap_in_tick()  # a finished max_new==1 request frees capacity
            self._admit_tick()
        # memory telemetry: blocks held by every in-flight request (decoding
        # AND mid-prefill); _decode_tick charges its own ticks post-growth,
        # this snapshot covers prefill-only / idle advances
        rows_now = self.pool.blocks_in_use * self.pool.block_size
        prefill_pending = bool(self.lc.in_state(ReqState.PREFILLING))
        decoded = self._decode_tick(finished, prefill_pending or prefilled)
        if not decoded:
            if prefilled:
                self.t += 1
            else:
                na = self.scheduler.next_arrival()
                self.t = max(self.t + 1, na if na is not None else self.t + 1)
            self.kv_row_ticks += (self.t - t0) * rows_now
        # streaming deltas for everything still live that grew this tick
        # (accepted tokens only: SPECULATING never persists across a tick,
        # so provisional drafts are never reported)
        with TraceAnnotation("engine.outputs"):
            for e in self.lc.in_state(
                ReqState.PREFILLING, ReqState.RUNNING,
                ReqState.PREEMPTED_SWAPPED, ReqState.PREEMPTED_RECOMPUTE,
                ReqState.MIGRATING,
            ):
                if len(e.outputs) > e.emitted:
                    finished.append(self._output(e, finished=False))
        return finished
