"""Centralized compiled-program registry for the serving engines.

Every jitted program an engine builds (decode scan, prefill chunk, parallel
verify, ...) registers here, so the compiled-variant population is observable
in ONE place.  The engines deliberately bound recompilation by bucketing the
dynamic axes that would otherwise explode the jit cache:

  * gather width   — ``pow2_bucket`` over the block-table width ``nb``
  * scan horizon   — power-of-two ``H`` via the fused-decode horizon
  * glass mode     — a static of the program closure (one program per mode)

jax.jit keys its own cache on exactly those (shapes + statics), so the
variant count per program is the product of the buckets actually served —
NOT of the raw lengths.  ``ProgramCache.sizes()`` exposes the per-program
compiled counts (via the jitted callable's ``_cache_size``), which is what
the recompile-churn regression test asserts on: replaying an identical
workload must not add a single variant.
"""
from __future__ import annotations

from typing import Callable, Dict, Optional, Sequence

import jax

# the canonical bucket helper lives with the pool (widths are a pool
# property); re-exported here so program-cache users need one import
from .kv_pool import pow2_bucket  # noqa: F401


class ProgramCache:
    """Named registry of an engine's jitted entry points.

    ``register`` wraps a function with ``jax.jit`` and remembers the jitted
    callable; ``sizes``/``total`` report how many program variants each has
    compiled so far.  ``snapshot`` + ``misses_since`` give the churn between
    two points of a run — zero across a replay of an identical workload is
    the invariant the engines maintain.

    ``namespace`` scopes the reported names (``"replica1/decode"``): every
    engine owns its OWN registry (so cluster replicas can never collide on
    a ``register`` name, and each replica's programs follow its params onto
    its own device slice), and the namespace is what keeps the per-replica
    populations tellable apart when a cluster aggregates them for the
    churn accounting.
    """

    def __init__(self, namespace: str = "") -> None:
        self._fns: Dict[str, Callable] = {}
        self.namespace = namespace

    def _qual(self, name: str) -> str:
        return f"{self.namespace}/{name}" if self.namespace else name

    def register(
        self,
        name: str,
        fn: Callable,
        *,
        static_argnums: Sequence[int] = (),
        donate_argnums: Sequence[int] = (),
    ) -> Callable:
        if name in self._fns:
            raise ValueError(f"program {name!r} already registered")
        jitted = jax.jit(
            fn,
            static_argnums=tuple(static_argnums),
            donate_argnums=tuple(donate_argnums),
        )
        self._fns[name] = jitted
        return jitted

    def sizes(self) -> Dict[str, int]:
        """Compiled-variant count per registered program (namespace-qualified
        names when a namespace is set)."""
        return {self._qual(name): int(fn._cache_size()) for name, fn in self._fns.items()}

    def total(self) -> int:
        return sum(self.sizes().values())

    def snapshot(self) -> Dict[str, int]:
        """Alias of :meth:`sizes` named for the churn-accounting idiom."""
        return self.sizes()

    def misses_since(self, snap: Dict[str, int]) -> Dict[str, int]:
        """New compilations per program since ``snap`` (missing names count
        from zero)."""
        now = self.sizes()
        return {
            name: now[name] - snap.get(name, 0)
            for name in now
            if now[name] - snap.get(name, 0)
        }
