"""Per-rung compiled programs, with the next rungs up compiled ahead.

The drivers compile one program per window rung (see
``region_store.window_ladder``) and dispatch the one that covers the live
count, so only the rungs a run reaches are ever compiled.  A run climbs the
ladder one rung at a time, so the programs it needs next are known: while
the current rung runs, the next :data:`AHEAD` rungs compile in background
threads (an XLA compile releases the GIL), and a cold run waits for about
one compile in ``AHEAD + 1`` instead of one per rung.  A jitted function
that was lowered and compiled ahead does not compile again when called.

A recorder counts ``core.programs.ahead``, the compiles started ahead, and
at :meth:`RungPrograms.close` ``core.programs.ahead_unused``, the rungs
compiled or started ahead that the run never called.
"""

from __future__ import annotations

from concurrent.futures import Future, ThreadPoolExecutor
from typing import Callable, Optional

import jax
from jax.sharding import NamedSharding

from repro.telemetry import NULL

#: rungs compiled ahead of the one in use
AHEAD = 3


def _spec(x) -> jax.ShapeDtypeStruct:
    """Abstract argument the way a call passes it: a mesh-sharded array
    keeps its sharding; anything on one device is left unplaced.  Any other
    difference (a weak type, say) would make the call compile again."""
    sharding = getattr(x, "sharding", None)
    if not isinstance(sharding, NamedSharding):
        sharding = None
    return jax.ShapeDtypeStruct(
        x.shape, x.dtype, sharding=sharding, weak_type=jax.typeof(x).weak_type
    )


class RungPrograms:
    """``programs(rung, *args)`` returns the jitted program for ``rung``,
    built by ``build(rung)`` on first use, and starts compiling the next
    rungs of ``ladder`` for arguments shaped like ``args``."""

    def __init__(self, ladder, build: Callable[[int], Callable], recorder=NULL):
        self.ladder = tuple(ladder)
        self._build = build
        self._recorder = recorder
        self._fns: dict[int, Callable] = {}
        self._ahead: dict[int, Future] = {}
        self._called: set[int] = set()
        self._pool: Optional[ThreadPoolExecutor] = None

    def _fn(self, rung: int) -> Callable:
        fn = self._fns.get(rung)
        if fn is None:
            fn = self._fns[rung] = self._build(rung)
        return fn

    def __call__(self, rung: int, *args) -> Callable:
        fn = self._fn(rung)
        self._called.add(rung)
        pending = self._ahead.get(rung)
        if pending is not None:
            pending.result()  # calling fn before this ends compiles it twice
        i = self.ladder.index(rung)
        nxt = [w for w in self.ladder[i + 1 : i + 1 + AHEAD] if w not in self._ahead]
        if nxt:
            specs = jax.tree.map(_spec, args)
            if self._pool is None:
                self._pool = ThreadPoolExecutor(AHEAD, "rung-compile")
            for w in nxt:
                self._ahead[w] = self._pool.submit(
                    lambda f=self._fn(w): f.lower(*specs).compile()
                )
            self._recorder.count("core.programs.ahead", len(nxt))
        return fn

    def close(self) -> None:
        """Drop compiles not yet started (one under way runs to its end)."""
        if self._pool is not None:
            self._pool.shutdown(wait=False, cancel_futures=True)
        unused = [
            w
            for w, f in self._ahead.items()
            if w not in self._called and not f.cancelled()
        ]
        self._recorder.count("core.programs.ahead_unused", len(unused))
