"""Future combinators at three levels of the TPU hierarchy.

The paper's ``Future[A]`` is a handle to a value being produced
asynchronously, forced by ``Await.result``.  JAX/XLA has no user-visible
threads, but it has the same concept at every level:

1. **Dataflow futures** (:class:`Future`): under ``jit`` every op is
   issued into a dataflow graph; a value "in flight" is simply one whose
   consumer hasn't been scheduled yet.  ``defer`` builds the value now
   (issuing its producer early), ``force`` pins a scheduling edge with
   ``lax.optimization_barrier`` so XLA cannot sink the producer to the
   consumption point — i.e. the async region is explicit, and on TPU the
   async collective/DMA actually overlaps the intervening compute.
2. **Collective futures** (``ppermute_future`` / ``all_gather_future``):
   issue the collective early, force late.  This is the manual
   compute/comm overlap idiom; XLA:TPU lowers these to async
   ``collective-permute-start/done`` pairs.
3. **Host futures** (:class:`HostFuture`): a thin wrapper over
   ``concurrent.futures`` used by the data pipeline (prefetch = the
   stream's future tail) and the checkpointer (async writes).

``jax.block_until_ready`` is the outermost ``Await.result``: JAX
dispatch is itself asynchronous, so every jitted call already returns a
future in the paper's sense.
"""
from __future__ import annotations

import concurrent.futures
import dataclasses
from typing import Any, Callable

import jax
import jax.numpy as jnp
from jax import lax

PyTree = Any


@dataclasses.dataclass
class Future:
    """A traced value plus an explicit not-yet-forced scheduling region."""

    _value: PyTree
    _forced: bool = False

    def map(self, f: Callable[[PyTree], PyTree]) -> "Future":
        """The Lazy/Future monad's ``map`` — forwards the asynchrony."""
        return Future(f(self._value), self._forced)

    def flat_map(self, f: Callable[[PyTree], "Future"]) -> "Future":
        return f(self._value)

    def force(self, anchor: PyTree | None = None) -> PyTree:
        """Await.result.

        If ``anchor`` is given, insert an optimization barrier tying the
        future's completion *after* the anchor's computation, making the
        overlap region explicit to XLA: compute(anchor) runs while the
        future's producer (e.g. an async collective) is in flight.
        """
        if anchor is None or self._forced:
            return self._value
        leaves, treedef = jax.tree.flatten(self._value)
        anchor_leaf = jax.tree.leaves(anchor)[0]
        # Barrier couples (value, anchor) so neither crosses the other.
        barriered = lax.optimization_barrier(tuple(leaves) + (anchor_leaf,))
        self._forced = True
        return jax.tree.unflatten(treedef, list(barriered[: len(leaves)]))


def defer(f: Callable[..., PyTree], *args, **kwargs) -> Future:
    """Issue ``f(*args)`` now; force its result later (paper's ``future``)."""
    return Future(f(*args, **kwargs))


def ppermute_future(x: PyTree, axis_name: str, perm) -> Future:
    """Start a collective-permute; force at the use site to overlap."""
    return defer(
        lambda t: jax.tree.map(lambda v: lax.ppermute(v, axis_name, perm), t), x
    )


def all_gather_future(x: PyTree, axis_name: str, *, tiled: bool = True) -> Future:
    """Start an all-gather; force at the use site to overlap."""
    return defer(
        lambda t: jax.tree.map(
            lambda v: lax.all_gather(v, axis_name, tiled=tiled), t
        ),
        x,
    )


def psum_scatter_future(x: PyTree, axis_name: str) -> Future:
    """Start a reduce-scatter; force at the use site to overlap."""
    return defer(
        lambda t: jax.tree.map(
            lambda v: lax.psum_scatter(v, axis_name, tiled=True), t
        ),
        x,
    )


class HostFuture:
    """Host-side future (data prefetch, async checkpoint writes)."""

    _pool = concurrent.futures.ThreadPoolExecutor(max_workers=4)

    def __init__(self, fn: Callable[[], Any]):
        self._fut = self._pool.submit(fn)

    def map(self, f: Callable[[Any], Any]) -> "HostFuture":
        fut = self._fut
        return HostFuture(lambda: f(fut.result()))

    def done(self) -> bool:
        return self._fut.done()

    def force(self, timeout: float | None = None) -> Any:
        return self._fut.result(timeout=timeout)
