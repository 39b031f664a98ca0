"""A closed loop over a ring of inputs that live on the device.

``call`` is issued on ``ring[i % len(ring)]`` while at most
``in_flight`` results are outstanding; each result counts when
``block_until_ready`` returns for it.  Issuing stops once ``seconds``
have passed, the outstanding results are drained, and the window runs
from the first issue to the last completion, so a rate is all the work
over all the time.  A seeded reservoir keeps ``keep`` of the results
(with their ring index) for the output check.
"""
from __future__ import annotations

import time
from collections import deque


class Reservoir:
    """A uniform sample of ``k`` items from a stream (algorithm R),
    drawn with ``rng``."""

    def __init__(self, k: int, rng):
        self.k, self.rng, self.seen, self.items = k, rng, 0, []

    def offer(self, item) -> None:
        if len(self.items) < self.k:
            self.items.append(item)
        else:
            j = int(self.rng.integers(0, self.seen + 1))
            if j < self.k:
                self.items[j] = item
        self.seen += 1


def run(ctx, call, ring, *, in_flight: int, keep: int, rng, span: str):
    """Drive ``call`` for ``ctx.seconds``; returns ``(done, seconds,
    dispatch_s, kept)``: completed calls, window length, host seconds
    spent in each call before it returned, and the kept
    ``(ring index, result)`` pairs."""
    import jax
    sample = Reservoir(keep, rng)
    pending = deque()
    dispatch = []
    done = 0

    def retire():
        nonlocal done
        idx, out = pending.popleft()
        with ctx.annotate("bench.wait"):
            jax.block_until_ready(out)
        done += 1
        sample.offer((idx % len(ring), out))

    ctx.begin_window()
    t0 = time.perf_counter()
    i = 0
    while True:
        if len(pending) >= in_flight:
            retire()
        if time.perf_counter() - t0 >= ctx.seconds:
            break
        with ctx.annotate(span):
            t = time.perf_counter()
            out = call(ring[i % len(ring)])
            dispatch.append(time.perf_counter() - t)
        pending.append((i, out))
        i += 1
    while pending:
        retire()
    seconds = time.perf_counter() - t0
    ctx.end_window()
    return done, seconds, dispatch, sample.items
