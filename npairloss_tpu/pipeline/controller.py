"""Bounded dispatch depth — a semaphore on in-flight jitted steps.

JAX dispatch is asynchronous: without a bound, a sync-free loop can
enqueue thousands of steps ahead of a device that is falling behind,
each one pinning its staged inputs and delaying every host-visible
signal (metrics, the divergence guard) by the queue's length.  The
controller admits at most ``max_in_flight`` dispatched
steps: before dispatching a new one, the loop calls :meth:`reserve`,
which blocks on the OLDEST pending step's completion token until the
bound is respected.  Blocking on a token (``block_until_ready`` on a
tiny per-step output array) synchronizes the host with device progress
WITHOUT transferring anything — it is not a host sync in the
transfer-guard sense.
"""

from __future__ import annotations

import collections


class DispatchController:
    """``reserve()`` before dispatch, ``admit(token)`` after.

    ``token`` is any object with ``block_until_ready()`` — in the Solver
    it is the pipelined step's tiny ``tick`` output (NOT donated into
    the next dispatch, so it stays readable).  ``blocked`` counts how
    often ``reserve`` actually had to wait — a saturated pipeline shows
    ``blocked ~= steps``, an underfed one ~0.
    """

    def __init__(self, max_in_flight: int = 2):
        if max_in_flight < 1:
            raise ValueError(
                f"max_in_flight must be >= 1, got {max_in_flight}"
            )
        self.max_in_flight = max_in_flight
        self._pending: collections.deque = collections.deque()
        self.blocked = 0

    @property
    def in_flight(self) -> int:
        return len(self._pending)

    def reserve(self) -> None:
        """Block until another dispatch is within the bound."""
        while len(self._pending) >= self.max_in_flight:
            oldest = self._pending.popleft()
            oldest.block_until_ready()
            self.blocked += 1

    def admit(self, token) -> None:
        self._pending.append(token)

    def drain(self) -> None:
        """Block until every admitted step has completed."""
        while self._pending:
            self._pending.popleft().block_until_ready()
