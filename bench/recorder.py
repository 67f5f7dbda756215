"""Raw timestamps of a run, recorded through the serving loop's metrics
hook (``ServeLoop(metrics=...)``): every request's due time, admission,
each token's emit time and its end, and every decode tick.  The numbers
are worked out from these by the benchmark's own arithmetic
(``stats.py``), not by the program's histograms."""
from __future__ import annotations

import dataclasses
import queue
import threading
import time


@dataclasses.dataclass
class Req:
    due: float
    admitted: float | None = None
    tokens: list = dataclasses.field(default_factory=list)  # emit times
    out: list = dataclasses.field(default_factory=list)  # the served ids
    state: str | None = None
    ended: float | None = None


class Recorder:
    """Implements the hooks ``ServeLoop`` calls on its metrics object.
    ``ended`` receives the rid of every request that reaches a terminal
    state (the closed-loop driver waits on it)."""

    def __init__(self, clock=time.monotonic):
        self.clock = clock
        self._mu = threading.Lock()
        self.reqs: dict[int, Req] = {}
        self.ticks: list[tuple[float, int]] = []  # (end time, live slots)
        self.rejected: dict[str, int] = {}
        self.ended: queue.SimpleQueue = queue.SimpleQueue()

    def reset(self) -> None:
        """Forget everything recorded so far (the warm-up's requests)."""
        with self._mu:
            self.reqs.clear()
            self.ticks.clear()
            self.rejected.clear()
        while not self.ended.empty():
            self.ended.get_nowait()

    # -- ServeLoop hooks ----------------------------------------------------
    def record_arrival(self, rid: int, t: float) -> None:
        with self._mu:
            self.reqs[rid] = Req(due=t)

    def record_admitted(self, rid: int, t: float, *, overlapped: bool) -> None:
        with self._mu:
            r = self.reqs.get(rid)
            if r is not None and r.admitted is None:
                r.admitted = t

    def record_token(self, rid: int, t: float) -> None:
        with self._mu:
            r = self.reqs.get(rid)
            if r is not None:
                r.tokens.append(t)

    def record_done(self, rid: int, state: str) -> None:
        with self._mu:
            r = self.reqs.get(rid)
            if r is None:
                return
            r.state, r.ended = state, self.clock()
        self.ended.put(rid)

    def record_rejected(self, reason: str) -> None:
        with self._mu:
            self.rejected[reason] = self.rejected.get(reason, 0) + 1

    def record_tick(self, n_slots: int) -> None:
        t = self.clock()
        with self._mu:
            self.ticks.append((t, n_slots))

    def record_bucket_compile(self) -> None:
        pass

    def snapshot(self, engine=None, fault_plan=None) -> dict:
        return {}

    # -- reading --------------------------------------------------------------
    def copy(self) -> tuple[dict[int, Req], list[tuple[float, int]]]:
        with self._mu:
            reqs = {rid: dataclasses.replace(r, tokens=list(r.tokens))
                    for rid, r in self.reqs.items()}
            return reqs, list(self.ticks)
