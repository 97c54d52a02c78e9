"""The open-loop scheduler object the benchmark hands to ``ServingEngine.run``.

``run`` asks ``admissions(step, waiting, n_running, cache)`` once per step,
after that step's decode tokens are on the host.  This object:

* admits a request only once its due time (seconds after the window
  opened) has passed, in FIFO order, into free slots, reserving its pages
  up front as the program's own continuous-batching scheduler does;
* sleeps until the next due time when nothing is running, so ``run``'s
  step bound is never reached by spinning;
* stamps every step boundary on the host clock and credits the tokens each
  running request gained in that step to it;
* stamps each request's first token: the prefill's token reaches the host
  inside ``run``'s admission, just before the request's block-table row is
  read, so the cache handed to ``admissions`` has its ``block_table_row``
  wrapped to stamp that moment.  A request whose first token was not
  stamped that way is credited at the next boundary.

The clock and the sleep are injectable so the logic can be driven without
real time.
"""

from __future__ import annotations

import time


class OpenLoopScheduler:
    name = "open-loop"

    def __init__(self, max_batch: int, due_s: dict[int, float],
                 prompt_len: dict[int, int], clock=time.perf_counter,
                 sleep=time.sleep) -> None:
        self.max_batch = max_batch
        self.due_s = due_s
        self.prompt_len = prompt_len
        self.clock = clock
        self.sleep = sleep
        self.start: float | None = None
        self.picked_at: dict[int, float] = {}
        self.first_at: dict[int, float] = {}
        self.token_at: dict[int, list[float]] = {}
        # one row per step that decoded: (host time, rows decoded, summed
        # attended context of those rows)
        self.decode_steps: list[tuple[float, int, int]] = []
        self.idle_sleeps = 0
        self._hooks: list[tuple[float, object]] = []
        self._running: list = []
        self._cache = None

    def open(self, start: float) -> None:
        """The window opens at host time ``start``."""
        self.start = start

    def at(self, when: float, fn) -> None:
        """Call ``fn()`` at the first boundary at or after host time ``when``."""
        self._hooks.append((when, fn))
        self._hooks.sort(key=lambda h: h[0])

    def due_at(self, req_id: int) -> float:
        return self.start + self.due_s[req_id]

    # -- what run() calls -----------------------------------------------------

    def admissions(self, step: int, waiting: list, n_running: int,
                   cache) -> list:
        now = self.clock()
        self._wrap(cache)
        self._credit(now)
        while self._hooks and self._hooks[0][0] <= now:
            self._hooks.pop(0)[1]()
            now = self.clock()
        if n_running == 0 and waiting and self.due_at(waiting[0].req_id) > now:
            self.sleep(self.due_at(waiting[0].req_id) - now)
            self.idle_sleeps += 1
            now = max(self.clock(), self.due_at(waiting[0].req_id))
        picked = []
        budget = cache.allocator.num_free
        for req in waiting:
            if self.due_at(req.req_id) > now:
                break
            if n_running + len(picked) >= self.max_batch:
                break
            need = cache.pages_needed(req.spec.total_len)
            if need > budget:
                break
            budget -= need
            picked.append(req)
        for req in picked:
            self.picked_at[req.req_id] = now
            self.token_at[req.req_id] = []
            self._running.append(req)
        return picked

    def release(self) -> None:
        """Let go of the run's cache (and its pools) once ``run`` returned."""
        self._cache = None
        self._running = []

    # -- bookkeeping ------------------------------------------------------------

    def _wrap(self, cache) -> None:
        if cache is self._cache:
            return
        self._cache = cache
        read_row = cache.block_table_row

        def block_table_row(req_id=None):
            if req_id is not None and req_id not in self.first_at:
                self.first_at[req_id] = self.clock()
            return read_row(req_id)

        cache.block_table_row = block_table_row

    def _credit(self, now: float) -> None:
        rows = ctx = 0
        still = []
        for req in self._running:
            times = self.token_at[req.req_id]
            if not times and req.generated >= 1:
                times.append(self.first_at.get(req.req_id, now))
            new = req.generated - len(times)
            if new > 0:
                times.extend([now] * new)
                rows += 1
                ctx += self.prompt_len[req.req_id] + req.generated - 1
            if req.generated < req.spec.output_len:
                still.append(req)
        self._running = still
        if rows:
            self.decode_steps.append((now, rows, ctx))
