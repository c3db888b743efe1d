"""Admission picker for the serving engine: which pending requests take
the free rows now, and how much prefill work that may cost.

The engine has no shape buckets to fill and no reason to wait for
co-batching, so the trade this module owns is prefill work against the
in-flight rows' next token (``TokenBudgetBatcher``). The picker is the
queue's single consumer and blocks on the queue's condition, waking on
arrivals or timeout.
"""

from __future__ import annotations

from collections.abc import Sequence

from machine_learning_apache_spark_tpu.serving.queue import (
    RequestQueue,
    ServeRequest,
)


class TokenBudgetBatcher:
    """Continuous batching's half of the chunked-prefill compromise.

    A free row should start decoding the oldest pending request *now*.
    What it must ration is **prefill work per decode iteration** — each
    admission runs an encode of the request's chunk-padded prompt, and
    admitting an unbounded burst between two launches would stall every
    in-flight row's next token behind a wall of prefill (the head-of-line
    blocking chunked prefill exists to prevent). So one ``take`` returns
    the longest FIFO prefix of pending requests whose summed chunk-padded
    prompt cost fits ``token_budget`` (the head request is always
    granted — a budget smaller than one prompt must not wedge the queue),
    capped at ``max_requests`` (the engine's free rows).

    Strictly FIFO: a large prompt at the head is never skipped in favour
    of cheaper ones behind it — the same no-starvation contract the page
    and slot pools enforce with ticket queues.
    """

    def __init__(self, queue: RequestQueue, *, chunk: int):
        if chunk < 1:
            raise ValueError(f"chunk must be >= 1, got {chunk}")
        self.queue = queue
        self.chunk = chunk

    def cost(self, ids: Sequence[int]) -> int:
        """Prefill cost of one request: its prompt length rounded up to
        the chunk grid — what the compiled prefill program computes."""
        n = max(len(ids), 1)
        return ((n + self.chunk - 1) // self.chunk) * self.chunk

    def take(
        self,
        *,
        max_requests: int,
        token_budget: int,
        timeout: float = 0.0,
        cost_fn=None,
    ) -> list[ServeRequest]:
        """FIFO-prefix take under the budget; blocks up to ``timeout``
        while the queue is empty (expired requests are swept on every
        wake). Returns [] on timeout or when
        ``max_requests`` is 0.

        ``cost_fn(request) -> int`` overrides the chunk-grid default —
        the engine uses it to price prefix-cache hits at zero, since a
        hit attaches pages without running any prefill program and so
        cannot stall in-flight rows (the thing the budget exists to
        prevent)."""
        if max_requests <= 0:
            return []
        clock = self.queue.clock
        give_up = clock() + timeout
        with self.queue.cond:
            while True:
                now = clock()
                self.queue._expire_locked(now)
                pending = self.queue.pending_locked()
                if pending:
                    chosen: list[ServeRequest] = []
                    spent = 0
                    for r in pending:
                        if len(chosen) >= max_requests:
                            break
                        c = (
                            cost_fn(r) if cost_fn is not None
                            else self.cost(r.ids)
                        )
                        if chosen and spent + c > token_budget:
                            break
                        chosen.append(r)
                        spent += c
                    self.queue.take_locked(chosen)
                    for r in chosen:
                        r.trace.mark("batched", now, budget_spent=spent)
                    return chosen
                remaining = give_up - now
                if remaining <= 0:
                    return []
                self.queue.cond.wait(remaining)
