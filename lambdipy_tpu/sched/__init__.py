"""SLO-aware admission control and request scheduling for the serve path.

The seed's ``BundleServer`` admitted every request behind a single
draining gate: under overload, latency grew without bound and nothing was
ever rejected explicitly. This package converts the batchers into a
*service* (the admission + scheduling layer of the vLLM/Orca lineage):

- :mod:`lambdipy_tpu.sched.queue` — a bounded queue with per-class FIFO
  lanes (interactive / batch / background);
- :mod:`lambdipy_tpu.sched.policy` — pluggable dequeue policies (fifo,
  priority, fair-share weighted round-robin);
- :mod:`lambdipy_tpu.sched.admission` — per-tenant token buckets,
  queue-depth caps and deadline-based shedding (429/503 + Retry-After);
- :mod:`lambdipy_tpu.sched.estimator` — an EWMA cost model of per-request
  service time (prefill + decode tokens) used for deadline feasibility.

:class:`Scheduler` below ties them together and is what
``runtime/server.py`` fronts every invoke with; the request-context
helpers let the batchers (``runtime/batching.py`` /
``runtime/continuous.py``) see the scheduling class of the request they
are serving without threading it through every handler signature.
"""

from __future__ import annotations

import os
import threading
import time
from dataclasses import dataclass, field

from lambdipy_tpu.runtime.metrics import LatencyStats
from lambdipy_tpu.sched.admission import AdmissionController, Shed
from lambdipy_tpu.sched.estimator import CostEstimator
from lambdipy_tpu.sched.policy import make_policy
from lambdipy_tpu.sched.queue import CLASSES, RequestQueue, Ticket

__all__ = ["Scheduler", "Shed", "Ticket", "CLASSES",
           "set_request_context", "clear_request_context",
           "current_request_class", "current_request_deadline_ms",
           "current_request_rid"]


# -- request context ---------------------------------------------------------
# The HTTP thread that admitted a request is the thread that runs the
# handler (and therefore enters the batchers). A thread-local carries the
# request's scheduling class down that call stack so batch formation can
# dequeue by policy without new parameters on every handler.

_ctx = threading.local()


def set_request_context(cls: str = "interactive", tenant: str = "anon",
                        deadline_ms: float | None = None,
                        rid: int | None = None) -> None:
    _ctx.cls, _ctx.tenant, _ctx.deadline_ms = cls, tenant, deadline_ms
    _ctx.rid = rid


def clear_request_context() -> None:
    _ctx.cls = _ctx.tenant = _ctx.deadline_ms = _ctx.rid = None


def current_request_class() -> str:
    return getattr(_ctx, "cls", None) or "interactive"


def current_request_deadline_ms() -> float | None:
    """The admitted request's ``x-deadline-ms``, if it carried one — the
    continuous engine uses it to cancel rows whose deadline expired
    mid-decode at the next drain barrier instead of decoding them to
    completion."""
    return getattr(_ctx, "deadline_ms", None)


def current_request_rid() -> int | None:
    """The request's span id (``runtime/spans.py``), taken when the server
    had read it; the engine stamps the request's tiles under it."""
    return getattr(_ctx, "rid", None)


# -- scheduler ---------------------------------------------------------------


@dataclass
class SchedConfig:
    """Operator surface, settable per bundle (``[payload.extra]``) or per
    serve process (CLI flags); every field has a serving-safe default."""

    policy: str = "fair"
    max_concurrency: int = 8       # invokes running at once
    queue_cap: int = 64            # queued (not yet running) requests
    rate: float = 0.0              # per-tenant tokens/s; 0 = unlimited
    burst: float = 0.0             # bucket size; 0 = 2 * rate
    default_cost_ms: float = 50.0  # estimator prior before any sample

    @classmethod
    def from_extra(cls, extra: dict | None, **overrides) -> "SchedConfig":
        """Bundle ``[payload.extra]`` keys (strings), then the
        LAMBDIPY_SCHED_POLICY env var (process-level operator intent,
        also read by the handler's batch formation), then explicit
        overrides (CLI/ctor, already typed). Unknown extra keys are
        ignored — extra is a shared namespace."""
        extra = extra or {}
        kw: dict = {}
        for name, cast in (("policy", str), ("max_concurrency", int),
                           ("queue_cap", int), ("rate", float),
                           ("burst", float), ("default_cost_ms", float)):
            raw = extra.get(f"sched_{name}")
            if raw is not None:
                kw[name] = cast(raw)
        env_policy = os.environ.get("LAMBDIPY_SCHED_POLICY")
        if env_policy:
            kw["policy"] = env_policy
        kw.update({k: v for k, v in overrides.items() if v is not None})
        return cls(**kw)


class Scheduler:
    """Admission + queue + slot handoff in front of the invoke path.

    A request thread calls :meth:`admit` (immediate accept-or-shed) and
    then :meth:`wait_turn` (parks in its class lane until the policy
    grants it one of ``max_concurrency`` run slots); :meth:`finish`
    releases the slot, wakes the next grant, and feeds the estimator.
    :meth:`grant_ahead` grants one queued ticket against a run slot that
    is ABOUT to be released (the continuous engine calls it when a row's
    last segment is next): the request is at the engine when the batch
    slot frees instead of a response and a wake-up later.
    """

    def __init__(self, config: SchedConfig | None = None):
        self.config = config or SchedConfig()
        # normalize degenerate configs ONCE here so every consumer (the
        # admission depth check, wait math, the queue's own bound) sees
        # the same floors: queue_cap=0 would otherwise shed every
        # request 503 on an idle server
        self.config.max_concurrency = max(1, self.config.max_concurrency)
        self.config.queue_cap = max(1, self.config.queue_cap)
        self.policy = make_policy(self.config.policy)
        self.estimator = CostEstimator(
            default_ms=self.config.default_cost_ms)
        self.queue = RequestQueue(capacity=self.config.queue_cap)
        self.admission = AdmissionController(
            rate=self.config.rate, burst=self.config.burst)
        self._cond = threading.Condition()
        self._running = 0
        # grants made ahead of a release (grant_ahead) that no finish()
        # has repaid yet: the gate stands that far over max_concurrency
        self._ahead = 0
        self.granted_ahead = 0
        self.draining = False
        # observability: per-class queue-wait reservoirs + counters
        self.wait_stats = {c: LatencyStats(capacity=512) for c in CLASSES}
        self.admitted = 0
        self.completed = 0

    # -- admission -----------------------------------------------------------

    def admit(self, *, tenant: str = "anon", cls: str = "interactive",
              deadline_ms: float | None = None, prefill_tokens: int = 0,
              decode_tokens: int = 0, rid: int | None = None) -> Ticket | Shed:
        if cls not in CLASSES:
            cls = "interactive"
        cost_ms = self.estimator.estimate(prefill_tokens, decode_tokens)
        with self._cond:
            ahead = self.queue.depth() + self._running
            # queue wait ≈ work ahead of us spread over the run slots
            wait_ms = (ahead * self.estimator.mean_ms()
                       / max(1, self.config.max_concurrency))
            shed = self.admission.check(
                tenant=tenant, cls=cls, deadline_ms=deadline_ms,
                queue_depth=self.queue.depth(),
                queue_cap=self.config.queue_cap,
                est_wait_ms=wait_ms, est_cost_ms=cost_ms,
                draining=self.draining)
            if shed is not None:
                return shed
            ticket = Ticket(cls=cls, tenant=tenant,
                            deadline_ms=deadline_ms, cost_ms=cost_ms,
                            prefill_tokens=prefill_tokens,
                            decode_tokens=decode_tokens, rid=rid)
            self.queue.push(ticket)
            self.admitted += 1
            self._pump_locked()
            return ticket

    # -- slot handoff ---------------------------------------------------------

    def _pump_locked(self, credit: int = 0,
                     max_prefill_tokens: int | None = None) -> None:
        """Grant queued tickets, in the policy's order, while a run slot
        is free. ``credit`` counts run slots about to be released on top
        of the grants ahead still owed, and ``max_prefill_tokens`` bounds
        the prompt of a ticket granted that way (:meth:`grant_ahead`)."""
        while self._running < (self.config.max_concurrency + self._ahead
                               + credit):
            ticket = self.queue.pop(self.policy)
            if ticket is None:
                return
            if max_prefill_tokens is not None \
                    and ticket.prefill_tokens > max_prefill_tokens:
                # the policy's next is not to be granted ahead: it keeps
                # its place and waits for the release itself
                self.queue.unpop(ticket)
                return
            now = time.monotonic()
            wait_ms = (now - ticket.enqueued) * 1e3
            # stamp the ticket so the server can echo queue_wait_ms in
            # the response body — a client can then window queue-wait
            # client-side instead of reading the
            # replica's cumulative reservoir
            ticket.wait_ms = wait_ms
            self.wait_stats[ticket.cls].record(wait_ms)
            # deadline re-check at grant time: overload that built up
            # AFTER this request was admitted can make its deadline
            # unmeetable — shed it now instead of burning a device slot
            # on a response the client already abandoned
            if (ticket.deadline_ms is not None
                    and wait_ms + ticket.cost_ms > ticket.deadline_ms):
                ticket.expired = True
                ticket.granted = True  # wakes the waiter; it sends 503
                self.admission.count_shed("deadline", ticket.cls)
                self._cond.notify_all()
                continue
            ticket.granted = True
            self._running += 1
            self._cond.notify_all()

    def wait_turn(self, ticket: Ticket, timeout: float | None = None) -> bool:
        """Park until the policy grants this ticket a run slot. Returns
        False when the ticket expired (deadline shed at grant time) —
        the caller must NOT run the request and must not call finish."""
        deadline = None if timeout is None else time.monotonic() + timeout
        with self._cond:
            while not ticket.granted:
                remaining = (None if deadline is None
                             else deadline - time.monotonic())
                if remaining is not None and remaining <= 0:
                    self.queue.remove(ticket)
                    ticket.expired = True
                    self.admission.count_shed("deadline", ticket.cls)
                    return False
                self._cond.wait(timeout=remaining)
            return not ticket.expired

    def grant_ahead(self, max_prefill_tokens: int | None = None) -> bool:
        """A run slot is about to be released: grant ONE queued ticket
        now, on credit. The next :meth:`finish` repays the credit (it
        frees the slot the grant was made against), so ``running`` never
        exceeds ``max_concurrency`` + the releases announced and not yet
        made. The policy's order and the deadline re-check at grant time
        hold as for any grant; with a free run slot or an empty queue
        nothing is granted and no credit is kept. ``max_prefill_tokens``
        (the continuous engine passes the longest prompt it prefills
        itself, at the barrier): a ticket whose prompt is longer is NOT
        granted ahead and nobody overtakes it. Such a request prefills on
        its own thread the moment it is granted, and ahead of a release
        that prefill would queue on the device in front of the ending
        row's last segments. Returns whether a ticket was granted."""
        with self._cond:
            self._pump_locked(credit=1,
                              max_prefill_tokens=max_prefill_tokens)
            if self._running <= self.config.max_concurrency + self._ahead:
                return False
            self._ahead += 1
            self.granted_ahead += 1
            return True

    def finish(self, ticket: Ticket, *, service_ms: float | None = None) -> None:
        with self._cond:
            self._running -= 1
            if self._ahead:
                self._ahead -= 1  # a grant ahead is repaid by this release
            self.completed += 1
            if service_ms is not None:
                self.estimator.observe(service_ms, ticket.prefill_tokens,
                                       ticket.decode_tokens)
            self._pump_locked()
            self._cond.notify_all()

    # -- lifecycle / observability -------------------------------------------

    def drain(self) -> None:
        """Stop admitting; queued requests still run to completion."""
        with self._cond:
            self.draining = True

    def idle(self) -> bool:
        with self._cond:
            return self._running == 0 and self.queue.depth() == 0

    def report(self) -> dict:
        with self._cond:
            running = self._running
            depths = self.queue.snapshot()
            admitted, completed = self.admitted, self.completed
            granted_ahead = self.granted_ahead
        waits = {}
        for c in CLASSES:
            rep = self.wait_stats[c].report()
            if rep["count"]:
                waits[c] = {"count": rep["count"],
                            "p50_ms": rep["p50_ms"],
                            "p99_ms": rep["p99_ms"]}
        return {
            "policy": self.policy.name,
            "max_concurrency": self.config.max_concurrency,
            "queue_cap": self.config.queue_cap,
            "running": running,
            "queued": depths,
            "admitted": admitted,
            "completed": completed,
            "granted_ahead": granted_ahead,
            "shed": self.admission.shed_report(),
            "queue_wait": waits,
            "estimator": self.estimator.report(),
        }
