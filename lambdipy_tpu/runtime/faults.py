"""Deterministic fault injection for the serve path.

The continuous engine's recovery machinery (watchdog, replay-on-restart,
degradation ladder — runtime/continuous.py) only earns trust if every
path through it runs in CI, not just when a TPU transport happens to
wedge. This module gives the tests and the soak (``chaos/soak.py``) a
deterministic way to make named SITES misbehave:

========================  ====================================================
site                      where it fires
========================  ====================================================
``segment_dispatch``      the engine thread dispatching a decode segment
``segment_fetch``         the per-segment ``device_get`` in the collector
``first_fetch``           the engine's read of the first tokens of the rows it
                          has just packed, after the dispatch that follows
``group_prefill``         the engine's ragged b-row joiner prefill
``prefix_assemble``       continue-prefill from a cached prefix KV
``prefix_walk``           the prefix store's cold-walk, once per chunk
                          dispatch (an exception fails the walk open —
                          the request serves unrouted; a delay models
                          per-chunk prefill device time)
``transport``             the ``block_until_ready`` device wait before fetch
``page_alloc``            the paged-KV pool taking pages for an admission
``route_connect``         the fleet router opening a replica connection
``route_body``            the router reading a replica response body
``route_latency``         the router's forward path (network latency site)
``probe``                 the replica pool's per-replica health probe
``kv_ship``               the router's prefill→decode KV-block ship (fires
                          once per ship attempt, before the export leg)
``kv_ship_chunk``         the router's pipelined ship relay, once per
                          relayed KV chunk frame (an exception is a
                          MID-STREAM transfer failure — the receiving
                          import aborts its staged pages and the request
                          degrades to mixed-mode; a delay is per-chunk
                          wire time)
``session_pin``           the prefix store pinning a session's radix head
                          (fires once per turn, before any pin mutation;
                          an exception fails the pin OPEN — the turn
                          serves unpinned, counted)
``session_failover``      the router re-homing a session off a dead/
                          drained replica (fires before the re-ship legs;
                          an exception skips the re-ship — the new home
                          re-prefills locally, counted)
========================  ====================================================

The ``route_*``/``probe`` sites live in the FLEET layer (fleet/router.py
and fleet/pool.py): they make the *network* lie — dropped connections
(``route_connect:exception``), connections dying mid-body
(``route_body:exception``), latency spikes
(``route_latency:delay@ms=300``), and flapping replicas
(``probe:exception@seg=3,n=6``) — so ``tests/test_fleet_resilience.py``
and the soak's nemesis run drops, latency and flaps against a fleet with
the same deterministic call counting the engine sites get.

Each site can raise (``exception``), stall (``delay``, ``ms=``) or block
indefinitely (``hang`` — until the plan is released, the watchdog aborts
the wait, or a hard cap expires so test runs never leak threads).

Specs are strings so they travel through env/bundle extras::

    LAMBDIPY_FAULT="segment_fetch:hang@seg=3"      # hang from the 3rd fetch on
    LAMBDIPY_FAULT="group_prefill:exception"        # raise on the 1st call
    LAMBDIPY_FAULT="transport:delay@ms=200,n=2"     # 200 ms stall, twice
    LAMBDIPY_FAULT="segment_fetch:exception;transport:delay"  # multiple rules

Grammar: ``site:kind[@key=val,key=val]`` joined by ``;``. ``seg=N`` is
the 1-based per-site call index where the rule starts firing (default 1),
``n=K`` how many calls it fires for (default 1 for exception/delay,
unlimited for hang; ``n=inf`` forces unlimited), ``ms=X`` the delay
duration. Call counting is per site and strictly deterministic — the
whole point is that a chaos case replays identically run after run.

Sites live in a structured ``REGISTRY`` (:class:`FaultSite`: owning
layer, arming env var, semantics note) that feeds the chaos soak's
nemesis menu (:func:`list_sites`) and the docs table; a grep-based test
asserts every ``check()`` call site in the tree is registered. Plans
also support RUNTIME arming (:meth:`FaultPlan.arm` /
:meth:`FaultPlan.clear` / :meth:`FaultPlan.armed` — the replica's
``POST /v1/debug/faults`` control surface and the ``faults.armed``
metrics block), so composed faults can start and stop on a nemesis
timeline without restarting the process.
"""

from __future__ import annotations

import math
import os
import threading
import time
from dataclasses import dataclass, field

@dataclass(frozen=True)
class FaultSite:
    """One registered injection point. ``owner`` names the layer whose
    plan drives it (engine | store | pool | router), ``env`` the spec
    env var that arms it in a live process (engine/store sites ride the
    replica's ``LAMBDIPY_FAULT``; pool/router sites the fleet process's
    ``LAMBDIPY_FLEET_FAULT``), ``note`` a one-line semantics summary.
    The chaos soak's nemesis menu and the docs table are both derived
    from this registry — and a grep-based test asserts every
    ``faults.check(...)``/``_device_wait(...)`` call site in the tree is
    registered here, so a new site cannot silently dodge the soak."""

    name: str
    owner: str
    env: str
    note: str


_ENGINE_ENV = "LAMBDIPY_FAULT"
_FLEET_ENV = "LAMBDIPY_FLEET_FAULT"

REGISTRY: dict[str, FaultSite] = {s.name: s for s in (
    FaultSite("segment_dispatch", "engine", _ENGINE_ENV,
              "the engine thread dispatching a decode segment"),
    FaultSite("segment_fetch", "engine", _ENGINE_ENV,
              "the per-segment device_get in the collector"),
    FaultSite("first_fetch", "engine", _ENGINE_ENV,
              "the engine's read of the first tokens of the rows it has "
              "just packed (the packed carry's tok leaf), after the "
              "dispatch that follows the pack"),
    FaultSite("group_prefill", "engine", _ENGINE_ENV,
              "the engine's ragged b-row joiner prefill"),
    FaultSite("prefix_assemble", "engine", _ENGINE_ENV,
              "continue-prefill from a cached prefix KV"),
    FaultSite("prefix_walk", "store", _ENGINE_ENV,
              "the prefix store's cold walk, once per chunk dispatch "
              "(exception fails the walk open; delay models per-chunk "
              "prefill device time)"),
    FaultSite("transport", "engine", _ENGINE_ENV,
              "the block_until_ready device wait before fetch"),
    FaultSite("page_alloc", "store", _ENGINE_ENV,
              "the paged-KV pool taking pages for an admission"),
    FaultSite("session_pin", "store", _ENGINE_ENV,
              "the prefix store pinning a session's radix head (fails "
              "OPEN: the turn serves unpinned, counted)"),
    FaultSite("offload_stall", "store", _ENGINE_ENV,
              "the host offload arena's batched page re-online (delay "
              "= a slow fetch, timed as a re-online stall; exception "
              "= a FAILED re-online — the caller recomputes the page "
              "via prefill, counted, never a wrong token)"),
    # fleet-layer (router/pool) network sites
    FaultSite("route_connect", "router", _FLEET_ENV,
              "the fleet router opening a replica connection"),
    FaultSite("route_body", "router", _FLEET_ENV,
              "the router reading a replica response body"),
    FaultSite("route_latency", "router", _FLEET_ENV,
              "the router's forward path (network latency site)"),
    FaultSite("probe", "pool", _FLEET_ENV,
              "the replica pool's per-replica health probe"),
    FaultSite("kv_ship", "router", _FLEET_ENV,
              "the router's prefill->decode KV ship, once per attempt"),
    FaultSite("kv_ship_chunk", "router", _FLEET_ENV,
              "the pipelined ship relay, once per relayed KV chunk "
              "frame (exception = mid-stream transfer failure; delay = "
              "per-chunk synthetic wire time)"),
    FaultSite("session_failover", "router", _FLEET_ENV,
              "the router re-homing a session off a dead/drained "
              "replica (exception skips the re-ship, counted)"),
)}

# tuple view kept for spec validation and pre-registry callers
SITES = tuple(REGISTRY)
KINDS = ("exception", "delay", "hang")


def list_sites(*, owner: str | None = None,
               env: str | None = None) -> list[FaultSite]:
    """Registry query feeding the nemesis menu and the docs table:
    all sites, optionally filtered by owning layer or arming env var."""
    return [s for s in REGISTRY.values()
            if (owner is None or s.owner == owner)
            and (env is None or s.env == env)]
_KIND_ALIASES = {"error": "exception", "raise": "exception",
                 "sleep": "delay", "stall": "delay", "block": "hang"}

# injected hangs still resolve after this many seconds even if nothing
# releases or aborts them — a safety net so a test that forgets teardown
# cannot leak a thread for the life of the process
HANG_CAP_S = 300.0


class InjectedFault(RuntimeError):
    """An exception (or aborted hang) raised by the fault layer.

    ``fault_site`` lets the engine's failure handler attribute the
    failure without string-parsing the message."""

    def __init__(self, site: str, kind: str, occurrence: int):
        self.fault_site = site
        self.fault_kind = kind
        self.occurrence = occurrence
        super().__init__(
            f"injected {kind} at {site} (call #{occurrence})")


class EngineWatchdogTimeout(TimeoutError):
    """A device-side wait exceeded the engine watchdog. Raised to the
    waiters of an engine the watchdog declared wedged, and by guarded
    request-thread waits whose injected hang the watchdog aborted."""

    def __init__(self, site: str, timeout_s: float):
        self.fault_site = f"watchdog:{site}"
        super().__init__(
            f"engine watchdog: {site} wait exceeded {timeout_s:.3g}s")


@dataclass
class FaultRule:
    site: str
    kind: str
    seg: int = 1            # 1-based call index where firing starts
    n: float = 1            # firings (math.inf = permanent)
    ms: float = 50.0        # delay duration
    fired: int = 0

    def matches(self, count: int) -> bool:
        return self.seg <= count and self.fired < self.n

    def describe(self) -> str:
        span = "inf" if math.isinf(self.n) else str(int(self.n))
        return (f"{self.site}:{self.kind}@seg={self.seg},n={span}"
                + (f",ms={self.ms:g}" if self.kind == "delay" else ""))


def parse_spec(spec: str | None) -> list[FaultRule]:
    """Parse a fault spec string into rules (shared by
    :meth:`FaultPlan.from_spec` and the runtime :meth:`FaultPlan.arm`)."""
    rules: list[FaultRule] = []
    for part in (spec or "").split(";"):
        part = part.strip()
        if not part:
            continue
        head, _, params = part.partition("@")
        site, sep, kind = head.partition(":")
        site, kind = site.strip(), kind.strip().lower()
        kind = _KIND_ALIASES.get(kind, kind)
        if not sep or site not in SITES or kind not in KINDS:
            raise ValueError(
                f"bad fault spec {part!r}: want site:kind with site in "
                f"{SITES} and kind in {KINDS}")
        rule = FaultRule(site=site, kind=kind,
                         n=(math.inf if kind == "hang" else 1))
        for kv in filter(None, (p.strip() for p in params.split(","))):
            key, eq, val = kv.partition("=")
            key = key.strip().lower()
            try:
                if key in ("seg", "at"):
                    rule.seg = max(1, int(val))
                elif key == "n":
                    rule.n = math.inf if val.strip() in ("inf", "-1") \
                        else max(1, int(val))
                elif key == "ms":
                    rule.ms = max(0.0, float(val))
                else:
                    raise ValueError(key)
            except ValueError:
                raise ValueError(
                    f"bad fault param {kv!r} in {part!r} "
                    f"(known: seg=N, n=K|inf, ms=X)") from None
        rules.append(rule)
    return rules


class FaultPlan:
    """A deterministic set of :class:`FaultRule`\\ s plus the per-site
    call counters they key on. An empty plan is a no-op and costs one
    ``if`` per site check — safe to leave wired in production.

    Rules may also be armed and cleared AT RUNTIME (:meth:`arm` /
    :meth:`clear`) — the chaos soak's nemesis drives a live replica's
    plan over ``POST /v1/debug/faults`` this way, and a cleared plan
    releases its in-flight hangs without poisoning later ones."""

    def __init__(self, rules: list[FaultRule] | None = None):
        self.rules = list(rules or ())
        self._counts: dict[str, int] = {}
        self._lock = threading.Lock()
        self._release = threading.Event()

    # -- construction --------------------------------------------------------

    @classmethod
    def empty(cls) -> "FaultPlan":
        return cls([])

    @classmethod
    def from_spec(cls, spec: str | None) -> "FaultPlan":
        """Parse ``site:kind@k=v,...;site2:...``; unknown sites/kinds and
        malformed params raise ``ValueError`` — a typo in a chaos spec
        must fail the run loudly, not silently test nothing."""
        return cls(parse_spec(spec))

    @classmethod
    def from_env(cls, environ=None, *, var: str = "LAMBDIPY_FAULT"
                 ) -> "FaultPlan":
        """``var`` selects the env knob: the engine reads
        ``LAMBDIPY_FAULT``; the fleet layer reads
        ``LAMBDIPY_FLEET_FAULT`` so arming a replica's engine sites
        never silently arms the router in the same shell."""
        return cls.from_spec((environ or os.environ).get(var))

    # -- the injection point -------------------------------------------------

    def check(self, site: str, interrupt: threading.Event | None = None
              ) -> None:
        """Called once per site invocation. No-op without a matching
        rule; otherwise sleeps (delay), raises (exception), or blocks
        (hang) until :meth:`release`, the ``interrupt`` event (the
        watchdog's abort), or the hard cap — then raises, because a wait
        the system gave up on must not look like a success."""
        if not self.rules:
            return
        with self._lock:
            count = self._counts.get(site, 0) + 1
            self._counts[site] = count
            rule = next((r for r in self.rules
                         if r.site == site and r.matches(count)), None)
            if rule is not None:
                rule.fired += 1
        if rule is None:
            return
        if rule.kind == "delay":
            time.sleep(rule.ms / 1e3)
            return
        if rule.kind == "hang":
            # capture the CURRENT release event: clear() sets it and then
            # installs a fresh one, so this hang resolves while a
            # later-armed hang still blocks (runtime re-arming must not
            # inherit a permanently-released plan)
            release = self._release
            deadline = time.monotonic() + HANG_CAP_S
            while time.monotonic() < deadline:
                if release.wait(0.02):
                    break
                if interrupt is not None and interrupt.is_set():
                    break
        raise InjectedFault(site, rule.kind, count)

    # -- runtime arming (nemesis control surface) ----------------------------

    def arm(self, spec: str) -> list[str]:
        """Parse ``spec`` and ADD its rules to the live plan (call
        counters keep running — a rule armed mid-soak fires on the next
        matching call). Returns the added rules' descriptions; raises
        ``ValueError`` on a bad spec, touching nothing."""
        rules = parse_spec(spec)
        with self._lock:
            self.rules.extend(rules)
        return [r.describe() for r in rules]

    def clear(self) -> int:
        """Drop every rule and release in-flight hangs, leaving the plan
        re-armable: waiters blocked on the old release event resolve
        (raising ``InjectedFault``, as an abandoned wait must), while
        hangs armed LATER block on the fresh event. Call counters are
        kept — they are the deterministic spine replay depends on.
        Returns the number of rules cleared."""
        with self._lock:
            n = len(self.rules)
            self.rules = []
            released, self._release = self._release, threading.Event()
        released.set()
        return n

    def armed(self) -> dict:
        """Live-plan snapshot for ``/metrics`` (``faults.armed``): the
        armed sites/kinds with remaining fire counts, plus the per-site
        call counters — so a soak run (or a stray ``LAMBDIPY_FAULT``
        left set in prod) is visible at the front door."""
        with self._lock:
            rules = [{
                "site": r.site,
                "kind": r.kind,
                "seg": r.seg,
                "n": ("inf" if math.isinf(r.n) else int(r.n)),
                **({"ms": r.ms} if r.kind == "delay" else {}),
                "fired": r.fired,
                "remaining": ("inf" if math.isinf(r.n)
                              else max(0, int(r.n) - r.fired)),
            } for r in self.rules]
            counts = dict(self._counts)
        return {"active": bool(rules),
                "sites": sorted({r["site"] for r in rules}),
                "rules": rules,
                "counts": counts}

    # -- lifecycle / introspection -------------------------------------------

    def release(self) -> None:
        """Unblock every in-flight (and future) hang — test teardown."""
        self._release.set()

    def active(self) -> bool:
        return bool(self.rules)

    def counts(self) -> dict:
        with self._lock:
            return dict(self._counts)

    def describe(self) -> list[str]:
        return [r.describe() for r in self.rules]
