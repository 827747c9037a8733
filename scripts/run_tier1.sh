#!/usr/bin/env bash
# Tier-1 gate, runnable locally and in CI.
#
# Phase 1 fails FAST on collection errors: a module-level import break
# (like the tomllib one that silently knocked out 7 test files on
# Python 3.10) must turn the build red by itself, not hide behind
# --continue-on-collection-errors in the main run.
#
# Phase 2 is the ROADMAP.md tier-1 suite split into TWO module shards
# (2a: the engine/serving stack, 2b: everything else), each with its
# own 870 s timeout — the single-process run was flirting with the
# ceiling (~750-810 s observed, high machine variance; ROADMAP
# carry-over). Same flags, same tests, union = tests/ (2b ignores
# exactly 2a's modules, so a NEW module lands in 2b by default); the
# aggregate DOTS_PASSED still prints. Keeping the continuous-engine
# modules together in 2a preserves their shared session-scoped
# tiny_server compile cache.
#
# Phase 3 is a quick bench.py smoke pinned to the CPU by name
# (LAMBDIPY_PLATFORM=cpu — without the pin bench.py refuses to run off
# the TPU) on the tiny model, so a bench orchestration regression turns
# tier-1 red, not measurement day.
#
# Phase 4 smokes the decode-window sweep; phase 5 the pipelined-engine
# sweep (bitwise parity across pipeline depths + depth-2 tok/s beating
# depth-1 under a synthetic fetch RTT — bench.py --pipeline exits
# nonzero on either regression); phase 6 the FLEET (2 CPU replicas
# behind the affinity router, one SIGKILLed mid-traffic — zero lost
# requests, ejection, supervisor respawn, re-admission, rolling
# restart — the slow tests in tests/test_fleet.py); phase 7 the CHAOS
# matrix (bench.py --chaos: every runtime/faults.py site x {exception,
# delay, hang} injected into a live continuous engine — no waiter
# outlives its bound, zero silent losses, replay parity is bitwise);
# phase 8 the FLEET-BOUNDARY chaos matrix (bench.py --chaos-fleet:
# router-side network faults — dropped connections, mid-body deaths,
# latency spikes, flapping probes — plus a fleet-wide shed burst the
# router's spill queue must absorb with zero client-visible errors);
# phase 9 the PAGED-KV sweep (bench.py --paged: bitwise paged-vs-dense
# parity, zero-copy prefix hits, token-bounded capacity margin).
#
# Phase 10 is the SPECULATIVE-DECODING sweep (bench.py --spec: bitwise
# engine parity spec-on-vs-off — greedy + seeded-sampled, cold +
# prefix-hit, streamed, concurrent rows, pipeline depths 1-2, dense +
# paged — plus the >1.5x tok/s claim on a repetitive-continuation
# workload with acceptance counters published under batching.spec).
#
# Phase 11 is the SHARDED-SERVING sweep (bench.py --mesh over 2 forced
# CPU host devices: bitwise tp=2-vs-tp=1 parity across the same matrix,
# plus the per-device KV/param HBM halving gate from batching.mesh).
#
# Phase 12 is the DISAGGREGATED-SERVING sweep (bench.py --disagg,
# subprocess replicas): bitwise split-fleet-vs-direct parity (greedy +
# seeded-sampled, dense + paged KV, real KV ships observed), decode
# tok/s under a concurrent cold-prefill burst >= 1.2x the mixed fleet
# at equal replica count, and an injected kv_ship failure completing
# the whole burst bitwise with zero client-visible errors. Phase 12b
# adds the synthetic-RTT axis (bench.py --disagg-rtt): pipelined-ship
# TTFT <= 0.6x the blocking ship's at 66 ms per relayed chunk, and
# bitwise zero-error delivery under permanent mid-stream chunk failure.
#
# Phase 13 is the MULTI-TURN SESSION sweep (bench.py --sessions,
# subprocess replicas behind the sticky-session router): bitwise
# transcript parity vs direct serving across {greedy, seeded-sampled}
# x {dense, paged} x {healthy, mid-conversation replica SIGKILL},
# zero client-visible errors through failover (incl. a reachable-home
# failover whose KV re-ships old home -> new home), turn-2+ TTFT
# <= 0.15x cold TTFT on a healthy home, and pinned-page accounting
# returning to exactly zero after every session closes (DELETE fan-out
# plus one lease expiry).
#
# Every phase prints its wall-clock so the budget breakdown is visible
# in the log (ROADMAP open item: phase 2 runs close to its 870 s cap).

set -u
cd "$(dirname "$0")/.."

phase_t0=0
PHASE_NAMES=()
PHASE_SECS=()
phase_begin() { phase_t0=$(date +%s); echo "== $1 =="; }
phase_end() {
    local secs=$(( $(date +%s) - phase_t0 ))
    PHASE_NAMES+=("$1")
    PHASE_SECS+=("$secs")
    echo "== $1 wall: ${secs}s =="
}
# the budget breakdown in one place (ROADMAP open item: phase 2 runs
# close to its 870 s cap) — printed on EVERY exit, so a failed run
# still shows where the wall-clock went up to the failure
phase_table() {
    local total=0 i
    echo "== phase wall-clock summary =="
    for i in "${!PHASE_NAMES[@]}"; do
        printf '  %-14s %6ss\n' "${PHASE_NAMES[$i]}" "${PHASE_SECS[$i]}"
        total=$(( total + PHASE_SECS[i] ))
    done
    printf '  %-14s %6ss\n' "total" "$total"
}
trap phase_table EXIT

phase_begin "phase 1: collection must be clean"
rm -f /tmp/_t1_collect.log
env JAX_PLATFORMS=cpu python -m pytest tests/ -q -m 'not slow' \
    --collect-only --continue-on-collection-errors \
    -p no:cacheprovider 2>&1 | tee /tmp/_t1_collect.log
if grep -qE '^ERROR |[0-9]+ errors? in ' /tmp/_t1_collect.log; then
    echo "FATAL: test collection errors (see above)" >&2
    exit 1
fi
phase_end "phase 1"

# the engine/serving stack: these share conftest.py's session-scoped
# tiny_server (one compiled-program cache). The bring-up and chip-smoke
# rehearsal modules ride here too: they boot servers (~2 min together)
# and 2b is the shard nearer its cap
ENGINE_SHARD="tests/test_continuous.py tests/test_continuous_pipeline.py \
tests/test_faults.py tests/test_prefixstore.py tests/test_paged.py \
tests/test_pagepool.py tests/test_decode_attention.py \
tests/test_runtime.py tests/test_fleet.py tests/test_e2e.py \
tests/test_bringup.py tests/test_chip_smoke.py"

set -o pipefail
phase_begin "phase 2a: tier-1 engine/serving shard"
rm -f /tmp/_t1a.log
timeout -k 10 870 env JAX_PLATFORMS=cpu python -m pytest $ENGINE_SHARD \
    -q -m 'not slow' --continue-on-collection-errors \
    -p no:cacheprovider -p no:xdist -p no:randomly 2>&1 | tee /tmp/_t1a.log
rc=${PIPESTATUS[0]}
phase_end "phase 2a"
if [ "$rc" -ne 0 ]; then exit "$rc"; fi

phase_begin "phase 2b: tier-1 remainder shard"
ignores=""
for m in $ENGINE_SHARD; do ignores="$ignores --ignore=$m"; done
rm -f /tmp/_t1b.log
timeout -k 10 870 env JAX_PLATFORMS=cpu python -m pytest tests/ $ignores \
    -q -m 'not slow' --continue-on-collection-errors \
    -p no:cacheprovider -p no:xdist -p no:randomly 2>&1 | tee /tmp/_t1b.log
rc=${PIPESTATUS[0]}
phase_end "phase 2b"
echo DOTS_PASSED=$(cat /tmp/_t1a.log /tmp/_t1b.log \
    | grep -aE '^[.FEsx]+( *\[ *[0-9]+%\])?$' | tr -cd . | wc -c)
if [ "$rc" -ne 0 ]; then exit "$rc"; fi

phase_begin "phase 3: bench.py CPU smoke"
if ! timeout -k 10 600 env JAX_PLATFORMS=cpu \
    LAMBDIPY_PLATFORM=cpu LAMBDIPY_BENCH_MODEL=resnet50-tiny \
    python bench.py; then
    echo "FATAL: bench.py CPU smoke failed" >&2
    exit 1
fi
phase_end "phase 3"

phase_begin "phase 4: decode-window bench smoke"
if ! timeout -k 10 600 env JAX_PLATFORMS=cpu \
    python bench.py --decode-window; then
    echo "FATAL: bench.py --decode-window smoke failed" >&2
    exit 1
fi
phase_end "phase 4"

# Phase 5: pipelined-engine smoke — the sweep itself asserts bitwise
# parity between pipeline depths and that depth-2 throughput stays
# above depth-1 at the synthetic-RTT points (20/66 ms), so either
# regression turns tier-1 red here.
phase_begin "phase 5: pipeline bench smoke"
if ! timeout -k 10 600 env JAX_PLATFORMS=cpu \
    python bench.py --pipeline; then
    echo "FATAL: bench.py --pipeline smoke failed" >&2
    exit 1
fi
phase_end "phase 5"

# Phase 6: fleet smoke (~3-4 min CPU) — boots 2 supervised CPU replicas
# behind the affinity router, SIGKILLs one worker mid-traffic and
# asserts zero failed requests, ejection within a probe interval,
# re-admission after the supervisor respawn (same URL), then a rolling
# restart over the live floor; plus router-vs-direct bitwise parity,
# the live-server readiness split, and the shared-prefix
# affinity-concentration check (all the `slow` tests in test_fleet.py).
phase_begin "phase 6: fleet smoke (tests/test_fleet.py -m slow)"
if ! timeout -k 10 900 env JAX_PLATFORMS=cpu \
    python -m pytest tests/test_fleet.py -q -m slow \
    -p no:cacheprovider -p no:xdist -p no:randomly; then
    echo "FATAL: fleet smoke failed" >&2
    exit 1
fi
phase_end "phase 6"

# Phase 7: chaos smoke — the deterministic fault-injection matrix.
# bench.py --chaos exits nonzero if any injected fault (site x kind,
# plus a permanent-hang wedge case) hangs a waiter past the watchdog
# bound, silently loses a request, breaks replay bitwise-parity, or
# leaves the engine unable to serve afterwards.
phase_begin "phase 7: chaos matrix (bench.py --chaos)"
if ! timeout -k 10 600 env JAX_PLATFORMS=cpu \
    python bench.py --chaos; then
    echo "FATAL: bench.py --chaos matrix failed" >&2
    exit 1
fi
phase_end "phase 7"

# Phase 8: fleet-boundary chaos — bench.py --chaos-fleet boots a live
# 2-replica CPU fleet behind the resilient router and runs the
# drop/latency/mid-body/flap matrix plus a fleet-wide shed burst,
# exiting nonzero on any silent loss, unbounded tail, failed flap
# recovery, or a burst the spill queue failed to absorb. Budgeted like
# the phase-2 shards (same 870 s ceiling); its wall-clock prints below.
phase_begin "phase 8: fleet chaos matrix (bench.py --chaos-fleet)"
if ! timeout -k 10 870 env JAX_PLATFORMS=cpu \
    python bench.py --chaos-fleet; then
    echo "FATAL: bench.py --chaos-fleet matrix failed" >&2
    exit 1
fi
phase_end "phase 8"

# Phase 9: paged-KV smoke — bench.py --paged exits nonzero if the paged
# engine's outputs diverge bitwise from the dense path (cold, prefix
# hits, sampled, streamed, concurrent, depths 1-2), if a prefix hit
# pays any assembly copy (assembly_bytes_peak must stay 0 while the
# dense comparison re-assembles), or if page accounting fails to admit
# strictly more mixed-length rows than window accounting in the same
# HBM budget (the margin prints on stderr).
phase_begin "phase 9: paged KV sweep (bench.py --paged)"
if ! timeout -k 10 600 env JAX_PLATFORMS=cpu \
    python bench.py --paged; then
    echo "FATAL: bench.py --paged sweep failed" >&2
    exit 1
fi
phase_end "phase 9"

# Phase 10: speculative-decoding smoke — bench.py --spec exits nonzero
# if any spec-on engine output diverges bitwise from the plain path
# (greedy + seeded-sampled, cold + prefix hits, streamed, concurrent,
# depths 1-2, dense + paged), if the accept-all workload fails to
# verify >1 token per weight read, or if engine tok/s fails to beat
# the plain engine by >1.5x on the repetitive-continuation workload
# (acceptance rate + tokens/step print in the JSON line and ride
# /metrics under batching.spec on live servers).
phase_begin "phase 10: speculative decoding sweep (bench.py --spec)"
if ! timeout -k 10 600 env JAX_PLATFORMS=cpu \
    python bench.py --spec; then
    echo "FATAL: bench.py --spec sweep failed" >&2
    exit 1
fi
phase_end "phase 10"

# Phase 11: sharded-serving smoke — bench.py --mesh forces 2 CPU host
# devices and exits nonzero if any tp=2 engine output diverges bitwise
# from the single-device path (greedy + seeded-sampled, cold + prefix
# hits, streamed, concurrent, depths 1-2, dense + paged), or if the
# live batching.mesh gauges show per-device KV/param bytes above 0.55x
# their replicated footprint (the 1/tp HBM split sharded serving
# exists for). tp=1-vs-tp=2 CPU tok/s prints in the JSON line
# (informational: tiny-dim CPU collectives are expected to lose).
phase_begin "phase 11: sharded serving mesh sweep (bench.py --mesh)"
if ! timeout -k 10 600 env JAX_PLATFORMS=cpu \
    python bench.py --mesh; then
    echo "FATAL: bench.py --mesh sweep failed" >&2
    exit 1
fi
phase_end "phase 11"

# Phase 12: disaggregated prefill/decode — bench.py --disagg boots
# subprocess replica pairs (dense, then paged) behind the phase-split
# router and exits nonzero if split-fleet outputs diverge bitwise from
# direct (greedy + seeded-sampled), if no KV ship actually lands (or a
# paged import is not a zero-copy page insert), if split-fleet decode
# tok/s under the cold-prefill burst fails the 1.2x gate vs the mixed
# fleet, or if an injected kv_ship failure costs any request.
phase_begin "phase 12: disaggregated serving sweep (bench.py --disagg)"
if ! timeout -k 10 870 env JAX_PLATFORMS=cpu \
    python bench.py --disagg; then
    echo "FATAL: bench.py --disagg sweep failed" >&2
    exit 1
fi
phase_end "phase 12"

# Phase 12b: the synthetic-RTT axis of the same split (bench.py
# --disagg-rtt) — every relayed KV chunk pays 66 ms through the
# kv_ship_chunk delay site and every cold-walk chunk 66 ms through
# prefix_walk, so the pipelined (chunked, windowed) ship must land
# cold-request TTFT <= 0.6x the blocking buffer-then-relay ship's
# (transfer hidden under prefill), and a permanent mid-stream chunk
# failure must deliver every request bitwise with zero client errors
# and no ship-dedup poisoning.
phase_begin "phase 12b: pipelined-ship RTT sweep (bench.py --disagg-rtt)"
if ! timeout -k 10 600 env JAX_PLATFORMS=cpu \
    python bench.py --disagg-rtt; then
    echo "FATAL: bench.py --disagg-rtt sweep failed" >&2
    exit 1
fi
phase_end "phase 12b"

# Phase 13: multi-turn sessions — bench.py --sessions exits nonzero if
# any conversation turn diverges bitwise from the direct single-server
# transcript (healthy, mid-conversation SIGKILL, or post-restart), if
# any turn surfaces a client error during failover, if turn-2+ TTFT on
# a healthy home exceeds 0.15x the cold turn-1 TTFT, or if pinned-leaf
# accounting fails to return to zero after sessions close.
phase_begin "phase 13: multi-turn session sweep (bench.py --sessions)"
if ! timeout -k 10 870 env JAX_PLATFORMS=cpu \
    python bench.py --sessions; then
    echo "FATAL: bench.py --sessions sweep failed" >&2
    exit 1
fi
phase_end "phase 13"

# Phase 14: composed-fault chaos soak — bench.py --soak runs the fixed
# CI seed set through the nemesis (1-3 overlapping fault-site events
# from the runtime/faults.py registry, >= 1 worker SIGKILL and >= 1
# drain per schedule) against a live 2-replica managed fleet (dense +
# paged) under a seeded open-loop mixed workload, then re-runs the
# first seed asserting a byte-identical timeline and identical verdict.
# Exits nonzero on any silent loss (delivered-but-wrong bytes, or a
# failure outside the priced-shed contract), an overlong waiter, a
# quiesce invariant that fails to converge (pagepool/pin accounting,
# spill depth), or a checker canary that fails to reject a
# suppressed-shed history. A failing seed prints its timeline file for
# one-command replay (bench.py --soak --seed N --replay-timeline F).
phase_begin "phase 14: composed-fault chaos soak (bench.py --soak)"
if ! timeout -k 10 870 env JAX_PLATFORMS=cpu \
    python bench.py --soak; then
    echo "FATAL: bench.py --soak failed" >&2
    exit 1
fi
phase_end "phase 14"

# Phase 15: elastic control plane — bench.py --autoscale fires an
# open-loop cold-prefill spike at a 2-replica mixed fleet and exits
# nonzero if the live FleetController fails to promote a prefill
# replica under the sustained queue-wait breach, if the autoscaled
# fleet's interactive queue-wait P99 fails to recover to <= 0.7x the
# static fleet's, if any delivered answer diverges bitwise or any
# request is silently lost through the controller's role flip, if the
# recorded decision trace fails to replay byte-identically from its
# snapshots, or if a dry-run controller over the same pressured fleet
# actuates anything (intents must log, actions must not fire).
phase_begin "phase 15: elastic control plane (bench.py --autoscale)"
if ! timeout -k 10 870 env JAX_PLATFORMS=cpu \
    python bench.py --autoscale; then
    echo "FATAL: bench.py --autoscale failed" >&2
    exit 1
fi
phase_end "phase 15"

# Phase 16: model-draft speculative tier — bench.py --spec-draft (2
# forced CPU host devices for its mesh leg) exits nonzero if any
# draft-on engine output diverges bitwise from the plain path (greedy +
# seeded-sampled, streamed, concurrent, dense + paged + tp=2 mesh,
# plus an aux DraftProvider leg), if the shallow-exit drafting engine
# fails to beat spec-off by >1.5x tok/s on a NON-repetitive workload
# (prompts selected so prompt-lookup pays nothing — the traffic the
# PR-9 lookup tier cannot speed up), if the per-row adaptive k fails
# to converge from its k=2 slow-start to the full bucket on easy rows
# (acceptance-EWMA and k-histogram gates), or if adversarial
# high-temperature rows fail to demote model->lookup->off and hold
# >= 0.95x spec-off wall-clock (the never-pay-the-draft-forward
# guarantee). Draft counters ride /metrics under batching.spec.draft.
phase_begin "phase 16: model-draft spec tier (bench.py --spec-draft)"
if ! timeout -k 10 870 env JAX_PLATFORMS=cpu \
    python bench.py --spec-draft; then
    echo "FATAL: bench.py --spec-draft sweep failed" >&2
    exit 1
fi
phase_end "phase 16"

# Phase 17: long-context capacity gate — bench.py --long-context
# serves logical contexts at 8x/16x/32x the compiled window through
# the sliding-window runner + paged-KV host offload inside ONE fixed
# page budget (a single compiled window of pages plus two slack) and
# exits nonzero if the pool sheds any work, if a within-window row
# diverges bitwise from the dense solo path, if TTFT grows
# superlinearly or tok/s cliffs between multipliers, if the re-online
# stall fraction exceeds its bound with the decode-cursor prefetch
# live (resident_cap churn forces real spills), or if the hot loop
# re-encodes the kvwire leaf template more than once.
phase_begin "phase 17: long-context capacity gate (bench.py --long-context)"
if ! timeout -k 10 600 env JAX_PLATFORMS=cpu \
    python bench.py --long-context; then
    echo "FATAL: bench.py --long-context gate failed" >&2
    exit 1
fi
phase_end "phase 17"

# Phase 18: whole-prompt sequence-parallel prefill — bench.py
# --sp-prefill (2 forced CPU host devices for the sp=2 mesh) exits
# nonzero if any prefill_mode=sp output diverges bitwise from the
# chunked engine on the same sharded server (greedy + seeded-sampled,
# cold + prefix-store hit, streamed, concurrent, dense + paged), if
# the long-context runner's sharded round schedule diverges from the
# serial window/2 slide chain at 8x/16x the compiled window (or leaks
# pool pages), or if cold TTFT through the sp walk exceeds 0.6x the
# chunked walk with per-chunk prefill device time modeled through the
# deterministic prefix_walk delay site (the PR-12b idiom: the sharded
# walk stacks sp chunks of device time onto one critical-path slot).
phase_begin "phase 18: sp prefill gate (bench.py --sp-prefill)"
if ! timeout -k 10 600 env JAX_PLATFORMS=cpu \
    python bench.py --sp-prefill; then
    echo "FATAL: bench.py --sp-prefill gate failed" >&2
    exit 1
fi
phase_end "phase 18"
exit 0
