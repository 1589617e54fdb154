#!/usr/bin/env bash
# The one-command CI gate: static analysis, the fast serve suite, the fast
# chaos suite, then the tier-1 test suite.
#
#   scripts/ci_check.sh            # lint + obs/dpo/elastic/sched/serve/chaos-fast + tests
#   scripts/ci_check.sh --lint-only
#
# Lint: `ftc-lint finetune_controller_tpu/` must exit 0 — every finding is
# fixed or carries a justified `# ftc: ignore[rule-id] -- reason`
# (docs/static_analysis.md).  The v2 run includes the project-wide pass
# (call graph, lock discipline, RPC/metric conformance) under a 10s
# wall-clock budget so the interprocedural engine can never rot into a
# slow gate.  The clock is held HERE, where the lint runs alone;
# tests/test_project_analysis.py asserts the work that keeps it there (one
# index, every file parsed once), which a loaded test machine cannot move.
# Serve-fast: the continuous-batching inference suite (docs/serving.md) —
# batching invariance is THE serving correctness anchor, and a broken
# engine should fail in seconds, before the full tier-1 wall-clock.
# Chaos-fast: the resilience/fault-injection suite (docs/resilience.md)
# runs next and alone.  The full kill→resume loss-trajectory proof is
# marked `slow` and excluded here (run it with
# `pytest tests/test_chaos.py -m slow`).
# Tests: the tier-1 command from ROADMAP.md.
set -uo pipefail

cd "$(dirname "$0")/.."

echo "== ftc-lint (per-file + project-wide, 10s budget) ==" >&2
lint_start=$(date +%s)
python -m finetune_controller_tpu.analysis finetune_controller_tpu/
lint_rc=$?
lint_elapsed=$(( $(date +%s) - lint_start ))
if [ "$lint_rc" -ne 0 ]; then
    echo "ci_check: ftc-lint failed (exit $lint_rc)" >&2
    exit "$lint_rc"
fi
if [ "$lint_elapsed" -gt 10 ]; then
    echo "ci_check: ftc-lint took ${lint_elapsed}s — over the 10s budget;" \
         "the interprocedural pass must stay a fast gate" >&2
    exit 1
fi

if [ "${1:-}" = "--lint-only" ]; then
    exit 0
fi

echo "== shard-audit-fast (sharding conformance: heavy rules + AOT collective audit) ==" >&2
# The jax-importing sharding layer (docs/static_analysis.md §v3): the
# HEAVY project rules — rule-table coverage against abstract catalog param
# trees, axis-divisibility on every catalog topology, and the AOT
# collective audit that compiles the train/serve steps on simulated meshes
# and diffs the HLO collective set against docs/performance.md's
# Collective catalog — plus their test files (mutation flips included).
# These CANNOT ride the pure-AST lint stage above: importing jax alone
# blows the 10s budget, which is why the rules are registry-excluded by
# default and named explicitly here.
timeout -k 10 600 env JAX_PLATFORMS=cpu python -m finetune_controller_tpu.analysis \
    --rules shard-rule-coverage,shard-divisibility,collective-conformance \
    finetune_controller_tpu/
shard_lint_rc=$?
if [ "$shard_lint_rc" -ne 0 ]; then
    echo "ci_check: shard-audit-fast lint failed (exit $shard_lint_rc)" >&2
    exit "$shard_lint_rc"
fi
timeout -k 10 600 env JAX_PLATFORMS=cpu python -m pytest \
    tests/test_shard_conformance.py tests/test_collective_audit.py \
    tests/test_shard_audit.py -q \
    -p no:cacheprovider -p no:xdist -p no:randomly
shard_rc=$?
if [ "$shard_rc" -ne 0 ]; then
    echo "ci_check: shard-audit-fast failed (exit $shard_rc)" >&2
    exit "$shard_rc"
fi

echo "== obs-fast (tracing, timelines, histograms, phase profiling) ==" >&2
# The observability layer (docs/observability.md): span/event recorders,
# trace assembly + the gap-free validator, histogram exposition, the
# monitor's event ingest, and the hard-path timeline e2e (preempt ->
# resize -> retry -> promote).  Runs first among the suites — every later
# stage's diagnosis leans on these surfaces when IT fails.
timeout -k 10 600 env JAX_PLATFORMS=cpu python -m pytest \
    tests/test_obs.py tests/test_metrics_endpoint.py -q -m "not slow" \
    -p no:cacheprovider -p no:xdist -p no:randomly
obs_rc=$?
if [ "$obs_rc" -ne 0 ]; then
    echo "ci_check: obs-fast failed (exit $obs_rc)" >&2
    exit "$obs_rc"
fi

echo "== rlhf-fast (disaggregated rollout plane + reward model) ==" >&2
# The distributed RLHF data plane (docs/preference.md §Disaggregated
# rollouts): rollout RPC protocol idempotence, exactly-once dedup across
# respawns, policy rollover as adapter deltas, the Bradley–Terry reward
# trainer, AND the slow-marked chaos (SIGKILL mid-round) and remote-overlap
# e2e runs.  No 'not slow' filter: the e2es are excluded from tier-1 only
# to protect that stage's wall-clock.
timeout -k 10 900 env JAX_PLATFORMS=cpu python -m pytest \
    tests/test_rollout_plane.py tests/test_reward_model.py -q \
    -p no:cacheprovider -p no:xdist -p no:randomly
rlhf_rc=$?
if [ "$rlhf_rc" -ne 0 ]; then
    echo "ci_check: rlhf-fast failed (exit $rlhf_rc)" >&2
    exit "$rlhf_rc"
fi

echo "== dpo-fast (preference optimization: losses, data, actor/learner) ==" >&2
# DPO loss math (hand-computed logits, beta monotonicity, stop-gradient),
# seeded preference-pair round trips, rollout buffer/actor/learner loop,
# AND the slow-marked DPO preemption->resume e2e (docs/preference.md) —
# the prefs/ subsystem fails in minutes here, before everything else.
# No 'not slow' filter: the e2e is excluded from tier-1 only to protect
# that stage's wall-clock.
timeout -k 10 600 env JAX_PLATFORMS=cpu python -m pytest \
    tests/test_prefs.py tests/test_preference_data.py \
    tests/test_dpo_e2e.py -q \
    -p no:cacheprovider -p no:xdist -p no:randomly
dpo_rc=$?
if [ "$dpo_rc" -ne 0 ]; then
    echo "ci_check: dpo-fast failed (exit $dpo_rc)" >&2
    exit "$dpo_rc"
fi

echo "== elastic-fast (topology-portable checkpoints + resize) ==" >&2
# manifest round-trips, cross-topology (dp=2<->dp=1) restore bit-identity,
# resize planner/reservations/grow pass, supervisor topology handling, the
# resize-beats-evict sim gate, AND the slow-marked shrink->resume->grow e2e
# on real subprocesses (docs/elasticity.md) — the elastic layer fails in
# minutes here, before the sched/serve/chaos stages.  No 'not slow' filter:
# the e2e is excluded from tier-1 only to protect that stage's wall-clock.
timeout -k 10 600 env JAX_PLATFORMS=cpu python -m pytest \
    tests/test_elastic_restore.py tests/test_resize.py \
    "tests/test_sched_e2e.py::test_resize_shrinks_resumes_and_grows_back" -q \
    -p no:cacheprovider -p no:xdist -p no:randomly
elastic_rc=$?
if [ "$elastic_rc" -ne 0 ]; then
    echo "ci_check: elastic-fast failed (exit $elastic_rc)" >&2
    exit "$elastic_rc"
fi

echo "== sched-fast (fair-share properties on the simulator) ==" >&2
# pure control-flow (no trainer subprocesses): quota safety under
# preemption/backfill, victims-always-resume, Jain >= 0.8, FIFO starvation
# pins (docs/scheduling.md) — fails in seconds if admission regresses
timeout -k 10 300 env JAX_PLATFORMS=cpu python -m pytest \
    tests/test_sched.py -q \
    -p no:cacheprovider -p no:xdist -p no:randomly
sched_rc=$?
if [ "$sched_rc" -ne 0 ]; then
    echo "ci_check: sched-fast failed (exit $sched_rc)" >&2
    exit "$sched_rc"
fi

echo "== transport-fast (worker spawn, RPC protocol, cross-process failover) ==" >&2
# The cross-process serve transport (docs/serving.md §Cross-process
# transport): wire framing, the worker RPC protocol (in-process loopback),
# real worker-process spawn/probe/drain, the SIGKILLed-worker exactly-once
# proof, and the adapter registry-sync RPCs — the transport layer fails in
# minutes here, before the fleet suite that rides it.
timeout -k 10 900 env JAX_PLATFORMS=cpu python -m pytest \
    tests/test_transport.py -q \
    -p no:cacheprovider -p no:xdist -p no:randomly
transport_rc=$?
if [ "$transport_rc" -ne 0 ]; then
    echo "ci_check: transport-fast failed (exit $transport_rc)" >&2
    exit "$transport_rc"
fi

echo "== serve-chaos-fast (replica kill, drain, failover, autoscale) ==" >&2
# The fleet robustness anchors (docs/serving.md §Fleet): the 'not slow'
# replica-kill/drain/failover/autoscale tests lead, and the slow-marked
# fleet HTTP loops (429 Retry-After, concurrent-load CAS) ride along so
# the whole fleet layer is covered exactly once per gate, before the full
# serve suite below.
timeout -k 10 600 env JAX_PLATFORMS=cpu python -m pytest \
    tests/test_serve_fleet.py -q \
    -p no:cacheprovider -p no:xdist -p no:randomly
serve_chaos_rc=$?
if [ "$serve_chaos_rc" -ne 0 ]; then
    echo "ci_check: serve-chaos-fast failed (exit $serve_chaos_rc)" >&2
    exit "$serve_chaos_rc"
fi

echo "== kernels-fast (paged-attention kernel bit-identity + dispatch) ==" >&2
# The Pallas paged-attention kernel (docs/serving.md §Paged KV): interpret-
# mode bit-identity against the gather+chunked oracle across shapes/dtypes,
# the FTC_PAGED_ATTN dispatch gate, VMEM sizing, and the engine anchors
# under the forced kernel — a broken kernel fails here in seconds, before
# the serve suite exercises it indirectly.
timeout -k 10 600 env JAX_PLATFORMS=cpu python -m pytest \
    tests/test_paged_attention.py -q \
    -p no:cacheprovider -p no:xdist -p no:randomly
kernels_rc=$?
if [ "$kernels_rc" -ne 0 ]; then
    echo "ci_check: kernels-fast failed (exit $kernels_rc)" >&2
    exit "$kernels_rc"
fi

echo "== serve-fast (batching invariance + prefix cache + paged KV + adapters + metrics) ==" >&2
# no 'not slow' filter here: the serve suite IS this stage's whole job, so
# its slow-marked extras (sampled-decode parity, prefix-cache eviction
# mid-flight, the multi-tenant HTTP loop) run too — they are excluded from
# tier-1 below only to protect that stage's wall-clock budget
timeout -k 10 900 env JAX_PLATFORMS=cpu python -m pytest \
    tests/test_serve.py tests/test_prefix_cache.py \
    tests/test_kv_pages.py tests/test_serve_adapters.py \
    tests/test_metrics_endpoint.py -q \
    -p no:cacheprovider -p no:xdist -p no:randomly
serve_rc=$?
if [ "$serve_rc" -ne 0 ]; then
    echo "ci_check: serve-fast failed (exit $serve_rc)" >&2
    exit "$serve_rc"
fi

echo "== chaos-fast (resilience) ==" >&2
timeout -k 10 600 env JAX_PLATFORMS=cpu python -m pytest \
    tests/test_resilience.py tests/test_chaos.py -q -m 'not slow' \
    -p no:cacheprovider -p no:xdist -p no:randomly
chaos_rc=$?
if [ "$chaos_rc" -ne 0 ]; then
    echo "ci_check: chaos-fast failed (exit $chaos_rc)" >&2
    exit "$chaos_rc"
fi

echo "== tier-1 tests ==" >&2
rm -f /tmp/_t1.log
timeout -k 10 870 env JAX_PLATFORMS=cpu python -m pytest tests/ -q -m 'not slow' \
    --continue-on-collection-errors -p no:cacheprovider -p no:xdist -p no:randomly \
    2>&1 | tee /tmp/_t1.log
rc=${PIPESTATUS[0]}
echo "DOTS_PASSED=$(grep -aE '^[.FEsx]+( *\[ *[0-9]+%\])?$' /tmp/_t1.log | tr -cd . | wc -c)"
exit "$rc"
