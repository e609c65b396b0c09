#!/usr/bin/env bash
# Pre-PR gate, eleven stages:
#   1. graftlint --changed      — per-file rules on just the .py/.yaml
#      files changed vs the merge-base with main (fast half; stays
#      O(diff) as the repo grows)
#   2. graftlint --project      — whole-project mode: per-file rules over
#      everything PLUS the interprocedural call-chain analysis PLUS the
#      conf/ <-> schema cross-checks PLUS the concurrency rules
#      (unsynchronized-shared-mutation, lock-order-inversion,
#      blocking-call-under-lock, check-then-act-race). This is the real
#      gate; it is the same invocation tests/test_analysis.py's
#      self-gate pins at zero unwaived findings and zero stale waivers.
#   3. jaxpr dtype audit        — trace the synthetic-task train step
#      under the default fp32 policy and diff the jaxpr's
#      convert_element_type ops against the static dtype findings and
#      waivers. Must be clean: a reduced->wide upcast appearing here
#      before any bf16 work lands is a dtype-flow regression.
#   4. compact-train smoke      — the end-to-end harness lifecycle on
#      synthetic .tpk data: 3 IMP levels, asserts the second level
#      re-instantiates physically smaller, round-trips exactly back to
#      full coordinates, eval parity holds across the exit expansion,
#      and the per-width caches evict. Isolated stage so a compaction
#      regression is named before the full suite runs.
#   5. nm smoke                 — the N:M gathered-execution lifecycle on
#      the same synthetic data: level 0 dense, nm criterion projects at
#      prune time, the projected level runs gathered and exits back to
#      the dense step functions with one cached executable, stale plans
#      evict, and compact_train composes. Isolated so an N:M regression
#      is named before the full suite runs.
#   6. planner smoke            — the one-planner decision table + mixed
#      lifecycle (sparse/plan.py): every mask population lands on the
#      right backend with machine-readable reasons, autotune demotes
#      layers where gathering loses, mixed-plan logits/grads match
#      masked-dense on VGG and ViT, and the 3-level harness lifecycle
#      enters ONE mixed bundle and evicts it stale. Isolated so a
#      planner regression is named before the full suite runs.
#   7. serving-load smoke       — the fleet serving drain + open-loop
#      load generator on a jax-free fake engine: graceful drain answers
#      in-flight work then sheds, and the Poisson sweep finds the
#      saturation knee at the overloaded point, not the healthy one.
#      Isolated (and jax-light, so it's fast) because loadgen bugs
#      otherwise surface as flaky latency numbers in BENCH, not as a
#      named failure.
#   8. graftsan smoke           — the runtime lock-order sanitizer drives
#      the PrefetchEngine (pool decoders + transfer thread + racing
#      closes) and a 2-model FleetEngine under 1-slot LRU churn with
#      every package lock wrapped: an observed lock-order cycle, a
#      self-deadlock, or a shared-write race the static layer never
#      claimed (a lexical-model blind spot) fails the stage. Dynamic
#      mirror of stage 2, exactly as stage 3 mirrors the dtype rules.
#   9. exec-manifest round-trip — rebuild the static compile-surface
#      manifest (jit entries x compile sites x bucket sets x plan kinds)
#      and diff it against the checked-in
#      turboprune_tpu/analysis/exec_manifest.json. The lockfile locks the
#      SET: entries by (file, name, reason), sites by (file, target), plan
#      kinds by file, and no line number, so a line that moves is no
#      drift. Drift means code grew, dropped or renamed an executable the
#      manifest doesn't know: re-emit with --exec-manifest emit and review
#      the diff like a lockfile change.
#  10. compile audit            — the runtime mirror of stage 9: patch
#      jax's backend_compile, drive the serving engine (warmup + padded
#      predict) and the jitted train step, and fail on any XLA compile
#      not attributed to a manifest entry, or any compiled (plan,
#      bucket) outside the declared surface.
#  11. tier-1 fast tests        — the suite as the driver runs it (the
#      command of /root/TESTS_LAST_RUN.json: six xdist workers, one file
#      a worker at a time; its ALLOW_MULTIPLE_LIBTPU_LOAD is the
#      driver's to set, not a repository file's), under the driver's
#      limit of 1,470 s. One process cannot finish inside that limit.
# Each stage prints its wall time (even when it fails, so slow-AND-broken
# is visible as both). Exits nonzero if any stage fails. Run from
# anywhere: paths resolve relative to the repo root.
set -euo pipefail
cd "$(dirname "$0")/.."

run_stage() {
    local name="$1"
    shift
    echo "== ${name} =="
    local t0=${SECONDS} rc=0
    "$@" || rc=$?
    echo "-- ${name}: $(( SECONDS - t0 ))s (rc=${rc})"
    return "${rc}"
}

run_stage "graftlint --changed (per-file, vs merge-base with main)" \
    python -m turboprune_tpu.analysis --changed

run_stage "graftlint --project (interprocedural + config rules)" \
    python -m turboprune_tpu.analysis --project turboprune_tpu conf tests

run_stage "jaxpr dtype audit (train step, fp32 policy)" \
    env JAX_PLATFORMS=cpu python -m turboprune_tpu.analysis --jaxpr-audit train

run_stage "compact-train smoke (harness lifecycle on synthetic .tpk)" \
    env JAX_PLATFORMS=cpu python -m pytest \
    tests/test_compact_train.py::TestHarnessCompactTrainSmoke -q \
    -p no:cacheprovider -p no:xdist -p no:randomly

run_stage "nm smoke (gathered N:M lifecycle on synthetic .tpk)" \
    env JAX_PLATFORMS=cpu python -m pytest \
    tests/test_nm.py::TestHarnessNMSmoke -q \
    -p no:cacheprovider -p no:xdist -p no:randomly

run_stage "planner smoke (decision table + mixed plan lifecycle)" \
    env JAX_PLATFORMS=cpu python -m pytest \
    tests/test_plan.py -q \
    -p no:cacheprovider -p no:xdist -p no:randomly

run_stage "serving-load smoke (drain + open-loop knee, fake engine)" \
    env JAX_PLATFORMS=cpu python -m pytest \
    tests/test_fleet.py::TestGracefulDrain \
    tests/test_fleet.py::TestLoadgen -q \
    -p no:cacheprovider -p no:xdist -p no:randomly

run_stage "graftsan smoke (runtime lock-order + race sanitizer)" \
    env JAX_PLATFORMS=cpu python -m turboprune_tpu.analysis --sanitize all

run_stage "exec-manifest round-trip (static compile surface vs checked-in)" \
    python -m turboprune_tpu.analysis --exec-manifest diff

run_stage "compile audit (runtime compiles attributed to the manifest)" \
    env JAX_PLATFORMS=cpu python -m turboprune_tpu.analysis --compile-audit all

run_stage "tier-1 tests (fast tier, CPU, six workers)" \
    timeout -k 10 1470 env JAX_PLATFORMS=cpu \
    python -m pytest tests/ -q -m 'not slow' \
    --continue-on-collection-errors -p no:cacheprovider \
    -p xdist -n 6 --dist loadfile -p no:randomly

echo "check.sh: all gates passed"
