#!/usr/bin/env bash
# Fast verify tiers (PYTHONPATH handled for you):
#
#   scripts/tier1.sh            # fast tier: everything except @slow
#                               # (subprocess dry-runs, training loops)
#   scripts/tier1.sh core       # kernel/core edit loop (~1 min): SLaB
#                               # decomposition, Pallas kernels, taps,
#                               # flash-decode, HLO analysis
#   scripts/tier1.sh pipeline   # compression-policy loop: compressor
#                               # registry, plans, layer-wise pipeline,
#                               # taps (mixed-method e2e stays @slow)
#   scripts/tier1.sh packed     # packed-serving loop: variant-tagged
#                               # formats, per-variant kernels (incl.
#                               # ELL gather-matmul), heterogeneous
#                               # stacks, segmented-scan serving, e2e
#                               # packed forward/decode (full-depth
#                               # trace-count cases stay @slow)
#   scripts/tier1.sh moe        # expert-packed MoE loop: K_max
#                               # bucketing, vmapped expert kernels,
#                               # dense-member fallbacks, MoE/hybrid
#                               # shared-block parity (engine replay +
#                               # deepseek geometry stay @slow)
#   scripts/tier1.sh engine     # serving-engine loop: paged KV
#                               # cache + block allocator, request
#                               # scheduler policy, flash_decode
#                               # (contiguous + paged), engine e2e
#                               # traces vs greedy_decode
#   scripts/tier1.sh faults     # fault-tolerance loop: request
#                               # lifecycle statuses, deadlines, load
#                               # shedding, starvation caps, the
#                               # finite-logits guard, and the chaos
#                               # harness (FaultPlan) e2e recovery
#                               # traces incl. seed determinism +
#                               # block-leak teardown checks
#   scripts/tier1.sh allocator  # budget-allocator loop: water-filling
#                               # solver, @auto plans, plan DSL
#                               # round-trips, cross-variant kernel
#                               # parity sweep
#   scripts/tier1.sh distributed # tensor-parallel packed-serving loop:
#                               # per-variant Planner specs, segment
#                               # pre-slicing, decode parity under a
#                               # real 2-device mesh (2 fake CPU
#                               # devices; the 8-device subprocess
#                               # suite stays @slow in
#                               # tests/test_distributed.py)
#   scripts/tier1.sh <pytest args...>   # anything else passes through
#
# The full suite (the tier-1 gate, incl. @slow) stays:
#   PYTHONPATH=src python -m pytest -q
set -euo pipefail
cd "$(dirname "$0")/.."
export PYTHONPATH="src${PYTHONPATH:+:$PYTHONPATH}"

if [ "${1:-}" = "core" ]; then
    shift
    exec python -m pytest -q -m "not slow" \
        tests/test_slab_core.py tests/test_substrates.py \
        tests/test_kernels.py tests/test_flash_decode.py \
        tests/test_taps.py tests/test_perf_features.py "$@"
fi

if [ "${1:-}" = "pipeline" ]; then
    shift
    exec python -m pytest -q -m "not slow" \
        tests/test_plan.py tests/test_pipeline.py tests/test_taps.py "$@"
fi

if [ "${1:-}" = "packed" ]; then
    shift
    exec python -m pytest -q -m "not slow" \
        tests/test_kernels.py tests/test_packed_serving.py \
        tests/test_hetero_packing.py tests/test_variant_parity.py \
        tests/test_ell_kernels.py tests/test_segmented_scan.py "$@"
fi

if [ "${1:-}" = "moe" ]; then
    shift
    exec python -m pytest -q -m "not slow" \
        tests/test_expert_packing.py tests/test_models.py "$@"
fi

if [ "${1:-}" = "distributed" ]; then
    shift
    exec env XLA_FLAGS="--xla_force_host_platform_device_count=2" \
        python -m pytest -q -m "not slow" \
        tests/test_packed_sharding.py "$@"
fi

if [ "${1:-}" = "engine" ]; then
    shift
    exec python -m pytest -q -m "not slow" \
        tests/test_serving_engine.py tests/test_flash_decode.py "$@"
fi

if [ "${1:-}" = "faults" ]; then
    shift
    exec python -m pytest -q -m "not slow" \
        tests/test_serving_faults.py "$@"
fi

if [ "${1:-}" = "allocator" ]; then
    shift
    exec python -m pytest -q -m "not slow" \
        tests/test_allocator.py tests/test_plan_roundtrip.py \
        tests/test_plan.py tests/test_variant_parity.py "$@"
fi
exec python -m pytest -q -m "not slow" "$@"
