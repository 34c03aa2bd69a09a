#!/usr/bin/env bash
# The full analysis gate (docs/STATIC_ANALYSIS.md + docs/RELIABILITY.md):
# the pva-tpu-lint AST pass over the package tree, a short pva-tpu-tsan
# stress pass (lockset races + lock-order cycles over the threaded
# data/train/serve layers), the pva-tpu-graphcheck jaxpr/HLO passes over
# the real train/eval/serve steps (donation aliasing, dtype policy,
# sharding propagation, analytic FLOP coverage), the pva-tpu-spmdcheck
# collective-schedule divergence pass (multi-host readiness), then the
# pva-tpu-chaos fault-injection
# scenario (retry/preemption/shedding recovery asserted under seeded
# faults — including the PR-9 self-healing legs: guard_nan NaN-rollback,
# corrupt-clip quarantine, and the wedged-collective hang detector).
# Speed is not judged here: the benchmark is `python3 benchmarks/run.py`
# (BENCHMARK.json, PERF.md). The fused kernels' parity with their XLA
# references and the fleet control loops' verdicts are tier-1 tests
# (tests/test_zkernels.py, tests/test_zcontrol.py, tests/test_zhbmobs.py).
# Exit codes: 0 clean, 1 findings, 2 usage — CI gates on nonzero.
# Extra args pass through to the lint step only
# (e.g. `scripts/analyze.sh --select host-sync`).
set -euo pipefail
ROOT="$(cd "$(dirname "$0")/.." && pwd)"

"${ROOT}/scripts/lint.sh" "$@"

env PYTHONPATH="${ROOT}${PYTHONPATH:+:${PYTHONPATH}}" \
  JAX_PLATFORMS="${JAX_PLATFORMS:-cpu}" \
  python -m pytorchvideo_accelerate_tpu.analysis.tsan_report --smoke

# compiled-graph gate (docs/STATIC_ANALYSIS.md § graphcheck): the four
# jaxpr/HLO passes — donation aliasing, dtype policy, sharding
# propagation, analytic FLOP coverage — over the real train/eval/
# serve step functions; exit 1 on any finding
env PYTHONPATH="${ROOT}${PYTHONPATH:+:${PYTHONPATH}}" \
  JAX_PLATFORMS="${JAX_PLATFORMS:-cpu}" \
  python -m pytorchvideo_accelerate_tpu.analysis.graphcheck

# collective-schedule divergence gate (docs/STATIC_ANALYSIS.md
# § spmdcheck): the spmd-divergence kinds (divergent predicates,
# asymmetric branches, skip paths, checkpoint-write discipline) plus the
# collective_section coverage audit over the hot modules — the
# multi-host pod runtime's precondition; exit 1 on any finding
env PYTHONPATH="${ROOT}${PYTHONPATH:+:${PYTHONPATH}}" \
  JAX_PLATFORMS="${JAX_PLATFORMS:-cpu}" \
  python -m pytorchvideo_accelerate_tpu.analysis.spmdcheck

# disaggregated data-plane gate (docs/INPUT_PIPELINE.md § disaggregated
# data plane): 2 remote decode-worker processes must produce a byte-
# identical batch stream to the local loader on the same source/seed,
# with input-wait no worse than local; exit 1 on parity break/regression
env PYTHONPATH="${ROOT}${PYTHONPATH:+:${PYTHONPATH}}" \
  JAX_PLATFORMS="${JAX_PLATFORMS:-cpu}" \
  python -m pytorchvideo_accelerate_tpu.dataplane.bench --smoke

rc=0
env PYTHONPATH="${ROOT}${PYTHONPATH:+:${PYTHONPATH}}" \
  JAX_PLATFORMS="${JAX_PLATFORMS:-cpu}" \
  python -m pytorchvideo_accelerate_tpu.reliability.chaos --smoke || rc=$?

exit "$rc"
