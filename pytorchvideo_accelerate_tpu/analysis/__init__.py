"""Static analysis + runtime guards for JAX/TPU hazards (`pva-tpu-lint`).

The standing reviewer every PR must satisfy: a stdlib-`ast` pass over
the package that catches the performance/correctness bugs that hide as
legal Python in a jit+threads codebase — host-device syncs in the hot
loop, recompile hazards, half-locked shared state, trace-time side
effects, and discarded telemetry spans. `# pva: disable=<rule> -- why`
suppresses a line, auditable via `pva-tpu-doctor`'s lint snapshot.
Taxonomy and runbook: docs/STATIC_ANALYSIS.md.

Stdlib-only on purpose: the linter runs in CI, in tests/test_zlint.py
and from the doctor without importing jax or the code under analysis.
The one runtime piece (`RecompileGuard` -> `pva_train_recompiles`
gauge) closes the loop the static `recompile` rule can only hint at.
"""

from __future__ import annotations

from pytorchvideo_accelerate_tpu.analysis.core import (  # noqa: F401
    Finding,
    Rule,
    default_rules,
    iter_suppressions,
    lint_source,
    run_lint,
)
from pytorchvideo_accelerate_tpu.analysis.recompile_guard import (  # noqa: F401
    RecompileGuard,
    cache_size,
)

# jaxpr/HLO-level passes (pva-tpu-graphcheck) are NOT imported here:
# analysis/__init__ must stay importable without jax (the linter runs in
# CI and in the doctor against broken trees); reach them via
# `pytorchvideo_accelerate_tpu.analysis.graphcheck` directly.
from pytorchvideo_accelerate_tpu.analysis.tsan import (  # noqa: F401
    Tsan,
    get_tsan,
)
