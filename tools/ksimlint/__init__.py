"""ksimlint — the repo's AST contract analyzer (docs/lint.md).

Five rules turn this codebase's informal correctness contracts into
machine-checked invariants:

- ``lock-discipline``   ``# guarded-by:`` attributes only touched under
                        their lock (or in ``lock-held`` methods);
                        ``worker-thread`` functions never write driver
                        state.
- ``kernel-purity``     ``@device_kernel`` trace-time bodies stay free
                        of host effects and f32-determinism hazards.
- ``import-boundary``   the stdlib-only surfaces (chip_smoke.py and
                        trace_check.py parents, obs/faults/errors,
                        this analyzer) never reach
                        jax/numpy at import time.
- ``registry-literals`` every fault-site / span / event / fallback
                        reason literal resolves into its registry.
- ``env-contract``      every ``KSIM_*`` literal is documented in
                        docs/env.md, and vice versa.

Run ``make lint`` or ``python -m tools.ksimlint``; the package is
stdlib-only and safe in any environment (it never imports jax, numpy,
or ksim_tpu — everything is read from source ASTs).
"""

from tools.ksimlint.core import (
    DEFAULT_TARGETS,
    Finding,
    Project,
    SourceFile,
    run,
)

__all__ = ["DEFAULT_TARGETS", "Finding", "Project", "SourceFile", "run"]
