"""`ftc-lint`: JAX-aware static analysis for the two planes of this repo.

The compute plane (jitted training/inference code) and the controller plane
(async control-plane services, thread-backed pipelines) fail in different,
equally silent ways: a host sync inside a jitted step loop shows up only as a
mysteriously slow TPU profile; a swallowed exception in the reconciler shows
up as a job stuck QUEUED forever.  This package makes both classes of hazard
a mechanical CI failure instead of an expensive rediscovery:

* :mod:`engine` — the AST walker, the per-file AND project-wide rule
  registries, ``# ftc: ignore[rule-id]`` suppressions, text/JSON/SARIF
  reporting, and the ``ftc-lint`` console entry;
* :mod:`project` — the v2 core: whole-package module/symbol index, a
  conservative call graph, and async/thread-entry/jit classification;
* :mod:`rules_compute` — host-sync-in-jit, prng-key-reuse, recompile
  hazards, missing-donation;
* :mod:`rules_controller` — silent-except, shared-mutable-without-lock,
  blocking-io-in-async;
* :mod:`rules_flow` — the transitive (interprocedural) versions of
  blocking-io-in-async and host-sync-in-jit, with rendered call chains;
* :mod:`rules_concurrency` — lock-discipline: guarded-field inference for
  lock-holding classes, loop-vs-worker-thread race detection without one;
* :mod:`rules_protocol` — rpc-conformance (transport worker + state
  service op/payload tables vs their clients) and metric-doc-drift
  (emitted ``ftc_*`` families vs docs/observability.md's catalog);
* :mod:`recompile_guard` — the runtime complement: counts distinct jit
  signatures behind ``TrainConfig.recompile_budget`` and
  warns or raises when a shape-unstable step blows the budget;
* :mod:`transfer_guard` — runtime complement #2: wraps the trainer step
  and serve decode hot windows in ``jax.transfer_guard`` (plus a
  backend-independent ``jax.device_get`` trap) behind
  ``TrainConfig.transfer_guard`` / ``FTC_TRANSFER_GUARD``, armed by
  the benchmark so a reintroduced sync aborts the run.

``tests/test_lint_clean.py`` gates the repo: zero unsuppressed findings over
``finetune_controller_tpu/``.  See ``docs/static_analysis.md``.
"""

from .engine import Finding, LintResult, lint_paths, lint_source, main  # noqa: F401

__all__ = [
    "Finding",
    "LintResult",
    "lint_paths",
    "lint_source",
    "main",
    "RecompileGuard",
    "RecompileBudgetExceeded",
    "TransferGuard",
    "TransferGuardError",
]


def __getattr__(name: str):
    # the guards pull in jax; loaded lazily so the pure-AST `ftc-lint` CLI
    # (and scripts/ci_check.sh, which runs it first) stays jax-import-free
    if name in ("RecompileGuard", "RecompileBudgetExceeded"):
        from . import recompile_guard

        return getattr(recompile_guard, name)
    if name in ("TransferGuard", "TransferGuardError"):
        from . import transfer_guard

        return getattr(transfer_guard, name)
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")
