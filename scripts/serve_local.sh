#!/usr/bin/env bash
# Run the control plane locally for development (reference: the local serve
# wrappers in scripts/): API server with the in-process monitor and the
# subprocess "fake cluster" local backend — the full submit -> train ->
# metrics -> promote lifecycle with zero cluster dependencies.
#
# Usage: scripts/serve_local.sh [port]
set -euo pipefail

PORT="${1:-8787}"

export FTC_ENVIRONMENT="${FTC_ENVIRONMENT:-local}"
export FTC_BACKEND="${FTC_BACKEND:-local}"
export FTC_MONITOR_IN_PROCESS="${FTC_MONITOR_IN_PROCESS:-true}"
# pre-warmed trainer processes for the default (cpu-test) flavor: first
# submit skips the JAX import wait.  Set 0 on a one-chip TPU host if the
# default flavor is a TPU one — an idle warm worker holds the chip.
export FTC_WARM_WORKERS="${FTC_WARM_WORKERS:-1}"
# what the SERVER's own JAX users run on (in-process serving, generate_cli):
# the CPU unless told otherwise.  Jobs do not inherit it — a cpu flavor's
# trainer gets JAX_PLATFORMS=cpu, every other flavor's gets tpu and fails
# if it finds no chip (controller/backends/local.py::_runtime_env).  On a
# TPU host serve with FTC_SERVE_TRANSPORT=process so the server never holds
# the chip (docs/serving.md).
export JAX_PLATFORMS="${JAX_PLATFORMS:-cpu}"

exec python -m finetune_controller_tpu.controller.server --port "${PORT}"
