"""Tests that only run on real TPU hardware (skipped on the CPU CI mesh).

CPU CI exercises the Pallas kernels in interpreter mode and compiles them
for a described chip (``tests/test_chip_compile.py``); a Mosaic miscompile —
particularly in the segment-mask path — would still ship unnoticed without a
compiled-on-TPU parity check.  This is the pytest-gated form for TPU-equipped
CI; ``chip_smoke.py`` is the end-to-end gate.

Run with:  JAX_PLATFORMS=tpu python -m pytest tests/test_tpu_only.py -q
(the conftest pins the suite to CPU, so the TPU run must override it via
FTC_TEST_TPU=1).
"""

from __future__ import annotations

import os

import pytest

PARITY_SNIPPET = r"""
import jax, numpy as np
import jax.numpy as jnp
from finetune_controller_tpu.ops.pallas.flash_attention import flash_attention
from finetune_controller_tpu.ops.attention import xla_causal_attention

assert jax.devices()[0].platform == "tpu", jax.devices()
rng = np.random.default_rng(0)
b, s, h, hkv, d = 2, 2048, 8, 4, 64
q = jnp.asarray(rng.standard_normal((b, s, h, d)), jnp.bfloat16)
k = jnp.asarray(rng.standard_normal((b, s, hkv, d)), jnp.bfloat16)
v = jnp.asarray(rng.standard_normal((b, s, hkv, d)), jnp.bfloat16)
# packed-document segments: monotone ids with ragged boundaries + padded tail
seg = np.zeros((b, s), np.int32)
for row in range(b):
    bounds = sorted(rng.choice(np.arange(64, s - 64), 5, replace=False))
    for i, lo in enumerate(bounds):
        seg[row, lo:] = i + 1
seg[:, -37:] = 99  # padding segment
seg = jnp.asarray(seg)

ref = xla_causal_attention(q, k, v, segment_ids=seg)
out = jax.jit(
    lambda q, k, v: flash_attention(q, k, v, segment_ids=seg, interpret=False)
)(q, k, v)
err = float(jnp.max(jnp.abs(out.astype(jnp.float32) - ref.astype(jnp.float32))))

def loss_flash(q, k, v):
    o = flash_attention(q, k, v, segment_ids=seg, interpret=False)
    return jnp.sum(o.astype(jnp.float32) ** 2)

def loss_ref(q, k, v):
    return jnp.sum(xla_causal_attention(q, k, v, segment_ids=seg).astype(jnp.float32) ** 2)

gf = jax.jit(jax.grad(loss_flash, argnums=(0, 1, 2)))(q, k, v)
gr = jax.jit(jax.grad(loss_ref, argnums=(0, 1, 2)))(q, k, v)
gerr = max(
    float(jnp.max(jnp.abs(a.astype(jnp.float32) - b.astype(jnp.float32))))
    for a, b in zip(gf, gr)
)
import json
print(json.dumps({"fwd_max_err": err, "grad_max_err": gerr,
                  "ok": bool(err < 3e-2 and gerr < 2.0)}))
"""


requires_tpu = pytest.mark.skipif(
    not os.environ.get("FTC_TEST_TPU"),
    reason="TPU-only: set FTC_TEST_TPU=1 on a TPU host",
)


@requires_tpu
def test_compiled_flash_attention_with_segments_matches_xla():
    import subprocess
    import sys

    env = {k: v for k, v in os.environ.items() if k != "JAX_PLATFORMS"}
    env["JAX_PLATFORMS"] = "tpu"
    out = subprocess.run(
        [sys.executable, "-c", PARITY_SNIPPET],
        capture_output=True, text=True, timeout=900, env=env,
    )
    assert out.returncode == 0, out.stderr[-2000:]
    import json

    rec = json.loads(out.stdout.strip().splitlines()[-1])
    assert rec["ok"], rec
