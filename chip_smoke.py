#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that the system still starts on the chip.

    python chip_smoke.py              one TPU chip: submit -> train -> promote
                                      -> serve, at the full width and depth of
                                      tinyllama-1.1b, through the API a user
                                      would call
    python chip_smoke.py --chips 4    four chips: train.cli with mesh fsdp=4
                                      against the same spec on one chip, and
                                      nothing else
    JAX_PLATFORMS=cpu python chip_smoke.py --tiny [--chips 4]
                                      the same control flow on the CPU with
                                      tiny-test — a rehearsal, never a pass

This process never imports JAX: a parent that has touched JAX holds the chip,
and the trainer or serve worker it starts would then fail or hang.  It learns
the device from what its children report (the trainer's ``train-started``
event, the serve worker's ``runtime`` stats) and fails unless every one of
them ran on a TPU.  Training and serving take the chip in turn; everything
the script starts is stopped before it ends.

One line per phase goes to stdout as it happens.  Any phase that fails ends
the run with a non-zero exit code and no result line.  After a full pass the
last line is exactly::

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}

Compiled programs are cached where ``JAX_COMPILATION_CACHE_DIR`` says, else in
``<checkout>/.cache/xla`` (``finetune_controller_tpu/platform.py``), so a
second run in the same checkout shows warm compile times next to the first
run's cold ones.
"""

from __future__ import annotations

import argparse
import concurrent.futures
import contextlib
import csv
import json
import os
import random
import shutil
import signal
import socket
import statistics
import subprocess
import sys
import tempfile
import time
import urllib.error
import urllib.request
import uuid
from pathlib import Path

REPO = Path(__file__).resolve().parent

#: every process the script starts inherits this variable; whatever still
#: carries it when the script ends is an orphan
MARKER_ENV = "CHIP_SMOKE_RUN"

#: per-step |loss(fsdp=4) - loss(1 chip)| the four-chip comparison allows.
#: Same seed, data and global batch; what differs is the order of the bf16
#: reductions (four batch shards, gathered parameters), which the optimizer
#: then amplifies step by step.  Measured on one v5e chip between two runs
#: that differ in nothing but reduction order (grad_accum_steps 1 against 4):
#: 0.0008 over 24 steps at the learning rate used here — and 0.11 at ten
#: times that rate, where step 6 lands on either side of an instability.
FOUR_CHIP_LOSS_TOL = 0.005
#: max |kernel - gather| the paged-attention parity child allows: two
#: roundings of the storage dtype at the outputs' magnitude (unit-normal V
#: rows average to |out| < 4, where one bf16 ulp is 2**-6)
PAGED_TOL = {"bfloat16": 2 ** -5, "float32": 4e-6}

#: runs in a child AFTER the server has gone (the chip is free again): the
#: Pallas paged kernel against chunked_cache_attention over the gathered
#: cache, compiled for whatever backend the child lands on
PAGED_PARITY_SNIPPET = r"""
import json, sys
import jax, jax.numpy as jnp
from finetune_controller_tpu.platform import device_report, enable_compile_cache
from finetune_controller_tpu.ops.attention import (
    chunked_cache_attention, paged_gather, paged_kernel_eligible)
from finetune_controller_tpu.ops.pallas.paged_attention import paged_attention

enable_compile_cache()
shapes, dtype_name = json.loads(sys.argv[1]), sys.argv[2]
dtype = jnp.dtype(dtype_name)
on_tpu = jax.default_backend() == "tpu"
oracle = jax.jit(lambda q, k, v, t, i: chunked_cache_attention(
    q, paged_gather(k, t), paged_gather(v, t), i))
rows = []
for n, (b, s, h, hkv, d, t, mp) in enumerate(shapes):
    ks = jax.random.split(jax.random.PRNGKey(n), 5)
    pages = b * mp + 1
    q = jax.random.normal(ks[0], (b, s, h, d), dtype)
    k = jax.random.normal(ks[1], (pages, t, hkv, d), dtype)
    v = jax.random.normal(ks[2], (pages, t, hkv, d), dtype)
    table = jax.random.randint(ks[3], (b, mp), 0, pages, jnp.int32)
    table = table.at[:, -1].set(0)  # unmaterialised tail -> scratch page
    idx = jax.random.randint(ks[4], (b,), 0, mp * t - s + 1, jnp.int32)
    eligible = bool(paged_kernel_eligible(q, k, v, table))
    if on_tpu and not eligible:
        raise SystemExit(f"auto would not pick the kernel at {shapes[n]}")
    got = paged_attention(q, k, v, table, idx)  # compiled on a TPU
    want = oracle(q, k, v, table, idx)
    err = float(jnp.max(jnp.abs(
        got.astype(jnp.float32) - want.astype(jnp.float32))))
    finite = bool(jnp.all(jnp.isfinite(got.astype(jnp.float32))))
    rows.append({"shape": shapes[n], "max_err": err, "finite": finite,
                 "eligible": eligible})
print(json.dumps({"device": device_report(), "dtype": dtype_name,
                  "compiled": on_tpu, "cases": rows}))
"""


#: max |kernel - ragged_dot| the grouped-product parity child allows, as a
#: share of the reference's largest magnitude: two roundings of bf16 at the
#: top of the outputs' range (both accumulate in float32; the tiles change
#: the order)
GROUPED_TOL = 2 ** -6

#: runs in a child BEFORE the server starts (nothing holds the chip yet):
#: ``models/moe.py::_grouped_dot`` at the tiles its rule picks — on a TPU the
#: Pallas megablox
#: kernel — against ``jax.lax.ragged_dot``, values and activation gradient,
#: the weights ARGUMENTS of the jit (closed over they would be constants of
#: the program: gigabytes of host memory), on group sizes even, skewed, with
#: empty groups and with rows no group covers.  A tile that compiles and
#: then faults dies here in a minute.
GROUPED_PARITY_SNIPPET = r"""
import json, sys
import jax, jax.numpy as jnp, numpy as np
from finetune_controller_tpu.platform import device_report, enable_compile_cache
from finetune_controller_tpu.models import moe

enable_compile_cache()
shapes = json.loads(sys.argv[1])
on_tpu = jax.default_backend() == "tpu"


def loads(rng, m, g):
    even = np.bincount(rng.integers(0, g, m), minlength=g)
    p = np.exp(1.2 * rng.standard_normal(g))
    skewed = np.bincount(rng.choice(g, m, p=p / p.sum()), minlength=g)
    p[rng.permutation(g)[: max(1, g // 5)]] = 0
    empty = np.bincount(rng.choice(g, m, p=p / p.sum()), minlength=g)
    uncovered = np.bincount(rng.integers(0, g, m - m // 3), minlength=g)
    return {"even": even, "skewed": skewed, "empty_groups": empty,
            "rows_no_group_covers": uncovered}


def both(dot):
    # the product and its activation gradient under a fixed cotangent
    def f(rows, kernels, sizes, layer, cot):
        out, vjp = jax.vjp(lambda r: dot(r, kernels, sizes, layer), rows)
        return out, vjp(cot)[0]
    return jax.jit(f)


kernel = both(moe._grouped_dot)
oracle = both(lambda r, w, s, l: jax.lax.ragged_dot(r, w[l], s))
cases = []
for n_case, (m, g, k, n, layers) in enumerate(shapes):
    if on_tpu and not moe._pallas_grouped_dot_ok(m):
        raise SystemExit(f"the Pallas kernel would not run at {shapes[n_case]}")
    ks = jax.random.split(jax.random.PRNGKey(n_case), 3)
    rows = jax.random.normal(ks[0], (m, k), jnp.bfloat16)
    kernels = jax.random.normal(ks[1], (layers, g, k, n), jnp.bfloat16) * k ** -0.5
    cot = jax.random.normal(ks[2], (m, n), jnp.bfloat16)
    layer = jnp.int32(layers - 1)
    for name, sizes in loads(np.random.default_rng(n_case), m, g).items():
        sizes = jnp.asarray(sizes, jnp.int32)
        covered = (jnp.arange(m) < sizes.sum())[:, None]
        got = kernel(rows, kernels, sizes, layer, cot)
        want = oracle(rows, kernels, sizes, layer, cot)
        errs, finite = [], True
        for a, b in zip(got, want):
            # rows behind the last group: the Pallas kernel writes nothing
            a = jnp.where(covered, a, 0).astype(jnp.float32)
            b = jnp.where(covered, b, 0).astype(jnp.float32)
            errs.append(float(jnp.max(jnp.abs(a - b)) / jnp.max(jnp.abs(b))))
            finite = finite and bool(jnp.all(jnp.isfinite(a)))
        tile = moe.gmm_row_tile(m, g)
        cases.append({"shape": shapes[n_case], "sizes": name,
                      "tiles": [list(moe._GmmTiling(g)(m, k, n)),
                                list(moe._GmmTiling(g)(m, n, k))],
                      "work_over_need": float(moe.gmm_work_over_need(sizes, tile)),
                      "value_err": errs[0], "grad_err": errs[1], "finite": finite})
    del rows, kernels, cot, got, want, a, b   # the next stack needs the room
print(json.dumps({"device": device_report(), "compiled": on_tpu, "cases": cases}))
"""

#: largest error of the chunked scan, as a share of the largest magnitude of
#: the token-by-token recurrence's result: bf16 operands, float32 sums
SSD_TOL = 2 ** -6

#: the state-space mixer's chunked scan AS THE MIXER RUNS IT
#: (``ops/pallas/ssd_scan.py::ssd_scan``, the chooser's one function: the Pallas
#: kernels on the chip, ``models/ssm.py::ssd_chunked`` off it; bf16 products,
#: float32 decays and states) against the recurrence itself, a token at a
#: time in float32 (``benchmarks/reference/falcon_h1.py::recurrence``) —
#: values and the gradient of the inputs under a fixed cotangent, at the
#: family's own ranges (``A`` in [1, 16], step sizes log-uniform in [0.001,
#: 0.1]: decays near 1, so the carry between chunks is most of the result),
#: rows (rows, heads, head size, groups, state size, chunk); each case says
#: which form ran (``ssm_scan_impl``) and its heads a block
SSD_PARITY_SNIPPET = r"""
import json, sys
import jax, jax.numpy as jnp, numpy as np
from finetune_controller_tpu.platform import device_report, enable_compile_cache
from finetune_controller_tpu.ops.pallas.ssd_scan import ssd_scan, ssd_scan_impl
from benchmarks.reference.falcon_h1 import recurrence

enable_compile_cache()
cases = []
for n_case, (s, h, p, g, n, chunk) in enumerate(json.loads(sys.argv[1])):
    rng = np.random.default_rng(n_case)
    x, b, c, cot = (jnp.asarray(rng.standard_normal(shape), jnp.bfloat16)
                    for shape in ((1, s, h, p), (1, s, g, n), (1, s, g, n),
                                  (1, s, h, p)))
    dt = jnp.asarray(np.exp(rng.uniform(np.log(1e-3), np.log(1e-1), (1, s, h))),
                     jnp.float32)
    a = -jnp.asarray(rng.uniform(1.0, 16.0, (h,)), jnp.float32)
    d = jnp.ones((h,), jnp.float32)

    def chunked(x, dt, b, c):
        return ssd_scan(x, dt, a, b, c, d, chunk=chunk)

    def by_token(x, dt, b, c):
        x32 = x.astype(jnp.float32).reshape(1, s, g, h // g, p)
        dtg = dt.reshape(1, s, g, h // g)
        with jax.default_matmul_precision("highest"):
            y = recurrence(x32 * dtg[..., None], dtg * a.reshape(g, h // g),
                           b.astype(jnp.float32), c.astype(jnp.float32))
        return (y + x32).reshape(1, s, h, p)

    def both(f):
        def run(x, dt, b, c, cot):
            out, vjp = jax.vjp(f, x, dt, b, c)
            return (out, *vjp(cot.astype(out.dtype)))
        return jax.jit(run)

    got, want = both(chunked)(x, dt, b, c, cot), both(by_token)(x, dt, b, c, cot)
    errs = [float(jnp.max(jnp.abs(u.astype(jnp.float32) - v.astype(jnp.float32)))
                  / jnp.max(jnp.abs(v.astype(jnp.float32))))
            for u, v in zip(got, want)]
    impl, heads = ssd_scan_impl(h, p, g, n, chunk)
    cases.append({"shape": [s, h, p, g, n, chunk], "value_err": errs[0],
                  "grad_err": max(errs[1:]), "chunks": -(-s // chunk),
                  "ssm_scan_impl": impl, "ssm_scan_heads_per_block": heads,
                  "finite": all(bool(jnp.all(jnp.isfinite(u.astype(jnp.float32))))
                                for u in got)})
print(json.dumps({"device": device_report(),
                  "compiled": jax.default_backend() == "tpu", "cases": cases}))
"""


#: the flash kernels under a window and beside a sink — ``ops/pallas/
#: flash_attention.py`` compiled on the chip (interpreted off it), bf16 — against
#: the XLA form (``ops/attention.py::xla_causal_attention``: an explicit mask,
#: the sink one more column of a plain softmax) on the float32 values of the
#: same operands, a query head at a time so that no ``[H, S, S]`` array exists:
#: values and the gradients of q, k, v and the sink under a fixed cotangent.
#: Cases (rows, query heads, key/value heads, q/k width, v width, window (0 =
#: every earlier key), sink?); a window case says what its kernels compute
#: over what the window needs (``flash_window_work_over_need``)
WINDOW_TOL = 2 ** -5

WINDOW_PARITY_SNIPPET = r"""
import json, sys
import jax, jax.numpy as jnp, numpy as np
from finetune_controller_tpu.platform import device_report, enable_compile_cache
from finetune_controller_tpu.ops.attention import xla_causal_attention
from finetune_controller_tpu.ops.pallas.flash_attention import (
    causal_work_over_need, flash_attention, window_work_over_need)

enable_compile_cache()
on_chip = jax.default_backend() == "tpu"
dtype = jnp.bfloat16 if on_chip else jnp.float32
cases = []
for n_case, (s, h, hkv, d, dv, window, with_sink) in enumerate(json.loads(sys.argv[1])):
    rng = np.random.default_rng(n_case)
    q, k, v, cot = (jnp.asarray(rng.standard_normal(shape), dtype) for shape in
                    ((1, s, h, d), (1, s, hkv, d), (1, s, hkv, dv), (1, s, h, dv)))
    sink = jnp.asarray(rng.standard_normal((h,)), jnp.float32)
    window = window or None

    def kernels(q, k, v, sink):
        return flash_attention(q, k, v, window=window,
                               sink=sink if with_sink else None)

    def xla_by_head(q, k, v, sink):
        q, k, v = (a.astype(jnp.float32) for a in (q, k, v))
        k, v = (jnp.repeat(a, h // hkv, axis=2) for a in (k, v))

        @jax.checkpoint
        def one(head):
            qh, kh, vh, sh = head
            with jax.default_matmul_precision("highest"):
                return xla_causal_attention(
                    qh[:, :, None], kh[:, :, None], vh[:, :, None], window=window,
                    sink=sh[None] if with_sink else None)[:, :, 0]

        out = jax.lax.map(one, (jnp.moveaxis(q, 2, 0), jnp.moveaxis(k, 2, 0),
                                jnp.moveaxis(v, 2, 0), sink))
        return jnp.moveaxis(out, 0, 2)

    def both(f):
        def run(q, k, v, sink, cot):
            out, vjp = jax.vjp(f, q, k, v, sink)
            return (out, *vjp(cot.astype(out.dtype)))
        return jax.jit(run)

    got, want = both(kernels)(q, k, v, sink, cot), both(xla_by_head)(q, k, v, sink, cot)
    names = ("value", "dq", "dk", "dv", "dsink")[:5 if with_sink else 4]
    errs = {name: float(jnp.max(jnp.abs(u.astype(jnp.float32) - w.astype(jnp.float32)))
                        / jnp.max(jnp.abs(w.astype(jnp.float32))))
            for name, u, w in zip(names, got, want)}
    work = (window_work_over_need(s, window, head_widths=(d, dv)) if window
            else causal_work_over_need(s, head_widths=(d, dv)))
    cases.append({"shape": [s, h, hkv, d, dv, window or 0, int(with_sink)],
                  "errs": errs, "work_over_need": work,
                  "finite": all(bool(jnp.all(jnp.isfinite(u.astype(jnp.float32))))
                                for u in got)})
print(json.dumps({"device": device_report(), "compiled": on_chip, "cases": cases}))
"""


#: the held share of a latent expert layer — the grouped path of
#: ``models/moe.py`` (the share's own pairs sorted, gathered and multiplied by
#: two grouped products an expert without a gate needs, ``held_row_bound``
#: rows a pass, the latent projections around it) — against the masked plain
#: form (every held expert on every row, float32, weights zero where the
#: expert was not chosen; the SAME router product decides both, so no near-tie
#: falls two ways): value and the input's gradient under a fixed cotangent,
#: bf16 products against float32 ones
LATENT_TOL = 2 ** -5

#: (rows, d_model, latent, expert width, experts routed over, held, per token)
LATENT_SHARE_PARITY_SNIPPET = r"""
import json, sys
import jax, jax.numpy as jnp, numpy as np
from finetune_controller_tpu.platform import device_report, enable_compile_cache
from finetune_controller_tpu.models.lora import LoRADense
from finetune_controller_tpu.models.moe import MoEMLP, held_row_bound

enable_compile_cache()
cases = []
for n_case, (rows, d, latent, f, e, held, k) in enumerate(json.loads(sys.argv[1])):
    bf16 = jnp.bfloat16
    proj = lambda width: LoRADense(features=width, dtype=bf16, param_dtype=bf16,
                                   parent=None)
    layer = MoEMLP(d_model=d, d_ff=f, n_experts=e, top_k=k, dispatch="dropless",
                   scoring="sigmoid", routed_scale=5.0, experts_held=(0, held),
                   gated=False, aux_loss=False, dtype=bf16, param_dtype=bf16,
                   fc1_latent_proj=proj(latent), fc2_latent_proj=proj(d))
    rng = np.random.default_rng(n_case)
    x, cot = (jnp.asarray(rng.standard_normal((1, rows, d)), bf16) for _ in "xc")
    params = jax.jit(lambda: layer.init(jax.random.PRNGKey(n_case), x)["params"])()

    def grouped(x, params):
        return layer.apply({"params": params}, x, mutable=("moe_stats",))

    def masked(x, params):
        f32 = lambda t: t.astype(jnp.float32)
        xt = f32(x).reshape(rows, d)
        # the layer's own router product: the same choices on both sides
        scores = jax.nn.sigmoid(jnp.einsum(
            "td,de->te", xt, f32(params["router"]["kernel"])))
        _, chosen = jax.lax.top_k(scores, k)
        w = jnp.take_along_axis(scores, chosen, axis=-1)
        w = w / (w.sum(-1, keepdims=True) + 1e-20) * 5.0
        mask = (jax.nn.one_hot(chosen, e, dtype=jnp.float32)
                * w[..., None]).sum(1)[:, :held]
        with jax.default_matmul_precision("highest"):
            r = xt @ f32(params["fc1_latent_proj"]["kernel"])

            def one(acc, xs):
                up, down, col = xs
                return acc + col[:, None] * (
                    jnp.square(jax.nn.relu(r @ f32(up))) @ f32(down)), None

            total = jax.lax.scan(one, jnp.zeros_like(r), (
                params["experts"]["up_proj"]["kernel"],
                params["experts"]["down_proj"]["kernel"], mask.T))[0]
            out = total @ f32(params["fc2_latent_proj"]["kernel"])
        return out.reshape(1, rows, d), mask

    @jax.jit
    def both(x, params, cot):
        c32 = cot.astype(jnp.float32)

        def under_the_cotangent(fn):
            def weighed(t):
                out, aux = fn(t, params)
                return (out.astype(jnp.float32) * c32).sum(), (out, aux)
            return jax.value_and_grad(weighed, has_aux=True)

        (_, (got, stats)), got_dx = under_the_cotangent(grouped)(x)
        (_, (want, mask)), want_dx = under_the_cotangent(masked)(x)
        return got, stats, got_dx, want, mask, want_dx

    got, stats, got_dx, want, mask, want_dx = both(x, params, cot)
    err = lambda u, v: float(jnp.max(jnp.abs(u.astype(jnp.float32) - v))
                             / jnp.max(jnp.abs(v)))
    pairs = float(stats["moe_stats"]["pairs"][0])
    cases.append({"shape": [rows, d, latent, f, e, held, k],
                  "value_err": err(got, want),
                  "grad_err": err(got_dx, want_dx.astype(jnp.float32)),
                  "pairs": pairs, "pairs_by_the_mask": float((mask > 0).sum()),
                  "row_bound": held_row_bound(rows * k, held, e),
                  "pairs_over_bound": float(stats["moe_stats"]["pairs_over_bound"][0]),
                  "finite": bool(jnp.all(jnp.isfinite(got.astype(jnp.float32)))
                                 & jnp.all(jnp.isfinite(got_dx.astype(jnp.float32))))})
print(json.dumps({"device": device_report(),
                  "compiled": jax.default_backend() == "tpu", "cases": cases}))
"""


#: the joined LoRA product against the layer's old expression: the forms
#: differ by where they round (apart: base, delta, sum; joined: once), by
#: two bf16 ulps of the largest magnitude at most
LORA_TOL = 2 ** -6

LORA_PARITY_SNIPPET = r"""
import json, sys
import jax, jax.numpy as jnp, numpy as np
from finetune_controller_tpu.platform import device_report, enable_compile_cache
from finetune_controller_tpu.models.lora import joined_product
from finetune_controller_tpu.models.quant import dequantize_int4, quantize_int4

enable_compile_cache()
cases = []
for n_case, (rows, n_in, n_out, rank, block, scale) in enumerate(json.loads(sys.argv[1])):
    rng = np.random.default_rng(n_case)
    draw = lambda *shape: jnp.asarray(rng.standard_normal(shape), jnp.float32)
    packed, scales = quantize_int4(draw(n_in, n_out) * n_in ** -0.5, block)
    x, cot = draw(rows, n_in).astype(jnp.bfloat16), draw(rows, n_out).astype(jnp.bfloat16)
    a, b = draw(n_in, rank) * 0.02, draw(rank, n_out) * 0.02

    def joined(x, a, b):
        return joined_product(x, dequantize_int4(packed, scales), a, b, scale)

    def apart(x, a, b):
        y = x @ dequantize_int4(packed, scales)
        return y + (x @ a.astype(x.dtype)) @ b.astype(x.dtype) * scale

    def both(f):
        def run(x, a, b, cot):
            out, vjp = jax.vjp(f, x, a, b)
            return (out, *vjp(cot))
        return jax.jit(run)

    got, want = both(joined)(x, a, b, cot), both(apart)(x, a, b, cot)
    errs = [float(jnp.max(jnp.abs(u.astype(jnp.float32) - v.astype(jnp.float32)))
                  / jnp.max(jnp.abs(v.astype(jnp.float32))))
            for u, v in zip(got, want)]
    cases.append({"shape": [rows, n_in, n_out, rank], "value_err": errs[0],
                  "grad_err": max(errs[1:]),
                  "finite": all(bool(jnp.all(jnp.isfinite(u.astype(jnp.float32))))
                                for u in got)})
print(json.dumps({"device": device_report(),
                  "compiled": jax.default_backend() == "tpu", "cases": cases}))
"""


class SmokeFailure(Exception):
    """A phase did not do what it had to; the run ends non-zero."""


def say(phase: str, seconds: float, **fields) -> None:
    print(f"phase {phase}: {seconds:.1f}s {json.dumps(fields, sort_keys=True)}",
          flush=True)


def check(cond: bool, message: str) -> None:
    if not cond:
        raise SmokeFailure(message)


# ---------------------------------------------------------------------------
# what each mode runs
# ---------------------------------------------------------------------------


def mode_config(tiny: bool, seed: int) -> dict:
    """The one place the full run and the rehearsal differ."""
    # 24 steps, one checkpoint.  At full depth the increment loss of a
    # random-init base falls slowly under LoRA — 0.17 by step 24 on the chip,
    # against a batch-to-batch spread of +-0.05 — so a handful of steps cannot
    # tell a falling loss from noise, and each step costs 1.3 s.  At 0.002
    # the loss falls as far as at 0.02 and the run is numerically steady (see
    # FOUR_CHIP_LOSS_TOL); at 0.02 it overshoots around step 6.
    train = {
        "total_steps": 24, "warmup_steps": 1, "learning_rate": 0.002,
        "log_every": 1, "checkpoint_every": 24, "seed": seed,
    }
    if tiny:
        return {
            "platform": "cpu", "model_name": "tiny-test-lora",
            "preset": "tiny-test", "device": "cpu-test", "vocab": 256,
            "arguments": {**train, "batch_size": 4, "seq_len": 64,
                          "lora_rank": 4},
            "attention_impl": "xla",
            # interpret-mode parity at small shapes (b, s, h, hkv, d, t, mp)
            "paged_shapes": [[2, 1, 4, 2, 16, 8, 5], [1, 12, 4, 2, 16, 8, 5]],
            # the compiler's own grouped product against itself: control flow
            # only (rows, groups, k, n, layers of the stack)
            "grouped_shapes": [[256, 8, 64, 32, 2]],
            # the chunked scan against the recurrence: five and a half chunks
            "ssd_shapes": [[44, 4, 8, 2, 6, 8]],
            # the joined LoRA product against the layer's old expression
            # (rows, in, out, rank, quantisation block, scale)
            "lora_shapes": [[48, 64, 96, 4, 16, 2.0]],
            # a window call with a sink and a full call (rows, heads,
            # key/value heads, q/k, v, window, sink?)
            "window_shapes": [[40, 4, 2, 24, 16, 5, 1], [40, 4, 1, 24, 16, 0, 0]],
            # a held share of a latent expert layer against its masked form
            "latent_shapes": [[64, 32, 16, 24, 16, 4, 4]],
        }
    return {
        "platform": "tpu", "model_name": "tinyllama-1.1b-lora",
        "preset": "tinyllama-1.1b", "device": "v5e-1", "vocab": 32000,
        # batch 8 x seq 2048 is where attention_impl="auto" takes the Pallas
        # flash kernels forward and backward; the frozen base must be bf16
        # for that shape to fit one 16 GB chip
        "arguments": {**train, "batch_size": 8, "seq_len": 2048,
                      "lora_rank": 8, "frozen_dtype": "bfloat16"},
        "attention_impl": "pallas",
        # tinyllama's serve shapes (decode + the 32/128/512 prefill buckets)
        # and a head-dim-128 GQA model's decode and widest prefill
        "paged_shapes": [
            [8, 1, 32, 4, 64, 16, 40], [1, 32, 32, 4, 64, 16, 40],
            [1, 128, 32, 4, 64, 16, 40], [1, 512, 32, 4, 64, 16, 40],
            [8, 1, 32, 8, 128, 16, 40], [1, 512, 32, 8, 128, 16, 40],
        ],
        # the grouped expert products of the two expert configurations (up
        # and down: the activation gradient of one has the other's shapes),
        # read in place in a stack of 4 layers / 2 (3.2 GB / 0.8 GB a leaf),
        # and a decode step's 32 lanes x 8 over 256 experts
        "grouped_shapes": [
            [65536, 256, 2048, 768, 4], [65536, 256, 768, 2048, 4],
            [16384, 16, 6144, 2048, 2], [16384, 16, 2048, 6144, 2],
            [256, 256, 2048, 768, 1],
        ],
        # one block's scan of the hybrid configuration at its published
        # widths (32 heads of 128 x 256 states, B and C in 2 groups) and of
        # the pattern one (128 heads of 64 x 128 states, 8 groups): 1,024
        # rows, eight chunks of 128
        "ssd_shapes": [[1024, 32, 128, 2, 256, 128], [1024, 128, 64, 8, 128, 128]],
        # one Mistral-width projection (gate / up) over an int4 base, 2,048
        # rows, rank 16: the adapter inside the base product's contraction
        "lora_shapes": [[2048, 4096, 14336, 16, 64, 2.0]],
        # the window/full configuration's two attention calls as published
        # (benchmarks/configs/mimo-v2-flash-lora.json): one row of 16,384, 64
        # query heads of 192 beside v heads of 128 — over 8 key/value heads
        # under a window of 128 keys beside a sink, over 4 with every earlier key
        "window_shapes": [[16384, 64, 8, 192, 128, 128, 1],
                          [16384, 64, 4, 192, 128, 0, 0]],
        # one expert layer of the pattern configuration at its published
        # widths: 1,024 rows, top-22 of 512 experts of 1024 x 2688 in a
        # 1024-wide latent of a 4096-wide state, 128 held
        "latent_shapes": [[1024, 4096, 1024, 2688, 512, 128, 22]],
    }


def child_env(run_id: str, platform: str, extra: dict | None = None) -> dict:
    env = dict(os.environ)
    env["PYTHONPATH"] = str(REPO) + (
        os.pathsep + env["PYTHONPATH"] if env.get("PYTHONPATH") else "")
    env[MARKER_ENV] = run_id
    # full mode pins the children to the chip: one that finds none fails at
    # backend start-up instead of quietly running somewhere else
    env["JAX_PLATFORMS"] = platform
    env.update(extra or {})
    return env


def cache_state() -> tuple[str, int]:
    """Where the children cache compiled programs, and how many entries
    that directory holds now (the package's helper imports no JAX)."""
    from finetune_controller_tpu.platform import compile_cache_dir

    path = Path(compile_cache_dir())
    return str(path), sum(1 for _ in path.iterdir()) if path.is_dir() else 0


# ---------------------------------------------------------------------------
# process hygiene
# ---------------------------------------------------------------------------


def marked_pids(run_id: str) -> list[int]:
    """Live processes started by this run (they inherited the marker)."""
    needle = f"{MARKER_ENV}={run_id}".encode()
    found = []
    for entry in os.listdir("/proc"):
        if not entry.isdigit() or int(entry) == os.getpid():
            continue
        try:
            with open(f"/proc/{entry}/environ", "rb") as f:
                if needle in f.read().split(b"\0"):
                    found.append(int(entry))
        except OSError:
            continue  # gone, or not ours to read
    return found


def wait_gone(run_id: str, timeout_s: float) -> list[int]:
    deadline = time.monotonic() + timeout_s
    while True:
        left = marked_pids(run_id)
        if not left or time.monotonic() >= deadline:
            return left
        time.sleep(0.2)


def kill_marked(run_id: str) -> None:
    for pid in marked_pids(run_id):
        with contextlib.suppress(ProcessLookupError, PermissionError):
            os.kill(pid, signal.SIGKILL)


# ---------------------------------------------------------------------------
# the API
# ---------------------------------------------------------------------------


class Api:
    def __init__(self, port: int):
        self.base = f"http://127.0.0.1:{port}/api/v1"

    def call(self, method: str, path: str, body: dict | None = None,
             timeout: float = 60.0) -> dict:
        data = None if body is None else json.dumps(body).encode()
        req = urllib.request.Request(
            self.base + path, data=data, method=method,
            headers={"Content-Type": "application/json"},
        )
        try:
            with urllib.request.urlopen(req, timeout=timeout) as resp:
                return json.loads(resp.read() or b"{}")
        except urllib.error.HTTPError as e:
            detail = e.read().decode(errors="replace")[:2000]
            raise SmokeFailure(
                f"{method} {path} -> HTTP {e.code}: {detail}") from None

    def get(self, path: str, **kw) -> dict:
        return self.call("GET", path, **kw)

    def post(self, path: str, body: dict | None = None, **kw) -> dict:
        return self.call("POST", path, body or {}, **kw)


def check_server_off_jax(api: Api, after: str) -> None:
    """The server must never hold the chip: its children need it."""
    check(api.get("/health").get("jax_backend") is False,
          f"the API server started a JAX backend during {after!r}: on a TPU "
          "host it now holds the chip")


def free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def tail(path: Path, n: int = 40) -> str:
    try:
        lines = path.read_text(errors="replace").splitlines()
    except OSError:
        return f"<{path} unreadable>"
    # the server logs one access line per poll: not what a failure is about
    return "\n".join([l for l in lines if "aiohttp.access" not in l][-n:])


# ---------------------------------------------------------------------------
# one chip: submit -> train -> promote -> serve
# ---------------------------------------------------------------------------


def start_server(work: Path, run_id: str, platform: str) -> tuple:
    port = free_port()
    log = work / "server.log"
    env = child_env(run_id, platform, {
        "FTC_ENVIRONMENT": "local", "FTC_BACKEND": "local",
        "FTC_MONITOR_IN_PROCESS": "true",
        "FTC_STATE_DIR": str(work / "state"),
        "FTC_OBJECT_STORE_ROOT": str(work / "objects"),
        # an idle warm worker would hold the chip against the job
        "FTC_WARM_WORKERS": "0",
        "FTC_JOB_MONITOR_INTERVAL_S": "1",
        "FTC_ARTIFACT_SYNC_INTERVAL_S": "2",
        # a failed attempt is a failed phase, not something to retry past
        "FTC_RETRY_MAX_ATTEMPTS": "0",
        # the worker process owns the chip while serving, never the server
        "FTC_SERVE_TRANSPORT": "process", "FTC_SERVE_PAGED_KV": "true",
        "FTC_SERVE_AUTOLOAD": "false",
        "FTC_SERVE_WORKER_SPAWN_TIMEOUT_S": "900",
        "FTC_SERVE_REQUEST_TIMEOUT_S": "300",
        "FTC_RATE_LIMIT_READ_PER_MIN": "6000",
        "FTC_RATE_LIMIT_GENERATE_PER_MIN": "6000",
    })
    with open(log, "wb") as out:
        proc = subprocess.Popen(
            [sys.executable, "-m", "finetune_controller_tpu.controller.server",
             "--port", str(port)],
            cwd=str(work), env=env, stdout=out, stderr=subprocess.STDOUT,
            start_new_session=True,
        )
    api = Api(port)
    deadline = time.monotonic() + 120
    while True:
        if proc.poll() is not None:
            raise SmokeFailure(
                f"server exited with {proc.returncode}:\n{tail(log)}")
        try:
            api.get("/health", timeout=2)
            return proc, api, log
        except (SmokeFailure, OSError):
            check(time.monotonic() < deadline,
                  f"server not healthy in 120 s:\n{tail(log)}")
            time.sleep(0.3)


def training_summary(cfg: dict, who: str, started: dict, finished: dict,
                     rows: list[dict]) -> dict:
    """What a training run must show, from what the trainer wrote about
    itself: the ``train-started`` / ``train-finished`` event attributes and
    the metric rows, one per step, in step order.  Shared by the job the API
    ran and the ``train.cli`` runs of ``--chips 4``."""
    device = {k: started.get(k) for k in ("platform", "kind", "count")}
    check(device["platform"] == cfg["platform"],
          f"{who} ran on {device}, not on a {cfg['platform']}")
    check(started.get("attention_impl") == cfg["attention_impl"],
          f"{who}: attention resolved to {started.get('attention_impl')!r}, "
          f"expected {cfg['attention_impl']!r}")
    steps = cfg["arguments"]["total_steps"]
    check(len(rows) == steps, f"{who}: {len(rows)} metric rows, {steps} steps")
    losses = [float(r["loss"]) for r in rows]
    check(all(x == x and abs(x) != float("inf") for x in losses),
          f"{who}: non-finite loss in {losses}")
    check(losses[-1] < losses[0],
          f"{who}: loss did not fall: first {losses[0]}, last {losses[-1]}")
    compute_s = [float(r["phase_compute_ms"]) / 1000 for r in rows]
    return {
        "device": device, "mesh": started.get("mesh"),
        "attention_impl": started["attention_impl"], "losses": losses,
        # the first step's compute time is the compile (cold, or a cache
        # hit) plus one step; the median of the rest is a step
        "first_step_s": round(compute_s[0], 2),
        "steady_step_s": round(statistics.median(compute_s[1:]), 3),
        "state_bytes": started.get("device_state_bytes"),
        "peak_bytes": finished.get("device_peak_bytes"),
    }


def train_phase(api: Api, cfg: dict) -> tuple[str, dict]:
    t0 = time.monotonic()
    job_id = api.post("/jobs", {
        "model_name": cfg["model_name"], "device": cfg["device"],
        "arguments": cfg["arguments"],
    })["job_id"]
    say("submit", time.monotonic() - t0, job_id=job_id,
        model=cfg["model_name"], flavor=cfg["device"],
        arguments=cfg["arguments"])

    deadline = time.monotonic() + 1000
    while True:
        job = api.get(f"/jobs/{job_id}")
        status = str(job["status"]).lower()
        if status == "succeeded":
            break
        if status in ("failed", "cancelled", "lost"):
            lines = api.get(f"/jobs/{job_id}/logs?last_lines=30")["lines"]
            raise SmokeFailure(
                f"job {job_id} ended {status}: {job.get('metadata')}\n"
                + "\n".join(lines))
        check(time.monotonic() < deadline, f"job {job_id} still {status}")
        time.sleep(1.0)
    seconds = time.monotonic() - t0
    meta = job.get("metadata") or {}
    check(not meta.get("restarts"),
          f"the trainer needed {meta.get('restarts')} restart(s)")

    # what the trainer reported about itself: events.jsonl rides the
    # artifact sync and the monitor ingests it, a tick or two behind the job
    needed = ("train-started", "checkpoint-committed", "train-finished")
    deadline = time.monotonic() + 60
    while True:
        events = {e["event"]: e for e in
                  api.get(f"/jobs/{job_id}/timeline")["events"]}
        if all(n in events for n in needed):
            break
        check(time.monotonic() < deadline,
              f"timeline lacks {[n for n in needed if n not in events]}")
        time.sleep(0.5)
    records = sorted(api.get(f"/jobs/{job_id}/metrics")["records"],
                     key=lambda r: float(r["step"]))
    summary = training_summary(
        cfg, "the trainer", events["train-started"]["attrs"],
        events["train-finished"]["attrs"], records)

    listing = {a["path"] for a in
               api.get(f"/jobs/{job_id}/artifacts?list=1")["artifacts"]}
    check("done.txt" in listing, "no done.txt among the artifacts")
    manifest = (f"checkpoints/step_{cfg['arguments']['total_steps']}"
                "/manifest.json")
    check(manifest in listing, f"no {manifest} among the artifacts")
    say("train", seconds, **summary, checkpoint=manifest)
    return job_id, summary["device"]


def promote_phase(api: Api, job_id: str) -> None:
    t0 = time.monotonic()
    api.post(f"/jobs/{job_id}/promote")
    deadline = time.monotonic() + 300
    while True:
        job = api.get(f"/jobs/{job_id}")
        status = str(job.get("promotion_status")).lower()
        if status == "completed":
            break
        check(status != "failed", f"promotion failed: {job.get('metadata')}")
        check(time.monotonic() < deadline, f"promotion still {status}")
        time.sleep(0.5)
    say("promote", time.monotonic() - t0, uri=job.get("promotion_uri"))


def make_prompts(vocab: int, seed: int) -> list[list[int]]:
    """Mixed lengths covering the 32/128/512 prefill buckets; the first
    token is unique per prompt so no two share a prefix."""
    rng = random.Random(seed)
    lengths = [5, 24, 32, 60, 128, 129, 300, 512]
    return [
        [2 + i] + [rng.randrange(2, vocab) for _ in range(n - 1)]
        for i, n in enumerate(lengths)
    ]


def serve_phase(api: Api, job_id: str, cfg: dict, seed: int) -> tuple:
    t0 = time.monotonic()
    api.post(f"/admin/serve/{job_id}/load", timeout=1000)
    load_s = time.monotonic() - t0
    deadline = time.monotonic() + 30
    while True:
        session = api.get("/admin/serve")["sessions"][job_id]
        check(session["transport"] == "process" and session["worker_pids"],
              f"no serve worker process: {session.get('transport')}")
        replicas = list(session["replicas"].values())
        check(len(replicas) == 1, f"{len(replicas)} replicas, expected 1")
        runtime = replicas[0].get("runtime") or {}
        # the worker's device reaches the session with its first stats beat,
        # which a loaded host delivers after the load call has returned
        if runtime or time.monotonic() > deadline:
            break
        time.sleep(0.5)
    device = {k: runtime.get(k) for k in ("platform", "kind", "count")}
    check(device["platform"] == cfg["platform"],
          f"the serve worker ran on {device}, not on a {cfg['platform']}")
    paged = runtime.get("paged_attention") or {}
    check(bool(paged), "the serve engine is not paged")
    if cfg["platform"] == "tpu":
        check(set(paged.values()) == {"kernel"},
              f"paged attention did not resolve to the kernel: {paged}")
    say("serve-load", load_s, device=device, paged_attention=paged,
        # every prefill bucket and the decode step, compiled (or loaded from
        # the compile cache) and run once before the worker takes traffic
        warm_start_s=runtime.get("warm_start_s"),
        worker_pids=session["worker_pids"])

    prompts = make_prompts(cfg["vocab"], seed)
    new_tokens = 16

    def generate(i: int, wave: str) -> list[int]:
        out = api.post(f"/jobs/{job_id}/generate", {
            "request_id": f"{wave}-{i}", "tokens": prompts[i],
            "max_new_tokens": new_tokens, "timeout_s": 300,
        }, timeout=330)
        tokens = out["tokens"]
        check(len(tokens) == new_tokens
              and all(0 <= t < cfg["vocab"] for t in tokens),
              f"request {wave}-{i} answered {tokens}")
        return tokens

    # first: every prompt once, one at a time, on a cold prefix cache
    t1 = time.monotonic()
    first = [generate(i, "first") for i in range(len(prompts))]
    first_s = time.monotonic() - t1
    # together: the same eight at once share the decode batch (and queue for
    # the prefill), joining and leaving mid-flight
    t2 = time.monotonic()
    with concurrent.futures.ThreadPoolExecutor(len(prompts)) as pool:
        together = list(pool.map(lambda i: generate(i, "together"),
                                 range(len(prompts))))
    together_s = time.monotonic() - t2
    # alone: and once more one at a time.  "together" and "alone" both find
    # their prompt in the prefix cache, so they run the same programs on the
    # same cached pages and differ only in who else rides the batch
    t3 = time.monotonic()
    alone = [generate(i, "alone") for i in range(len(prompts))]
    alone_s = time.monotonic() - t3
    differ = [i for i in range(len(prompts)) if together[i] != alone[i]]
    check(not differ,
          "greedy output depends on batching for prompts "
          f"{differ}: together {[together[i] for i in differ]} "
          f"alone {[alone[i] for i in differ]}")
    # reported, not required: a prefix hit prefills only the prompt's tail,
    # through a smaller bucket's program than the cold prefill ran — equal
    # bit for bit on the CPU, last-bit different on the chip, which can move
    # a greedy near-tie of a random-weight model (docs/serving.md)
    moved = [i for i in range(len(prompts)) if first[i] != alone[i]]
    # the fleet's view of the worker is its last health probe: wait for one
    # that has seen every request
    deadline = time.monotonic() + 30
    while True:
        stats = api.get("/admin/serve")["sessions"][job_id]
        if stats.get("requests_completed_total", 0) >= 3 * len(prompts):
            break
        check(time.monotonic() < deadline,
              f"the worker reports {stats.get('requests_completed_total')} "
              f"completed requests of {3 * len(prompts)}")
        time.sleep(0.5)
    check(stats.get("step_errors_total", 0) == 0,
          f"{stats.get('step_errors_total')} decode step errors")
    say("serve-generate", time.monotonic() - t1,
        requests=3 * len(prompts), prompt_lengths=[len(p) for p in prompts],
        tokens_generated=stats.get("tokens_generated_total"),
        first_s=round(first_s, 2), together_s=round(together_s, 2),
        alone_s=round(alone_s, 2), identical_alone_and_together=True,
        prompts_moved_by_prefix_reuse=moved,
        prefix_hits=stats.get("prefix_hits_total"),
        compilations=stats.get("compilations"))
    return device, session["worker_pids"]


def stop_server(proc: subprocess.Popen, run_id: str, log: Path) -> None:
    proc.terminate()
    try:
        proc.wait(timeout=60)
    except subprocess.TimeoutExpired:
        raise SmokeFailure(
            f"server ignored SIGTERM for 60 s:\n{tail(log)}") from None
    left = wait_gone(run_id, 30)
    check(not left, f"processes survived the server's shutdown: {left}")


def parity_child(run_id: str, cfg: dict, what: str, snippet: str, *args) -> dict:
    """Run a parity snippet in a child that takes the chip, and return the
    JSON record of its last line; its device must be the mode's."""
    out = subprocess.run(
        [sys.executable, "-c", snippet, *args],
        env=child_env(run_id, cfg["platform"]), cwd=str(REPO),
        capture_output=True, text=True, timeout=900,
    )
    check(out.returncode == 0,
          f"{what} parity child exited {out.returncode}:\n{out.stderr[-3000:]}")
    rec = json.loads(out.stdout.strip().splitlines()[-1])
    check(rec["device"]["platform"] == cfg["platform"],
          f"{what} parity ran on {rec['device']}")
    check(all(c["finite"] for c in rec["cases"]), f"non-finite output: {rec}")
    return rec


def paged_parity_phase(run_id: str, cfg: dict) -> dict:
    t0 = time.monotonic()
    dtype = "bfloat16"
    rec = parity_child(run_id, cfg, "paged", PAGED_PARITY_SNIPPET,
                       json.dumps(cfg["paged_shapes"]), dtype)
    worst = max(c["max_err"] for c in rec["cases"])
    check(worst <= PAGED_TOL[dtype],
          f"paged kernel off the gather path by {worst} > {PAGED_TOL[dtype]}:"
          f" {rec['cases']}")
    say("paged-parity", time.monotonic() - t0, compiled=rec["compiled"],
        dtype=dtype, tolerance=PAGED_TOL[dtype], worst_max_err=worst,
        max_err_by_shape={"x".join(map(str, c["shape"])): c["max_err"]
                          for c in rec["cases"]})
    return rec["device"]


def grouped_parity_phase(run_id: str, cfg: dict) -> dict:
    t0 = time.monotonic()
    rec = parity_child(run_id, cfg, "grouped", GROUPED_PARITY_SNIPPET,
                       json.dumps(cfg["grouped_shapes"]))
    worst = max(max(c["value_err"], c["grad_err"]) for c in rec["cases"])
    check(worst <= GROUPED_TOL,
          f"grouped product off ragged_dot by {worst} > {GROUPED_TOL} of the "
          f"largest magnitude: {rec['cases']}")
    say("grouped-parity", time.monotonic() - t0, compiled=rec["compiled"],
        tolerance=GROUPED_TOL, worst_err=worst,
        tiles_by_shape={"x".join(map(str, c["shape"])): c["tiles"]
                        for c in rec["cases"]},
        work_over_need={"x".join(map(str, c["shape"])) + ":" + c["sizes"]:
                        round(c["work_over_need"], 3) for c in rec["cases"]})
    return rec["device"]


def ssd_parity_phase(run_id: str, cfg: dict) -> dict:
    t0 = time.monotonic()
    rec = parity_child(run_id, cfg, "ssd", SSD_PARITY_SNIPPET,
                       json.dumps(cfg["ssd_shapes"]))
    worst = max(max(c["value_err"], c["grad_err"]) for c in rec["cases"])
    check(worst <= SSD_TOL,
          f"chunked scan off the token-by-token recurrence by {worst} > "
          f"{SSD_TOL} of the largest magnitude: {rec['cases']}")
    impls = {c["ssm_scan_impl"] for c in rec["cases"]}
    check(impls == ({"pallas"} if rec["compiled"] else {"xla"}),
          f"the chooser took {sorted(impls)} for {rec['cases']}")
    say("ssd-parity", time.monotonic() - t0, compiled=rec["compiled"],
        tolerance=SSD_TOL, worst_err=worst, ssm_scan_impl=impls.pop(),
        errs_by_shape={"x".join(map(str, c["shape"])):
                       {"value": c["value_err"], "grad": c["grad_err"],
                        "chunks": c["chunks"],
                        "heads_per_block": c["ssm_scan_heads_per_block"]}
                       for c in rec["cases"]})
    return rec["device"]


def window_parity_phase(run_id: str, cfg: dict) -> dict:
    t0 = time.monotonic()
    rec = parity_child(run_id, cfg, "window", WINDOW_PARITY_SNIPPET,
                       json.dumps(cfg["window_shapes"]))
    worst = max(max(c["errs"].values()) for c in rec["cases"])
    check(worst <= WINDOW_TOL,
          f"flash kernels off the XLA form by {worst} > {WINDOW_TOL} of the "
          f"largest magnitude: {rec['cases']}")
    say("window-parity", time.monotonic() - t0, compiled=rec["compiled"],
        tolerance=WINDOW_TOL, worst_err=worst,
        errs_by_shape={"x".join(map(str, c["shape"])): c["errs"]
                       for c in rec["cases"]},
        flash_window_work_over_need={
            "x".join(map(str, c["shape"])): round(c["work_over_need"], 4)
            for c in rec["cases"] if c["shape"][5]})
    return rec["device"]


def latent_share_parity_phase(run_id: str, cfg: dict) -> dict:
    t0 = time.monotonic()
    rec = parity_child(run_id, cfg, "latent share", LATENT_SHARE_PARITY_SNIPPET,
                       json.dumps(cfg["latent_shapes"]))
    worst = max(max(c["value_err"], c["grad_err"]) for c in rec["cases"])
    check(worst <= LATENT_TOL,
          f"held share's grouped path off the masked plain form by {worst} > "
          f"{LATENT_TOL} of the largest magnitude: {rec['cases']}")
    check(all(c["pairs"] == c["pairs_by_the_mask"] for c in rec["cases"]),
          f"the share computed other pairs than the mask holds: {rec['cases']}")
    say("latent-share-parity", time.monotonic() - t0, compiled=rec["compiled"],
        tolerance=LATENT_TOL, worst_err=worst,
        errs_by_shape={"x".join(map(str, c["shape"])):
                       {"value": c["value_err"], "grad": c["grad_err"],
                        "pairs": c["pairs"], "row_bound": c["row_bound"],
                        "pairs_over_bound": c["pairs_over_bound"]}
                       for c in rec["cases"]})
    return rec["device"]


def lora_parity_phase(run_id: str, cfg: dict) -> dict:
    t0 = time.monotonic()
    rec = parity_child(run_id, cfg, "lora", LORA_PARITY_SNIPPET,
                       json.dumps(cfg["lora_shapes"]))
    worst = max(max(c["value_err"], c["grad_err"]) for c in rec["cases"])
    check(worst <= LORA_TOL,
          f"joined LoRA product off the layer's old expression by {worst} > "
          f"{LORA_TOL} of the largest magnitude: {rec['cases']}")
    say("lora-parity", time.monotonic() - t0, compiled=rec["compiled"],
        tolerance=LORA_TOL, worst_err=worst,
        errs_by_shape={"x".join(map(str, c["shape"])):
                       {"value": c["value_err"], "grad": c["grad_err"]}
                       for c in rec["cases"]})
    return rec["device"]


def run_lifecycle(cfg: dict, work: Path, run_id: str, seed: int) -> dict:
    _, entries0 = cache_state()
    # first, while nothing holds the chip: a tile that faults ends the run
    # here, in a minute
    grouped_device = grouped_parity_phase(run_id, cfg)
    ssd_device = ssd_parity_phase(run_id, cfg)
    window_device = window_parity_phase(run_id, cfg)
    latent_device = latent_share_parity_phase(run_id, cfg)
    lora_device = lora_parity_phase(run_id, cfg)
    t0 = time.monotonic()
    server, api, log = start_server(work, run_id, cfg["platform"])
    say("server", time.monotonic() - t0, pid=server.pid, log=str(log))
    try:
        job_id, train_device = train_phase(api, cfg)
        check_server_off_jax(api, "train")
        # the trainer exited with its job; until the load below nothing
        # holds the chip
        promote_phase(api, job_id)
        check_server_off_jax(api, "promote")
        serve_device, worker_pids = serve_phase(api, job_id, cfg, seed)
        check_server_off_jax(api, "serve")
        t1 = time.monotonic()
        api.post(f"/admin/serve/{job_id}/unload", timeout=120)
        deadline = time.monotonic() + 60
        while any(Path(f"/proc/{pid}").exists() for pid in worker_pids):
            check(time.monotonic() < deadline,
                  f"serve worker {worker_pids} outlived its unload")
            time.sleep(0.2)
    except BaseException:
        # main() kills whatever is still running; say what the server saw
        print(f"--- server log tail ---\n{tail(log, 60)}", file=sys.stderr)
        raise
    stop_server(server, run_id, log)
    say("shutdown", time.monotonic() - t1, survivors=[])
    parity_device = paged_parity_phase(run_id, cfg)
    check(train_device == serve_device == parity_device == grouped_device
          == ssd_device == window_device == latent_device == lora_device,
          f"children disagree on the device: trainer {train_device}, "
          f"serve worker {serve_device}, parity children {parity_device}, "
          f"{grouped_device}, {ssd_device}, {latent_device}, {lora_device}")
    cache_dir, entries1 = cache_state()
    say("compile-cache", 0.0, dir=cache_dir,
        entries_before=entries0, entries_after=entries1)
    return train_device


# ---------------------------------------------------------------------------
# four chips: train.cli on mesh fsdp=4 against the same spec on one chip
# ---------------------------------------------------------------------------


def run_trainer(work: Path, run_id: str, cfg: dict, name: str, fsdp: int,
                extra_env: dict) -> dict:
    art = work / name
    spec = {
        "job_id": name,
        "model": {"preset": cfg["preset"],
                  "lora": {"rank": cfg["arguments"]["lora_rank"]}},
        "training": {"mode": "lora", **{
            k: v for k, v in cfg["arguments"].items() if k != "lora_rank"}},
        # fully specified: a mesh smaller than the host runs on a prefix of
        # its devices (parallel/mesh.py), which is how one process holding
        # four chips trains on one
        "mesh": {"dp": 1, "fsdp": fsdp, "ep": 1, "pp": 1, "sp": 1, "tp": 1},
        "dataset": {"synthetic": {"task": "increment"}},
        "artifacts_dir": str(art),
    }
    art.mkdir(parents=True)
    (work / f"{name}.json").write_text(json.dumps(spec, indent=1))
    t0 = time.monotonic()
    log = work / f"{name}.log"
    with open(log, "wb") as out:
        rc = subprocess.run(
            [sys.executable, "-m", "finetune_controller_tpu.train.cli",
             "--spec", str(work / f"{name}.json")],
            cwd=str(work), env=child_env(run_id, cfg["platform"], extra_env),
            stdout=out, stderr=subprocess.STDOUT, timeout=1500,
        ).returncode
    check(rc == 0, f"train.cli ({name}) exited {rc}:\n{tail(log, 60)}")
    seconds = time.monotonic() - t0
    with open(art / "metrics.csv") as f:
        rows = sorted(csv.DictReader(f), key=lambda r: int(float(r["step"])))
    events = {}
    for line in (art / "events.jsonl").read_text().splitlines():
        e = json.loads(line)
        events[e["event"]] = e["attrs"]
    check((art / "done.txt").exists(), f"{name}: no done.txt")
    summary = training_summary(
        cfg, name, events["train-started"], events["train-finished"], rows)
    say(f"train-{name}", seconds, **summary)
    return summary


def run_four_chips(cfg: dict, work: Path, run_id: str) -> dict:
    extra = {}
    if cfg["platform"] == "cpu":  # rehearsal: four virtual devices
        extra["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    four = run_trainer(work, run_id, cfg, "fsdp4", 4, extra)
    one = run_trainer(work, run_id, cfg, "fsdp1", 1, extra)
    check(four["device"] == one["device"] and four["device"]["count"] == 4,
          f"expected four devices in both runs: {four['device']}, "
          f"{one['device']}")
    check(four["mesh"] == {"fsdp": 4} and one["mesh"] == {},
          f"meshes {four['mesh']} / {one['mesh']}")
    diffs = [abs(a - b) for a, b in zip(four["losses"], one["losses"])]
    check(max(diffs) <= FOUR_CHIP_LOSS_TOL,
          f"fsdp=4 and one-chip losses differ by {max(diffs)} > "
          f"{FOUR_CHIP_LOSS_TOL}: {four['losses']} vs {one['losses']}")
    split = None
    if cfg["platform"] == "tpu":
        # the frozen base dominates the state: sharded four ways, every
        # device holds about a quarter of what the one-chip run holds on
        # its device; copied, each would hold all of it
        per_dev, whole = four["state_bytes"], one["state_bytes"][0]
        check(len(per_dev) == 4, f"state bytes of {len(per_dev)} devices")
        split = [round(b / whole, 3) for b in per_dev]
        check(max(split) <= 0.35,
              f"state is not split four ways: per-device share {split}")
    say("four-chip-compare", 0.0, max_loss_diff=max(diffs),
        tolerance=FOUR_CHIP_LOSS_TOL, loss_diffs=[round(d, 5) for d in diffs],
        state_share_per_device=split if split else "not measured off the chip")
    return four["device"]


# ---------------------------------------------------------------------------


def main(argv: list[str] | None = None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--chips", type=int, choices=(1, 4), default=1,
                   help="4 = only the fsdp=4 training path and the one-chip "
                        "run it is compared with")
    p.add_argument("--tiny", action="store_true",
                   help="CPU rehearsal with tiny-test; never a chip pass")
    p.add_argument("--seed", type=int, default=0,
                   help="seeds the weights, the synthetic data and the prompts")
    args = p.parse_args(argv)
    check((REPO / "finetune_controller_tpu").is_dir(),
          f"{REPO} holds no finetune_controller_tpu package")

    cfg = mode_config(args.tiny, args.seed)
    run_id = uuid.uuid4().hex
    work = Path(tempfile.mkdtemp(prefix="chip_smoke_"))
    t0 = time.monotonic()
    try:
        if args.chips == 4:
            device = run_four_chips(cfg, work, run_id)
        else:
            device = run_lifecycle(cfg, work, run_id, args.seed)
        check(not marked_pids(run_id), "a child process is still alive")
        check("jax" not in sys.modules, "the parent imported jax")
    except BaseException:
        # the server log and the sandboxes are what a failure is debugged from
        print(f"work directory kept: {work}", file=sys.stderr)
        raise
    else:
        shutil.rmtree(work, ignore_errors=True)
    finally:
        kill_marked(run_id)  # nothing outlives the script, pass or fail
    say("total", time.monotonic() - t0, chips=args.chips)
    if args.tiny:
        # a rehearsal has no "ok" key: nothing here can be read as a chip pass
        print(json.dumps({"rehearsal": "tiny", "passed": True,
                          "device": device}))
        return 0
    check(device["platform"] == "tpu"
          and (args.chips == 1 or device["count"] == 4),
          f"expected {args.chips} TPU chip(s), the children saw {device}")
    print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    try:
        sys.exit(main())
    except SmokeFailure as failure:
        print(f"FAILED: {failure}", file=sys.stderr)
        sys.exit(1)
