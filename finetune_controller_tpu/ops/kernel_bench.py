"""Attention kernel micro-benchmark: XLA einsum-softmax vs Pallas flash.

SURVEY.md §7 discipline — "benchmark first, hand-write second": the Pallas
kernel is only used where it measurably beats XLA's fused default. This
module provides the measurement (fwd and fwd+bwd wall time per call at a
given shape) and the dispatch gate (:func:`preferred_impl`) the model config
consults when ``attention_impl="auto"``.

Run on hardware:
    python -m finetune_controller_tpu.ops.kernel_bench [--seq 2048 ...]
"""

from __future__ import annotations

import functools
import time

import jax
import jax.numpy as jnp


def _time_chained(fn, q, k, v, chain, iters: int, warmup: int = 2) -> float:
    """Average per-call seconds with a host-level data-dependency chain:
    each call's output becomes the next call's query input (``chain`` maps the
    output to a q-shaped array). Independent repeated calls through an async
    runtime can overlap and appear cheaper than they are; a chain forces
    every execution onto the critical path, exactly like a training loop's
    donated state does."""
    def force(x):
        # a host fetch of a dependent scalar: the window ends only when the
        # whole chain has actually run
        return float(jnp.sum(x.astype(jnp.float32)))

    for _ in range(warmup):
        out = fn(q, k, v)
        q = chain(out, q)
    force(q)
    t0 = time.perf_counter()
    for _ in range(iters):
        out = fn(q, k, v)
        q = chain(out, q)
    force(q)
    return (time.perf_counter() - t0) / iters


def _make_qkv(batch, seq, heads, kv_heads, head_dim, dtype):
    kq, kk, kv = jax.random.split(jax.random.PRNGKey(0), 3)
    return (
        jax.random.normal(kq, (batch, seq, heads, head_dim), dtype),
        jax.random.normal(kk, (batch, seq, kv_heads, head_dim), dtype),
        jax.random.normal(kv, (batch, seq, kv_heads, head_dim), dtype),
    )


def _chain_grad(grads, q_prev):
    """Fold dQ back into the next call's q, keeping magnitudes bounded so
    the chain can run indefinitely without overflowing."""
    return q_prev + grads[0].astype(q_prev.dtype) * 1e-3


def bench_attention(
    batch: int = 8,
    seq: int = 2048,
    heads: int = 32,
    kv_heads: int = 4,
    head_dim: int = 64,
    dtype=jnp.bfloat16,
    iters: int = 10,
) -> dict[str, float]:
    """Per-call seconds for each impl, forward and grad (fwd+bwd)."""
    from .attention import xla_causal_attention
    from .pallas.flash_attention import flash_attention

    q, k, v = _make_qkv(batch, seq, heads, kv_heads, head_dim, dtype)

    def loss(attn, q, k, v):
        return (attn(q, k, v).astype(jnp.float32) ** 2).mean()

    def chain_fwd(out, q_prev):
        return out

    results: dict[str, float] = {}
    for name, attn in (("xla", xla_causal_attention), ("pallas", flash_attention)):
        # ftc: ignore[recompile-jit-in-loop] -- one compile per impl IS the benchmark; each (impl, shape) runs once per process
        fwd = jax.jit(functools.partial(attn))
        # ftc: ignore[recompile-jit-in-loop] -- same: the grad path compiles once per benched impl by design
        grad = jax.jit(jax.grad(functools.partial(loss, attn), argnums=(0, 1, 2)))
        results[f"{name}_fwd_s"] = _time_chained(fwd, q, k, v, chain_fwd, iters)
        results[f"{name}_grad_s"] = _time_chained(grad, q, k, v, _chain_grad, iters)
    return results


def bench_flash_variants(
    batch: int = 2,
    seq: int = 8192,
    heads: int = 32,
    kv_heads: int = 4,
    head_dim: int = 64,
    dtype=jnp.bfloat16,
    iters: int = 8,
    exp_dtypes: tuple[str, ...] = ("float32", "bfloat16"),
    blocks: tuple[int, ...] = (512, 1024),
) -> dict[str, float]:
    """Grad-path seconds per (exp_dtype, block) flash-kernel variant.

    The long-context tuning sweep (``docs/performance.md`` knob table):
    at head-dim 64 the kernels are VPU-bound on the S² exp, so the exp
    dtype and block size are the two dials worth measuring. Keys are
    ``"{exp_dtype}-b{block}"``; run it on the chip and apply the winner via
    the ``FTC_FLASH_*`` env knobs.
    """
    from .pallas.flash_attention import flash_attention

    q, k, v = _make_qkv(batch, seq, heads, kv_heads, head_dim, dtype)

    results: dict[str, float] = {}
    for edt in exp_dtypes:
        for blk in blocks:
            def loss(q, k, v, edt=edt, blk=blk):
                o = flash_attention(
                    q, k, v, block_q=blk, block_k=blk, exp_dtype=edt)
                return (o.astype(jnp.float32) ** 2).mean()

            # ftc: ignore[recompile-jit-in-loop] -- the sweep measures one compile per (exp_dtype, block) variant on purpose
            grad = jax.jit(jax.grad(loss, argnums=(0, 1, 2)))
            results[f"{edt}-b{blk}"] = _time_chained(
                grad, q, k, v, _chain_grad, iters)
    return results


def bench_paged_variants(
    batch: int = 8,
    heads: int = 32,
    kv_heads: int = 4,
    head_dim: int = 64,
    page_tokens: int = 16,
    pages_per_lane: tuple[int, ...] = (16, 64, 256),
    dtype=jnp.bfloat16,
    iters: int = 20,
) -> dict[str, float]:
    """Decode-step seconds for the paged-attention impls, gather vs kernel,
    swept over pages-per-lane (i.e. context length at fixed page size).

    The gather tax this measures: the gather path materialises a
    ``(B, MP*T, Hkv, D)`` logical cache from HBM every step, so its cost
    scales with MP even when most pages are beyond the lane's live length;
    the Pallas kernel (``ops/pallas/paged_attention.py``) reads each page
    once into VMEM scratch.  Keys are ``"{impl}-p{pages}"``; run on real
    hardware to pick ``FTC_PAGED_ATTN`` (``docs/performance.md``).
    """
    from .attention import chunked_cache_attention, paged_gather
    from .pallas.paged_attention import paged_attention

    results: dict[str, float] = {}
    for mp in pages_per_lane:
        pool_pages = batch * mp + 1
        kq, kk, kv = jax.random.split(jax.random.PRNGKey(1), 3)
        q = jax.random.normal(
            kq, (batch, 1, heads, head_dim), dtype)
        k_pool = jax.random.normal(
            kk, (pool_pages, page_tokens, kv_heads, head_dim), dtype)
        v_pool = jax.random.normal(
            kv, (pool_pages, page_tokens, kv_heads, head_dim), dtype)
        # each lane owns a disjoint page run, like a fragmented real pool
        table = (1 + jnp.arange(batch * mp, dtype=jnp.int32)
                 ).reshape(batch, mp)
        idx = jnp.full((batch,), mp * page_tokens - 1, jnp.int32)

        def gather_step(q, k, v, table=table, idx=idx):
            return chunked_cache_attention(
                q, paged_gather(k, table), paged_gather(v, table), idx)

        def kernel_step(q, k, v, table=table, idx=idx):
            return paged_attention(q, k, v, table, idx)

        def chain(out, q_prev):
            return q_prev + out.astype(q_prev.dtype) * 1e-3

        for name, step in (("gather", gather_step), ("kernel", kernel_step)):
            # ftc: ignore[recompile-jit-in-loop] -- the sweep measures one compile per (impl, pages) variant on purpose
            fn = jax.jit(step)
            results[f"{name}-p{mp}"] = _time_chained(
                fn, q, k_pool, v_pool, chain, iters)
    return results


#: measured crossover (v5e, 2026-07-31 run of this module at the bench shape
#: b8 h32/4 d64, with the r3 kernel defaults — block 1024, bf16 exp):
#: seq 512 XLA wins the grad path (8.7 ms vs 11.4); seq 1024 Pallas wins
#: (11.1 ms vs 15.1) and the S² HBM gap only widens with length (seq 2048:
#: 21.8 ms vs 37.2). The faster r3 defaults moved the crossover down from
#: the 2026-07 block-512 measurement (then 2048). The gate stays at the
#: shortest length with direct evidence of a Pallas win.
PALLAS_MIN_SEQ = 1024


def preferred_impl(seq_len: int, backend: str | None = None) -> str:
    """Dispatch gate for ``attention_impl="auto"``."""
    backend = backend or jax.default_backend()
    if backend == "tpu" and seq_len >= PALLAS_MIN_SEQ:
        return "pallas"
    return "xla"


def main() -> None:
    import argparse
    import json

    from ..platform import device_report, enable_compile_cache

    enable_compile_cache()
    device = device_report()
    if device["platform"] != "tpu":
        # off the chip the kernels run in the Pallas interpreter: timing
        # them says nothing about either implementation
        raise SystemExit(
            f"kernel_bench measures TPU kernels; this process runs on {device}"
        )

    p = argparse.ArgumentParser(prog="ftc-kernel-bench")
    p.add_argument("--batch", type=int, default=8)
    p.add_argument("--seq", type=int, nargs="*", default=[512, 1024, 2048, 4096])
    p.add_argument("--heads", type=int, default=32)
    p.add_argument("--kv-heads", type=int, default=4)
    p.add_argument("--head-dim", type=int, default=64)
    p.add_argument("--iters", type=int, default=10)
    p.add_argument("--flash-variants", action="store_true",
                   help="sweep the flash kernel's exp-dtype x block-size "
                        "grid instead of the impl comparison")
    p.add_argument("--paged-variants", action="store_true",
                   help="decode-step sweep of the paged-attention impls "
                        "(gather vs Pallas kernel) over pages-per-lane")
    p.add_argument("--page-tokens", type=int, default=16)
    p.add_argument("--pages-per-lane", type=int, nargs="*",
                   default=[16, 64, 256])
    args = p.parse_args()

    if args.paged_variants:
        r = bench_paged_variants(
            batch=args.batch, heads=args.heads, kv_heads=args.kv_heads,
            head_dim=args.head_dim, page_tokens=args.page_tokens,
            pages_per_lane=tuple(args.pages_per_lane), iters=args.iters,
        )
        r_ms = {k: round(v * 1e3, 3) for k, v in r.items()}
        print(json.dumps({
            "shape": f"b{args.batch} h{args.heads}/{args.kv_heads} "
                     f"d{args.head_dim} t{args.page_tokens}",
            "unit": "ms/decode-step",
            **r_ms,
        }))
        return

    if args.flash_variants:
        for seq in args.seq:
            r = bench_flash_variants(
                batch=args.batch, seq=seq, heads=args.heads,
                kv_heads=args.kv_heads, head_dim=args.head_dim,
                iters=args.iters,
            )
            r_ms = {k: round(v * 1e3, 3) for k, v in r.items()}
            print(json.dumps({
                "shape": f"b{args.batch} s{seq} h{args.heads}/"
                         f"{args.kv_heads} d{args.head_dim}",
                "unit": "ms/call (grad)",
                **r_ms,
                "winner": min(r_ms, key=r_ms.get),
            }))
        return

    for seq in args.seq:
        r = bench_attention(
            batch=args.batch, seq=seq, heads=args.heads,
            kv_heads=args.kv_heads, head_dim=args.head_dim, iters=args.iters,
        )
        r = {k: round(v * 1e3, 3) for k, v in r.items()}  # ms
        print(json.dumps({
            "shape": f"b{args.batch} s{seq} h{args.heads}/{args.kv_heads} d{args.head_dim}",
            "unit": "ms/call",
            **r,
            "winner_grad": "pallas" if r["pallas_grad_s"] < r["xla_grad_s"] else "xla",
        }))


if __name__ == "__main__":
    raise SystemExit(main())
