"""Blockwise int4 weight quantization — the QLoRA base-weight path.

BASELINE config #3 (Mistral-7B QLoRA). TPU-first design choices:

- **symmetric blockwise int4**: each ``block_size`` input-dim slice of a
  kernel column shares one bf16 scale; values live in [-7, 7] so the scale is
  ``absmax / 7`` and zero is exact (no zero-point tensor);
- **two nibbles per uint8** along the input dim — a quantized ``(in, out)``
  kernel is ``(in/2, out)`` uint8 + ``(in/block, out)`` scales: ~4.25
  bits/weight, which is what lets a 7B base fit one v5e chip's HBM next to
  optimizer-free LoRA adapters;
- **dequantize-then-matmul** at apply time, and what the chip's compiler
  makes of it (``tests/test_chip_compile.py`` holds it to this): the packed
  bytes are doubled along the input dim as bytes (the only relayout, at one
  byte an element), one elementwise pass takes each row's nibble,
  sign-extends it and multiplies by its block's scale, and the kernel is
  written once, as bf16, for the bf16 MXU matmul to read.  Nothing of the
  kernel's size exists in f32 and the scales are broadcast inside that pass,
  never to an array of their own (what each step costs on the chip: PERF.md
  section 5).

Gradients: the base kernel is intentionally non-differentiable (it lives in
``params``, the frozen collection — only the ``lora`` collection trains), so
no straight-through estimator is needed.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def quantize_int4(w: jax.Array, block_size: int = 64) -> tuple[jax.Array, jax.Array]:
    """(in, out) float → (packed (in/2, out) uint8, scales (in/block, out) bf16).

    ``in`` must divide by ``block_size`` and ``block_size`` must be even.
    """
    in_f, out_f = w.shape
    if in_f % block_size or block_size % 2:
        raise ValueError(f"in={in_f} must divide by even block_size={block_size}")
    wb = w.astype(jnp.float32).reshape(in_f // block_size, block_size, out_f)
    absmax = jnp.max(jnp.abs(wb), axis=1, keepdims=True)          # (nb, 1, out)
    # round the scale to its stored precision BEFORE quantizing, so the
    # round-trip error stays <= scale/2 per element
    scales = (absmax / 7.0).astype(jnp.bfloat16).astype(jnp.float32)
    q = jnp.clip(jnp.round(wb / jnp.maximum(scales, 1e-12)), -7, 7).astype(jnp.int8)
    q = q.reshape(in_f, out_f)
    # pack consecutive input-dim pairs: low nibble = even row, high = odd row
    lo = (q[0::2] & 0x0F).astype(jnp.uint8)
    hi = (q[1::2] & 0x0F).astype(jnp.uint8)
    packed = (lo | (hi << 4)).astype(jnp.uint8)                   # (in/2, out)
    return packed, scales.reshape(in_f // block_size, out_f).astype(jnp.bfloat16)


def dequantize_int4(
    packed: jax.Array, scales: jax.Array, *, dtype=jnp.bfloat16
) -> jax.Array:
    """Inverse of :func:`quantize_int4` → (in, out) in ``dtype``.

    A nibble in -7..7 times a bf16 scale is exact in f32, so the value is that
    product rounded once to ``dtype``: a bf16 multiply gives exactly that (the
    v5e's VPU and the CPU backend both multiply in f32 and round), and any
    other ``dtype`` is computed in f32.
    """
    half, out_f = packed.shape
    in_f = half * 2
    n_blocks = scales.shape[0]
    block_size = in_f // n_blocks
    compute = jnp.bfloat16 if dtype == jnp.bfloat16 else jnp.float32
    # the scope names this work in a profiler trace whoever calls it
    # (LoRADense, MoE experts, the serve engine): docs/observability.md
    with jax.named_scope("dequant_int4"):
        # interleave while the data is narrowest: every packed byte twice,
        # row 2h takes its low nibble and row 2h+1 its high one (a block is
        # even, so a row's parity is its parity inside the block)
        rows = jnp.repeat(packed, 2, axis=0).reshape(n_blocks, block_size, out_f)
        odd = jax.lax.broadcasted_iota(jnp.uint8, (1, block_size, 1), 1) & 1
        q = ((rows >> (odd * 4)) & 0x0F).astype(jnp.int8)
        q = jnp.where(q > 7, q - 16, q)           # 4-bit two's complement
        w = q.astype(compute) * scales[:, None, :].astype(compute)
        # the barrier keeps the pass above one fusion that writes the kernel
        # once in ``dtype``; without it XLA moves the multiply into the
        # matmul's operand and broadcasts the scales to a kernel-sized array
        # of their own for it to read
        w = jax.lax.optimization_barrier(w.astype(dtype))
        return w.reshape(in_f, out_f)


def quantized_param(module, name: str, shape: tuple, kernel_init,
                    quant_block: int, dtype) -> jax.Array:
    """The quantize-one-draw-at-init param pattern, shared by ``LoRADense``
    (dense ``kernel``) and ``MoEMLP`` (stacked ``experts_*``): quantize ONE
    weight draw for both stored params — flax folds the param name into the
    rng, so separate init fns would quantize two different matrices and
    store mismatched values/scales. Leading axes (the expert axis) are
    vmapped. Returns the dequantized kernel in ``dtype``.
    """
    per_matrix = len(shape) == 2

    packed0 = scales0 = None
    if module.is_initializing():
        w0 = kernel_init(module.make_rng("params"), shape, jnp.float32)
        if per_matrix:
            packed0, scales0 = quantize_int4(w0, quant_block)
        else:
            packed0, scales0 = jax.vmap(
                lambda w: quantize_int4(w, quant_block)
            )(w0)
    packed = module.param(f"{name}_packed", lambda _rng: packed0)
    scales = module.param(f"{name}_scales", lambda _rng: scales0)
    if per_matrix:
        return dequantize_int4(packed, scales, dtype=dtype)
    return jax.vmap(lambda p, s: dequantize_int4(p, s, dtype=dtype))(
        packed, scales
    )
