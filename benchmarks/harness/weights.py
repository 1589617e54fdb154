"""Seeded weights, made by the benchmark and handed to both sides.

Every array is a pure function of ``(seed, canonical leaf name, layer,
element index)``: a counter-based integer hash (two rounds of the murmur3
finaliser over the element's linear index), in ``uint32`` arithmetic that is
exact everywhere.  So the program's variable tree is filled in ONE jitted
call on the device, in the types it is served in, sharded or not, and the
plain reference regenerates any single layer by itself — bit for bit the
same, whatever the surrounding program, without ever taking an array from
the program.  (JAX's threefry generator took ~100 s for a 7 B model's worth
of bytes on a v5e chip — my chip run 1, PR 23 — and its hardware generator
is not the same function under ``vmap``; this hash is a few integer
operations an element.)

Canonical names are the benchmark's own (``blocks/attn/q_proj/kernel_packed``,
``embed_tokens/embedding`` ...); ``program.py`` maps the program's tree paths
onto them.  What is drawn follows the leaf's last path component:

* ``*_packed``  uint8, two int4 nibbles per byte, each uniform over the
  format's range [-7, 7] — MEAN ZERO.  (Uniform bytes, nibbles -8..7, have
  mean -0.5: every kernel then carries a rank-one common-mode component
  whose gain grows with sqrt(width), 3.5 at 4096, and gradients explode
  ~10x a layer backwards until bf16 rounding drowns them — cosine 0.0
  against the float32 reference in layer 0 of 4, my chip runs 2-3, PR 23;
  a property of those weights, not of the program.)
* ``*_scales``  bf16, one per 64 input rows and output column:
  ``(1/sqrt(fan_in)) / 4.32`` (4.32 = the standard deviation of a uniform
  nibble) times uniform[0.75, 1.25], so a dequantised kernel has the
  variance of a lecun-normal one;
* ``kernel``    bell / sqrt(fan_in); ``embedding`` bell of unit variance;
* the projections that write to the residual stream (``o_proj``,
  ``down_proj``) are drawn 8 times smaller, the 1/sqrt(2 x layers) of
  GPT-2's initialisation for ~32 layers: with unit embeddings the stream
  starts at unit size and grows slowly, as in a trained model;
* ``scale``     (RMSNorm) 1 + 0.1 bell; ``bias`` 0.1 bell;
* ``lora_a``, ``lora_b`` 0.02 bell — BOTH non-zero, a job in mid-training:
  with the zero-initialised ``lora_b`` of a fresh job the adapter branch
  contributes nothing to the forward pass and ``lora_a`` has no gradient,
  so neither could be checked.

``bell`` is the sum of a word's four bytes, centred and scaled to unit
variance (Irwin-Hall of order 4: bell-shaped, bounded at 3.45 sigma).
"""

from __future__ import annotations

import zlib

import jax
import jax.numpy as jnp

QUANT_NIBBLE_STD = 4.32  # std of a uniform draw from {-7..7}
_BELL_STD = 147.8       # std of the sum of four uniform bytes
_U32 = jnp.uint32


def _mix(x):
    """murmur3's 32-bit finaliser: every input bit reaches every output bit."""
    x = x ^ (x >> 16)
    x = x * _U32(0x85EBCA6B)
    x = x ^ (x >> 13)
    x = x * _U32(0xC2B2AE35)
    return x ^ (x >> 16)


def root_key(seed: int) -> jax.Array:
    """A key (two uint32 words) from any whole number — the driver's seeds
    pass 2**31."""
    seed = int(seed)
    return jnp.asarray([seed & 0xFFFFFFFF, (seed >> 32) & 0xFFFFFFFF], _U32)


def _leaf_key(key, name: str, layer):
    """One uint32 per (seed, leaf, layer); ``layer`` may be traced."""
    k = _mix(key[0] ^ _U32(zlib.crc32(name.encode())))
    k = _mix(k + key[1] * _U32(0x9E3779B1))
    return _mix(k + jnp.asarray(layer).astype(_U32) * _U32(0x85EBCA77) + _U32(1))


def _words(k, shape: tuple):
    """One hashed uint32 per element of ``shape``."""
    idx = jnp.zeros(shape, _U32)
    stride = 1
    for axis in reversed(range(len(shape))):
        idx = idx + jax.lax.broadcasted_iota(_U32, shape, axis) * _U32(
            stride & 0xFFFFFFFF)
        stride *= shape[axis]
    return _mix(_mix(idx * _U32(0x9E3779B1) + k) + k)


def _bell(k, shape):
    w = _words(k, shape)
    s = (w & 0xFF) + ((w >> 8) & 0xFF) + ((w >> 16) & 0xFF) + (w >> 24)
    return (s.astype(jnp.float32) - 510.0) * (1.0 / _BELL_STD)


def _uniform(k, shape, lo: float, hi: float):
    u = (_words(k, shape) >> 8).astype(jnp.float32) * (1.0 / (1 << 24))
    return lo + (hi - lo) * u


#: first component of the canonical name of a leaf that carries the layer
#: axis first (the program's scanned stack); every other leaf is one array
STACKED = "blocks"


def is_stacked(name: str) -> bool:
    return name.split("/")[0] == STACKED


RESIDUAL_WRITERS = ("o_proj", "down_proj")
RESIDUAL_SCALE = 0.125


def _draw(k, name: str, shape: tuple, dtype, quant_block: int):
    kind = name.rsplit("/", 1)[-1]
    dtype = jnp.dtype(dtype)
    out_scale = RESIDUAL_SCALE if any(
        f"/{p}/" in name for p in RESIDUAL_WRITERS) else 1.0
    if kind.endswith("_packed"):
        w = _words(k, shape)
        lo = ((w & 0xFFFF) % 15 + _U32(9)) & 0xF     # (v - 7) mod 16, v in 0..14
        hi = ((w >> 16) % 15 + _U32(9)) & 0xF
        return (lo | (hi << 4)).astype(jnp.uint8)
    if kind.endswith("_scales"):
        fan_in = shape[-2] * quant_block
        base = out_scale * (fan_in ** -0.5) / QUANT_NIBBLE_STD
        return (base * _uniform(k, shape, 0.75, 1.25)).astype(dtype)
    if kind == "kernel":
        return (_bell(k, shape) * (out_scale * shape[-2] ** -0.5)).astype(dtype)
    if kind == "embedding":
        return _bell(k, shape).astype(dtype)
    if kind == "scale":
        return (1.0 + 0.1 * _bell(k, shape)).astype(dtype)
    if kind == "bias":
        return (0.1 * _bell(k, shape)).astype(dtype)
    if kind in ("lora_a", "lora_b"):
        return (0.02 * _bell(k, shape)).astype(dtype)
    raise ValueError(f"no rule to draw weights for leaf {name!r}")


def layer_leaf(key, name: str, layer, shape: tuple, dtype,
               quant_block: int = 64):
    """One layer's array of a per-layer leaf (``shape`` without the layer
    axis).  ``layer`` may be traced."""
    return _draw(_leaf_key(key, name, layer), name, tuple(shape), dtype,
                 quant_block)


def leaf(key, name: str, shape: tuple, dtype, *, stacked: bool,
         quant_block: int = 64):
    """A whole leaf; ``stacked`` leaves carry the layer axis first and are
    the per-layer draws stacked, so ``leaf(...)[l] == layer_leaf(..., l)``."""
    if not stacked:
        return layer_leaf(key, name, 0, shape, dtype, quant_block)
    return jax.vmap(
        lambda l: layer_leaf(key, name, l, shape[1:], dtype, quant_block)
    )(jnp.arange(shape[0]))
