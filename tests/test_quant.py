"""The values of int4 dequantisation (ISSUE 25, models/quant.py).

PR 25 changed HOW ``dequantize_int4`` computes — the interleave on packed
bytes, the multiply in the output's precision — and nothing of WHAT: every
case here holds it, bit for bit, to a frozen copy of the implementation it
replaced.  What the chip's compiler makes of it is held in
``tests/test_chip_compile.py``.
"""

from __future__ import annotations

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from finetune_controller_tpu.models.lora import LoRADense
from finetune_controller_tpu.models.quant import (
    dequantize_int4,
    quantize_int4,
    quantized_param,
)


def frozen_dequantize_int4(packed, scales, *, dtype=jnp.bfloat16):
    """``dequantize_int4`` as it was before PR 25: unpack, interleave, the
    product with the block scales in f32, one rounding to ``dtype``."""
    half, out_f = packed.shape
    in_f = half * 2
    n_blocks = scales.shape[0]
    block_size = in_f // n_blocks
    lo = (packed & 0x0F).astype(jnp.int8)
    hi = (packed >> 4).astype(jnp.int8)
    lo = jnp.where(lo > 7, lo - 16, lo)
    hi = jnp.where(hi > 7, hi - 16, hi)
    q = jnp.stack([lo, hi], axis=1).reshape(in_f, out_f)
    qb = q.reshape(n_blocks, block_size, out_f).astype(jnp.float32)
    w = qb * scales[:, None, :].astype(jnp.float32)
    return w.reshape(in_f, out_f).astype(dtype)


def random_quantised(seed: int, in_f: int, out_f: int, block: int):
    """Every byte value and scales over six binades, not the narrow range a
    quantised normal draw would give."""
    kp, ks, ke = jax.random.split(jax.random.PRNGKey(seed), 3)
    packed = jax.random.randint(kp, (in_f // 2, out_f), 0, 256).astype(jnp.uint8)
    scales = (jax.random.normal(ks, (in_f // block, out_f))
              * 2.0 ** jax.random.randint(ke, (in_f // block, out_f), -9, -3))
    return packed, scales.astype(jnp.bfloat16)


def bits(a):
    a = np.asarray(a)
    return a.view({2: np.uint16, 4: np.uint32}[a.dtype.itemsize])


SHAPES = {"square": (128, 128), "tall": (384, 64), "wide": (64, 320)}


@pytest.mark.parametrize("jitted", [False, True], ids=["eager", "jit"])
@pytest.mark.parametrize("block", [32, 64])
@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32, jnp.float16],
                         ids=["bf16", "f32", "f16"])
@pytest.mark.parametrize("shape", list(SHAPES), ids=list(SHAPES))
def test_dequantize_is_bit_equal_to_the_frozen_copy(shape, dtype, block, jitted):
    in_f, out_f = SHAPES[shape]
    packed, scales = random_quantised(7, in_f, out_f, block)
    fn = lambda p, s: dequantize_int4(p, s, dtype=dtype)  # noqa: E731
    got = (jax.jit(fn) if jitted else fn)(packed, scales)
    want = frozen_dequantize_int4(packed, scales, dtype=dtype)
    assert got.shape == (in_f, out_f) and got.dtype == dtype
    np.testing.assert_array_equal(bits(got), bits(want))


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32], ids=["bf16", "f32"])
def test_float32_is_the_exact_product_and_bf16_its_one_rounding(dtype):
    """``hf_export`` asks for f32 and must get nibble x scale unrounded."""
    packed, scales = random_quantised(3, 128, 64, 64)
    lo = (np.asarray(packed) & 0x0F).astype(np.int8)
    hi = (np.asarray(packed) >> 4).astype(np.int8)
    q = np.stack([np.where(lo > 7, lo - 16, lo), np.where(hi > 7, hi - 16, hi)],
                 axis=1).reshape(128, 64).astype(np.float64)
    exact = q * np.repeat(np.asarray(scales, np.float64), 64, axis=0)
    got = dequantize_int4(packed, scales, dtype=dtype)
    np.testing.assert_array_equal(
        np.asarray(got, np.float64),
        np.asarray(jnp.asarray(exact, jnp.float32).astype(dtype), np.float64))


@pytest.mark.parametrize("block", [32, 64])
def test_round_trip_error_is_within_half_a_scale(block):
    w = jax.random.normal(jax.random.PRNGKey(0), (256, 96)) * 0.05
    packed, scales = quantize_int4(w, block)
    back = dequantize_int4(packed, scales, dtype=jnp.float32)
    bound = np.repeat(np.asarray(scales, np.float32), block, axis=0) / 2
    assert np.all(np.abs(np.asarray(back) - np.asarray(w)) <= bound * 1.0001)


class _Experts(nn.Module):
    """A stacked quantised kernel the way ``MoEMLP`` declares its experts."""

    shape: tuple
    block: int
    dtype: object

    @nn.compact
    def __call__(self):
        return quantized_param(self, "experts_up", self.shape,
                               nn.initializers.lecun_normal(), self.block,
                               self.dtype)


@pytest.mark.parametrize("block", [32, 64])
@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32], ids=["bf16", "f32"])
def test_stacked_experts_dequantise_as_each_matrix_alone(dtype, block):
    """``quantized_param`` vmaps over the expert axis: every expert's kernel
    is the frozen copy's, bit for bit."""
    mod = _Experts((3, 128, 64), block, dtype)
    variables = mod.init(jax.random.PRNGKey(1))
    packed = variables["params"]["experts_up_packed"]
    scales = variables["params"]["experts_up_scales"]
    assert packed.shape == (3, 64, 64) and scales.shape == (3, 128 // block, 64)
    got = jax.jit(mod.apply)(variables)
    assert got.shape == (3, 128, 64) and got.dtype == dtype
    for e in range(3):
        np.testing.assert_array_equal(
            bits(got[e]),
            bits(frozen_dequantize_int4(packed[e], scales[e], dtype=dtype)))


def _quantised_dense(in_f=128, out_f=96, dtype=jnp.bfloat16, rank=4):
    layer = LoRADense(features=out_f, lora_rank=rank, quantize_base=True,
                      dtype=dtype)
    variables = layer.init(jax.random.PRNGKey(2), jnp.zeros((1, 1, in_f), dtype))
    # B starts at zero: give the branch something to say
    lora = dict(variables["lora"])
    lora["lora_b"] = jax.random.normal(
        jax.random.PRNGKey(3), lora["lora_b"].shape) * 0.05
    return layer, {"params": variables["params"], "lora": lora}


def _reference_dense(variables, x, alpha=16.0):
    """``LoRADense`` with a quantised base, in f32 from the stored bytes."""
    p, lora = variables["params"], variables["lora"]
    kernel = frozen_dequantize_int4(
        p["kernel_packed"], p["kernel_scales"], dtype=jnp.float32)
    x = x.astype(jnp.float32)
    scale = alpha / lora["lora_a"].shape[1]
    hi = jax.lax.Precision.HIGHEST
    return (jnp.matmul(x, kernel, precision=hi)
            + jnp.matmul(jnp.matmul(x, lora["lora_a"], precision=hi),
                         lora["lora_b"], precision=hi) * scale)


@pytest.mark.parametrize("rows", [(32, 1), (8, 64)], ids=["decode-32x1", "rows-8x64"])
def test_quantised_dense_matches_the_float32_reference(rows):
    """A decode step's ``[32, 1, in]`` and a batch of rows go through the
    same kernel: both agree with the f32 reference to bf16 rounding."""
    layer, variables = _quantised_dense()
    x = jax.random.normal(jax.random.PRNGKey(4), rows + (128,)).astype(jnp.bfloat16)
    got = jax.jit(layer.apply)(variables, x)
    want = _reference_dense(variables, x)
    assert got.shape == rows + (96,) and got.dtype == jnp.bfloat16
    np.testing.assert_allclose(np.asarray(got, np.float32), np.asarray(want),
                               rtol=2e-2, atol=2e-2)


def test_decode_rows_equal_the_same_rows_in_a_batch():
    """Row for row: a lane of a decode step gets what the same vector gets
    inside ``[8, 64, in]`` (a row's product does not depend on its
    neighbours)."""
    layer, variables = _quantised_dense(dtype=jnp.float32)
    block = jax.random.normal(jax.random.PRNGKey(5), (8, 64, 128))
    lanes = block[:, :4, :].reshape(32, 1, 128)
    whole = jax.jit(layer.apply)(variables, block)
    decode = jax.jit(layer.apply)(variables, lanes)
    np.testing.assert_allclose(
        np.asarray(decode).reshape(8, 4, 96), np.asarray(whole[:, :4, :]),
        rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("wrt", ["x", "lora_a", "lora_b"])
def test_quantised_dense_gradients_match_the_float32_reference(wrt):
    """``value_and_grad`` through a quantised ``LoRADense`` (f32 compute, so
    the comparison is tight): the frozen kernel has no gradient of its own
    and hands the right one to ``x`` and to both adapter matrices."""
    layer, variables = _quantised_dense(dtype=jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(6), (2, 8, 128))
    target = jax.random.normal(jax.random.PRNGKey(7), (2, 8, 96))

    def put(x, variables, value):
        if wrt == "x":
            return value, variables
        return x, {"params": variables["params"],
                   "lora": {**variables["lora"], wrt: value}}

    def loss(value, apply):
        xx, vv = put(x, variables, value)
        return jnp.mean((apply(vv, xx) - target) ** 2)

    at = x if wrt == "x" else variables["lora"][wrt]
    got_v, got_g = jax.jit(jax.value_and_grad(
        lambda v: loss(v, layer.apply)))(at)
    want_v, want_g = jax.value_and_grad(
        lambda v: loss(v, _reference_dense))(at)
    np.testing.assert_allclose(got_v, want_v, rtol=1e-5)
    np.testing.assert_allclose(np.asarray(got_g), np.asarray(want_g),
                               rtol=2e-4, atol=1e-6)
