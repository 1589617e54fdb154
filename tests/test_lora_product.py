"""``LoRADense``'s arithmetic (ISSUE 37): the adapter's rank-``r`` products
inside the base product's contraction (``models/lora.py::joined_product``)
against the expression the layer had, ``x @ W + (x @ a) @ b * scale`` left to
autodiff — value and gradients; the rule that picks a projection's form from
its static shapes; and the programs that must not change.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from finetune_controller_tpu.models import lora
from finetune_controller_tpu.models.lora import LoRADense, joined_product

SCALE = 2.5


def apart(x, kernel, a, b, scale):
    """What the layer computed before: base and delta rounded apart."""
    return x @ kernel + (x @ a.astype(x.dtype)) @ b.astype(x.dtype) * scale


def operands(shape, rank, dtype, lead=(48,), seed=0):
    n_in, n_out = shape
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    x = jax.random.normal(ks[0], (*lead, n_in), jnp.float32).astype(dtype)
    kernel = (jax.random.normal(ks[1], shape) * n_in ** -0.5).astype(dtype)
    a = jax.random.normal(ks[2], (n_in, rank)) * 0.1
    b = jax.random.normal(ks[3], (rank, n_out)) * 0.1
    cot = jax.random.normal(ks[4], (*lead, n_out), jnp.float32).astype(dtype)
    return x, kernel, a, b, cot


def value_and_grads(fn, x, kernel, a, b, cot):
    out, vjp = jax.vjp(lambda x, a, b: fn(x, kernel, a, b, SCALE), x, a, b)
    return (out, *vjp(cot))


def close(got, want, dtype):
    """float32: 1e-6 of the largest magnitude.  bf16: one add's rounding —
    the forms differ by where they round (apart: base, delta, sum; joined:
    once), never by more than an ulp of the result."""
    got, want = (np.asarray(v, np.float32) for v in (got, want))
    tol = 1e-6 if dtype == jnp.float32 else 2.0 ** -7
    assert np.max(np.abs(got - want)) <= tol * np.max(np.abs(want))


SHAPES = {"square": (128, 128), "out>>in": (64, 384), "in>>out": (384, 64),
          "out-not-of-128": (128, 200)}


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
@pytest.mark.parametrize("rank", [8, 16])
@pytest.mark.parametrize("shape", SHAPES.values(), ids=SHAPES.keys())
def test_joined_product_is_the_layers_expression(shape, rank, dtype):
    ops = operands(shape, rank, dtype)
    got = value_and_grads(joined_product, *ops)
    want = value_and_grads(apart, *ops)
    assert [g.dtype for g in got] == [dtype, dtype, jnp.float32, jnp.float32]
    for g, w in zip(got, want):
        close(g, w, dtype)


def test_kernel_gets_its_gradient_where_it_trains():
    """A full fine-tune beside frozen adapters differentiates the kernel."""
    x, kernel, a, b, cot = operands((64, 96), 8, jnp.float32)
    got = jax.grad(lambda k: (joined_product(x, k, a, b, SCALE) * cot).sum())(kernel)
    want = jax.grad(lambda k: (apart(x, k, a, b, SCALE) * cot).sum())(kernel)
    close(got, want, jnp.float32)


def test_replayed_forward_under_checkpoint():
    x, kernel, a, b, cot = operands((128, 256), 16, jnp.float32)

    def through(fn):
        def block(x, a, b):
            return jnp.tanh(fn(jnp.tanh(x), kernel, a, b, SCALE))
        out, vjp = jax.vjp(jax.checkpoint(block), x, a, b)
        return (out, *vjp(cot))

    for g, w in zip(through(joined_product), through(apart)):
        close(g, w, jnp.float32)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
def test_zero_b_gives_exactly_the_base_product(dtype):
    """An adapter starts at ``b = 0``: the joined contraction then adds exact
    zeros.  Whole-number operands make every partial sum exact, so the two
    products may not differ in a single bit whatever order they sum in."""
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.integers(-4, 5, (40, 96)), dtype)
    kernel = jnp.asarray(rng.integers(-4, 5, (96, 72)), dtype)
    a = jnp.asarray(rng.standard_normal((96, 8)), jnp.float32)
    got = joined_product(x, kernel, a, jnp.zeros((8, 72)), SCALE)
    assert got.dtype == dtype
    np.testing.assert_array_equal(np.asarray(got, np.float32),
                                  np.asarray(x @ kernel, np.float32))


# ---- through the layer -------------------------------------------------------------

def layer_pair(monkeypatch, x, **kw):
    """The same layer and leaves in both forms: ``(joined, apart)`` as
    functions of ``(x, lora leaves)`` giving the layer's output."""
    layer = LoRADense(features=kw.pop("features", 160), lora_rank=8,
                      lora_alpha=8 * SCALE, **kw)
    monkeypatch.setattr(lora, "joins_base_product", lambda *a, **k: True)
    variables = layer.init(jax.random.PRNGKey(1), x)
    leaves = jax.tree.map(
        lambda v: jax.random.normal(jax.random.PRNGKey(2), v.shape) * 0.1,
        variables["lora"])
    if "bias" in variables["params"]:
        variables = {**variables, "params": {
            **variables["params"],
            "bias": jnp.linspace(-1.0, 1.0, layer.features)}}

    def form(joined):
        def apply(x, leaves):
            monkeypatch.setattr(lora, "joins_base_product",
                                lambda *a, **k: joined)
            return layer.apply({**variables, "lora": leaves}, x)
        return apply

    return form(True), form(False), leaves


LAYERS = {
    "flat": (dict(dtype=jnp.float32), (48, 64)),
    "batch-by-sequence": (dict(dtype=jnp.float32), (3, 16, 64)),
    "bias": (dict(dtype=jnp.float32, use_bias=True), (3, 16, 64)),
    "quantised-base": (dict(dtype=jnp.float32, quantize_base=True,
                            quant_block=16), (3, 16, 64)),
    "quantised-base-bf16": (dict(dtype=jnp.bfloat16, quantize_base=True,
                                 quant_block=16), (3, 16, 64)),
}


@pytest.mark.parametrize("kw,x_shape", LAYERS.values(), ids=LAYERS.keys())
def test_layer_in_the_joined_form_is_the_layer_apart(monkeypatch, kw, x_shape):
    dtype = kw["dtype"]
    x = jax.random.normal(jax.random.PRNGKey(0), x_shape, jnp.float32).astype(dtype)
    joined, apart_, leaves = layer_pair(monkeypatch, x, **kw)
    cot = jax.random.normal(jax.random.PRNGKey(3), (*x_shape[:-1], 160),
                            jnp.float32).astype(dtype)

    def both(apply):
        out, vjp = jax.vjp(apply, x, leaves)
        dx, dleaves = vjp(cot)
        return out, dx, dleaves["lora_a"], dleaves["lora_b"]

    assert "joined_product" in str(jax.make_jaxpr(joined)(x, leaves))
    assert "joined_product" not in str(jax.make_jaxpr(apart_)(x, leaves))
    for g, w in zip(both(joined), both(apart_)):
        assert g.shape == w.shape
        close(g, w, dtype)


# ---- the rule -----------------------------------------------------------------------

#: (rows of a microbatch, {projection: (in, out, joined?)}) of the benchmark's
#: five cells at their published widths, rank 16: what the chip read
#: (PERF.md section 6, PR 37) is the rule's answer at these
CELLS = {
    "mistral-2k-and-8k": (16384, {
        "q_proj": (4096, 4096, True), "k_proj": (4096, 1024, True),
        "v_proj": (4096, 1024, True), "o_proj": (4096, 4096, True),
        "gate_proj": (4096, 14336, True), "up_proj": (4096, 14336, True),
        "down_proj": (14336, 4096, False)}),
    # the 2k cell's step with fewer rows, read on both sides of the old cutoff
    "mistral-8192-rows": (8192, {
        "gate_proj": (4096, 14336, True), "down_proj": (14336, 4096, False)}),
    "mistral-4096-rows": (4096, {"q_proj": (4096, 4096, False)}),
    "joyai-4k": (8192, {
        "q_a_proj": (2048, 1536, True), "q_b_proj": (1536, 6144, True),
        "kv_a_proj_with_mqa": (2048, 576, True), "kv_b_proj": (512, 8192, True),
        "o_proj": (4096, 2048, True), "dense-gate_proj": (2048, 7168, True),
        "dense-down_proj": (7168, 2048, False), "shared-up_proj": (2048, 768, True),
        "shared-down_proj": (768, 2048, True)}),
    "glm-16k": (16384, {
        "q_a_proj": (6144, 2048, True), "q_b_proj": (2048, 16384, True),
        "kv_a_proj_with_mqa": (6144, 576, True), "kv_b_proj": (512, 28672, True),
        "o_proj": (16384, 6144, False), "dense-up_proj": (6144, 12288, True),
        "dense-down_proj": (12288, 6144, False), "shared-gate_proj": (6144, 2048, True),
        "shared-down_proj": (2048, 6144, True)}),
    "falcon-h1-8k": (8192, {
        "q_proj": (5120, 2560, False), "k_proj": (5120, 512, False),
        "o_proj": (2560, 5120, True), "in_proj": (5120, 9248, False),
        "out_proj": (4096, 5120, True), "gate_proj": (5120, 21504, False),
        "down_proj": (21504, 5120, False)}),
}


@pytest.mark.parametrize("rows,n_in,n_out,want", [
    pytest.param(rows, *widths, id=f"{cell}-{name}")
    for cell, (rows, projections) in CELLS.items()
    for name, widths in projections.items()])
def test_rule_at_the_cells_projections(rows, n_in, n_out, want):
    assert lora.joins_base_product(rows, n_in, 16) is want


@pytest.mark.parametrize("rows,n_in,n_out", [
    (32, 4096, 14336), (32, 14336, 4096), (32, 4096, 1024),      # decode lanes
    (2048, 4096, 4096), (2048, 4096, 14336), (512, 2048, 5632),  # prefill
    (8, 2048, 2048), (1, 4096, 32768),
], ids=lambda v: str(v))
def test_rule_keeps_serving_shapes_apart(rows, n_in, n_out):
    assert lora.joins_base_product(rows, n_in, 16) is False


def test_rule_keeps_a_sharded_joined_axis_and_rank_zero_apart():
    assert lora.joins_base_product(16384, 4096, 16) is True
    assert lora.joins_base_product(16384, 4096, 16, sharded=True) is False
    assert lora.joins_base_product(16384, 4096, 0) is False


def test_the_cutoff_was_read_under_this_compiler():
    """Which side of the cutoff wins is the compiler's fusions (PERF.md
    section 6, PR 37), and no CPU test can see them move.  Under another
    compiler: run both Mistral cells with the rule and with every projection
    apart, then move the cutoff or this record."""
    from importlib.metadata import version

    assert lora.CUTOFF_READ_UNDER == {
        "jax": version("jax"), "libtpu": version("libtpu")}


@pytest.mark.parametrize("axes,want", [
    (dict(dp=2), True), (dict(dp=4), False), (dict(sp=4), False),
    (dict(fsdp=2), False), (dict(tp=2), False),
    (dict(dp=2, fsdp=2, tp=2), False)], ids=str)
def test_layer_asks_the_mesh_in_scope(devices8, axes, want):
    """``fsdp`` and ``tp`` split a kernel's ``in`` and ``out`` — the axes the
    joined operands are concatenated along; ``dp`` and ``sp`` split the rows,
    and the rule is asked with a device's own: 480 rows of 64 columns join on
    two devices (240 >= 1.75 * 72 = 126) and not on four (120)."""
    import math

    from finetune_controller_tpu.parallel.mesh import MeshSpec
    from finetune_controller_tpu.parallel.ring import ring_mesh

    mesh = MeshSpec(**axes).build(devices8[:math.prod(axes.values())])
    layer = LoRADense(features=64, lora_rank=8, dtype=jnp.float32)
    x = jnp.ones((480, 64))
    variables = layer.init(jax.random.PRNGKey(0), x)
    with ring_mesh(mesh):
        got = str(jax.make_jaxpr(layer.apply)(variables, x))
    assert ("joined_product" in got) is want


# ---- programs that must not change -----------------------------------------------

def mistral_width_forward(rows: tuple[int, int], **cfg_kw):
    """The jaxpr of a two-layer model at the Mistral configuration's widths
    (int4 base, rank 16 on all seven projections) on ``rows`` tokens, traced
    on shapes alone."""
    from finetune_controller_tpu.models.llama import LlamaConfig, LlamaForCausalLM
    from finetune_controller_tpu.models.lora import LoRAConfig

    cfg = LlamaConfig(
        vocab_size=512, d_model=4096, n_layers=2, n_heads=32, n_kv_heads=8,
        d_ff=14336, max_seq_len=2048, attention_impl="xla", quantize_base=True,
        lora=LoRAConfig(rank=16), dtype=jnp.bfloat16, **cfg_kw)
    model = LlamaForCausalLM(cfg)
    tokens = jax.ShapeDtypeStruct(rows, jnp.int32)
    variables = jax.eval_shape(
        lambda: model.init(jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32)))
    return str(jax.make_jaxpr(lambda v, t: model.apply(v, t))(variables, tokens))


@pytest.mark.parametrize("rows", [(32, 1), (1, 512), (1, 2048)],
                         ids=["decode-32-lanes", "prefill-512", "prefill-2048"])
def test_a_serving_program_is_the_parents(monkeypatch, rows):
    """At decode and prefill shapes the rule keeps every projection apart:
    the traced program is the one with the rule switched off, which is the
    code the layer had."""
    got = mistral_width_forward(rows)
    assert "joined_product" not in got
    monkeypatch.setattr(lora, "joins_base_product", lambda *a, **k: False)
    assert got == mistral_width_forward(rows)


def test_a_training_shape_takes_the_joined_form():
    assert "joined_product" in mistral_width_forward((8, 2048))


def test_training_dropout_keeps_the_layers_own_code():
    layer = LoRADense(features=64, lora_rank=8, lora_dropout=0.1,
                      dtype=jnp.float32)
    x = jnp.ones((256, 64))
    variables = layer.init(jax.random.PRNGKey(0), x)

    def jaxpr(deterministic):
        return str(jax.make_jaxpr(lambda v, x: layer.apply(
            v, x, deterministic=deterministic,
            rngs={"dropout": jax.random.PRNGKey(1)}))(variables, x))

    assert "joined_product" in jaxpr(True)     # 256 rows of 64: the rule joins
    assert "joined_product" not in jaxpr(False)
