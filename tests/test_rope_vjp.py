"""The rotary embedding's own backward rule (ISSUE 50,
``models/llama.py::rotate_columns``): a cotangent is rotated back by the
negated angle in the forward's program.

* values and ``jax.vjp`` cotangents against autodiff of a plain float32 form
  written here, at the five shapes the benchmark's cells run, scaled down,
  over rows whose positions restart (packed documents) and llama3-scaled
  frequencies;
* in bf16 the rule's cotangent is at least as close to the float32 one as
  the cotangent autodiff makes of the slices and joins the call sites had;
* ``jax.grad`` through ``jax.checkpoint`` under the ``"full"`` policy;
* the structure: every rotary call site of the tiny models goes through the
  rule, and a differentiated step pads nothing for the rotation's sake.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from finetune_controller_tpu.models.llama import (
    PRESETS, LlamaConfig, LlamaForCausalLM, apply_rope, plain_inv_freqs,
    remat_policy_fn, rope_inv_freqs, rotate_columns)

B, S, H = 2, 12, 3

#: (head width, lo, hi, interleave, base): an eighth of the cells' widths
SHAPES = {
    "whole-head-of-128": (16, 0, 16, False, 1e4),        # Mistral, hybrid, lightning
    "first-64-of-192-full": (24, 0, 8, False, 5e6),      # window/full, a full layer
    "first-64-of-192-window": (24, 0, 8, False, 1e4),    # window/full, a window layer
    "last-64-of-192-pairs": (24, 16, 24, True, 1e4),     # expert cell
    "last-64-of-256-pairs": (32, 24, 32, True, 1e4),     # 16k cell
}
POSITIONS = {
    "one-document": jnp.broadcast_to(jnp.arange(S) * 37, (B, S)),
    "packed": jnp.stack([jnp.r_[jnp.arange(7), jnp.arange(5)] * 37,
                         jnp.r_[jnp.arange(2), jnp.arange(9), jnp.arange(1)] * 37]),
}


def frequencies(kind: str, theta: float, half: int) -> jax.Array:
    if kind == "plain":
        return plain_inv_freqs(theta, half)
    cfg = LlamaConfig(
        vocab_size=8, d_model=2 * half, n_layers=1, n_heads=1, n_kv_heads=1,
        d_ff=8, max_seq_len=64, rope_theta=theta, rope_scaling_factor=8.0,
        rope_scaling_original_max_len=64)
    scaled = rope_inv_freqs(cfg)
    assert scaled.shape == (half,) and not np.allclose(
        scaled, plain_inv_freqs(theta, half))
    return scaled


def plain(x, positions, inv_freqs, lo, hi, interleave):
    """The rotation in float32, written out: autodiff differentiates it."""
    x = x.astype(jnp.float32)
    angles = positions[..., None, None].astype(jnp.float32) * inv_freqs
    cos, sin = jnp.cos(angles), jnp.sin(angles)
    t = x[..., lo:hi]
    if interleave:
        a, b = t[..., 0::2], t[..., 1::2]
        out = jnp.stack([a * cos - b * sin, b * cos + a * sin], -1).reshape(t.shape)
    else:
        a, b = jnp.split(t, 2, axis=-1)
        out = jnp.concatenate([a * cos - b * sin, b * cos + a * sin], -1)
    return jnp.concatenate([x[..., :lo], out, x[..., hi:]], -1)


def call_site_of_the_parent(x, positions, inv_freqs, lo, hi, interleave):
    """What a call site did before the rule: a slice, :func:`apply_rope`, a
    join — in ``x``'s own type, the backward pass autodiff's."""
    return jnp.concatenate(
        [x[..., :lo],
         apply_rope(x[..., lo:hi], positions, inv_freqs=inv_freqs,
                    interleave=interleave),
         x[..., hi:]], -1)


def operands(shape: str, dtype=jnp.float32):
    d = SHAPES[shape][0]
    kx, kg = jax.random.split(jax.random.PRNGKey(d))
    return (jax.random.normal(kx, (B, S, H, d)).astype(dtype),
            jax.random.normal(kg, (B, S, H, d)).astype(dtype))


@pytest.mark.parametrize("freqs", ["plain", "llama3-scaled"])
@pytest.mark.parametrize("rows", list(POSITIONS))
@pytest.mark.parametrize("shape", list(SHAPES))
def test_values_and_cotangents_match_autodiff_of_the_plain_form(shape, rows, freqs):
    d, lo, hi, interleave, theta = SHAPES[shape]
    x, g = operands(shape)
    pos = POSITIONS[rows]
    f = frequencies(freqs, theta, (hi - lo) // 2)
    want, vjp_plain = jax.vjp(lambda x: plain(x, pos, f, lo, hi, interleave), x)
    got, vjp_rule = jax.vjp(
        lambda x: rotate_columns(x, pos, f, lo, hi, interleave), x)
    np.testing.assert_allclose(got, want, rtol=1e-6, atol=1e-6)
    np.testing.assert_allclose(vjp_rule(g)[0], vjp_plain(g)[0], rtol=1e-6, atol=1e-6)
    # the columns outside [lo, hi) pass both ways untouched
    keep = np.r_[0:lo, hi:d]
    np.testing.assert_array_equal(got[..., keep], x[..., keep])
    np.testing.assert_array_equal(vjp_rule(g)[0][..., keep], g[..., keep])
    assert float(jnp.abs(got[..., lo:hi] - x[..., lo:hi]).max()) > 1e-3


@pytest.mark.parametrize("rows", list(POSITIONS))
@pytest.mark.parametrize("shape", list(SHAPES))
def test_bf16_cotangent_is_rounded_once(shape, rows):
    """The rule rounds a cotangent ONCE, from float32; autodiff rounds each
    of the four products to bf16 and adds in bf16.  So the rule's is at least
    as close to the float32 cotangent, and the value is the call site's bit
    for bit."""
    _, lo, hi, interleave, theta = SHAPES[shape]
    x, g = operands(shape, jnp.bfloat16)
    pos = POSITIONS[rows]
    f = plain_inv_freqs(theta, (hi - lo) // 2)
    exact = jax.vjp(lambda x: plain(x, pos, f, lo, hi, interleave),
                    x.astype(jnp.float32))[1](g.astype(jnp.float32))[0]
    was, vjp_auto = jax.vjp(
        lambda x: call_site_of_the_parent(x, pos, f, lo, hi, interleave), x)
    got, vjp_rule = jax.vjp(
        lambda x: rotate_columns(x, pos, f, lo, hi, interleave), x)
    assert got.dtype == jnp.bfloat16 and vjp_rule(g)[0].dtype == jnp.bfloat16
    np.testing.assert_array_equal(np.asarray(got, np.float32),
                                  np.asarray(was, np.float32))

    def off(dx):
        return np.abs(np.asarray(dx, np.float32) - np.asarray(exact))

    rule, auto = off(vjp_rule(g)[0]), off(vjp_auto(g)[0])
    assert rule.max() <= auto.max() and rule.mean() <= auto.mean()
    assert rule.max() <= 2.0 ** -8 * np.abs(np.asarray(exact)).max()


@pytest.mark.parametrize("shape", list(SHAPES))
def test_gradient_through_a_checkpointed_block_under_full(shape):
    """A projection, the rotation and a product under ``jax.checkpoint`` with
    the ``"full"`` policy: the replayed forward and the rule give the plain
    form's gradients, and the rule keeps nothing of ``x``'s size."""
    d, lo, hi, interleave, theta = SHAPES[shape]
    pos = POSITIONS["packed"]
    f = plain_inv_freqs(theta, (hi - lo) // 2)
    u = jax.random.normal(jax.random.PRNGKey(3), (B, S, 10))
    w = jax.random.normal(jax.random.PRNGKey(4), (10, H * d)) * 0.3

    def block(rotate):
        def fn(w, u):
            q = rotate((u @ w).reshape(B, S, H, d), pos, f, lo, hi, interleave)
            return jnp.einsum("bshd,bthd->bhst", q, q)
        return fn

    def loss(fn):
        return lambda w, u: jnp.sum(jnp.tanh(fn(w, u)))

    kept = jax.checkpoint(block(rotate_columns), policy=remat_policy_fn("full"))
    got = jax.grad(loss(kept), argnums=(0, 1))(w, u)
    want = jax.grad(loss(block(plain)), argnums=(0, 1))(w, u)
    for a, b in zip(got, want):
        np.testing.assert_allclose(a, b, rtol=2e-5, atol=2e-5)
    x = (u @ w).reshape(B, S, H, d)
    _, vjp = jax.vjp(lambda x: rotate_columns(x, pos, f, lo, hi, interleave), x)
    kept_for_it = jax.tree_util.tree_leaves(vjp)       # the rule's residuals
    assert kept_for_it and all(leaf.size < x.size for leaf in kept_for_it)


def test_positions_and_frequencies_get_no_gradient():
    x, _ = operands("first-64-of-192-full")
    f = plain_inv_freqs(1e4, 4)
    pos = POSITIONS["packed"]
    df = jax.grad(lambda f: rotate_columns(x, pos, f, 0, 8, False).sum())(f)
    np.testing.assert_array_equal(df, np.zeros(4, np.float32))
    _, vjp = jax.vjp(lambda x, p: rotate_columns(x, p, f, 0, 8, False), x, pos)
    assert vjp(x)[1].dtype == jax.dtypes.float0


# ---- the structure: what a step's jaxpr holds -----------------------------------

def _walk(jaxpr, inside=()):
    """Every equation of ``jaxpr`` and of the jaxprs in its parameters, with
    the primitives it is nested in."""
    for eqn in jaxpr.eqns:
        yield eqn, inside
        for value in eqn.params.values():
            for sub in (value if isinstance(value, (list, tuple)) else (value,)):
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    yield from _walk(sub, (*inside, eqn.primitive.name))


def _stack(eqn) -> str:
    return str(eqn.source_info.name_stack)


#: preset -> (the module a rotary call stands under, the calls one trace holds)
CALL_SITES = {
    "tiny-test": ("attn", 2),                   # Attention: q, k of the stack's layer
    "tiny-mimo-v2-test": ("attn", 8),           # Attention: q, k of four traced layers
    "tiny-mla-moe-test": ("attn", 4),           # MLAttention: q, k_rope; dense layer, stack
    "tiny-minicpm-sala-test": ("lightning", 2),  # LightningMixer: q, k
}


@pytest.fixture(scope="module", params=list(CALL_SITES))
def traced(request):
    cfg = PRESETS[request.param]
    model = LlamaForCausalLM(cfg)
    tokens = jnp.zeros((1, 64), jnp.int32)
    variables = jax.eval_shape(
        lambda: model.init({"params": jax.random.PRNGKey(0)}, tokens))

    def loss(v):
        out = model.apply(v, tokens)
        return (out[0] if isinstance(out, tuple) else out).astype(jnp.float32).sum()

    return (request.param, jax.make_jaxpr(loss)(variables).jaxpr,
            jax.make_jaxpr(jax.grad(loss))(variables).jaxpr)


def test_every_rotary_call_site_goes_through_the_rule(traced):
    """``custom_vjp_call`` under the module's ``rope`` scope, as many as the
    model traces, and no sine or cosine anywhere outside one: no call site
    keeps a rotation autodiff would transpose."""
    preset, forward, _ = traced
    module, count = CALL_SITES[preset]
    calls = [_stack(e) for e, _ in _walk(forward)
             if e.primitive.name.startswith("custom_vjp_call")
             and "rope" in _stack(e).split("/")]
    assert len(calls) == count, calls
    assert all(f"{module}/rope" in stack for stack in calls), calls
    trig = [inside for e, inside in _walk(forward)
            if e.primitive.name in ("sin", "cos")]
    assert trig and all(
        any(p.startswith("custom_vjp_call") for p in inside) for inside in trig)


def test_a_differentiated_step_pads_nothing_for_the_rotation(traced):
    """Autodiff's transposes of a call site's slices and joins were ``pad`` s
    to the head's width, summed; the rule's backward is the forward's
    program under ``rope/rope`` (the call site's scope, then the rule's own).
    Latent attention's ``rope`` scope also cuts the position-free half of
    ``kv_b_proj``'s output, whose transpose stays a pad of THAT width."""
    preset, _, backward = traced
    cfg = PRESETS[preset]
    pads = [e for e, _ in _walk(backward)
            if e.primitive.name == "pad" and "rope" in _stack(e).split("/")]
    if cfg.attention_kind == "mla":
        kv_width = cfg.qk_nope_head_dim + cfg.v_head_dim
        assert all(e.outvars[0].aval.shape[-1] == kv_width
                   and e.invars[0].aval.shape[-1] == cfg.qk_nope_head_dim
                   for e in pads), [e.outvars[0].aval for e in pads]
    else:
        assert not pads, [_stack(e) for e in pads]
    ruled = {e.primitive.name for e, _ in _walk(backward)
             if "rope/rope" in _stack(e)}
    # the negated sine, the products, ONE rounding, and the barrier that keeps
    # a consumer's widening out of the rule's write
    assert {"neg", "mul", "convert_element_type", "optimization_barrier"} <= ruled, ruled
