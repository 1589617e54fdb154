"""The state-space mixer (``models/ssm.py``) and the hybrid block that runs it
beside attention (``models/llama.py``): the chunked scan against the
token-by-token recurrence at the family's own decays, packed documents against
the same documents alone, the block's shape, every muP multiplier, and what the
model refuses."""

import sys
import zlib
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))

from benchmarks.reference.falcon_h1 import recurrence  # noqa: E402
from finetune_controller_tpu.models import llama, ssm  # noqa: E402
from finetune_controller_tpu.models.llama import PRESETS, LlamaForCausalLM  # noqa: E402
from finetune_controller_tpu.models.lora import HYBRID_TARGETS, LoRAConfig  # noqa: E402
from finetune_controller_tpu.ops.pallas import ssd_scan  # noqa: E402
from finetune_controller_tpu.train.losses import next_token_loss  # noqa: E402

TINY = PRESETS["tiny-falcon-h1-test"].replace(
    dtype=jnp.float32, lora=LoRAConfig(rank=4, targets=HYBRID_TARGETS))
MULTIPLIERS = ("embedding_multiplier", "lm_head_multiplier",
               "attention_in_multiplier", "attention_out_multiplier",
               "key_multiplier", "ssm_in_multiplier", "ssm_out_multiplier",
               *(f"ssm_multipliers[{i}]" for i in range(5)),
               "mlp_multipliers[0]", "mlp_multipliers[1]")


def _scan_inputs(seq, seed=0, bsz=2, h=4, p=8, g=2, n=6, steps=(1e-3, 1e-1)):
    """Inputs at the family's initialisation: ``A`` from [1, 16], step sizes
    log-uniform in [0.001, 0.1] — a row decays by 0.2 to 0.999, so a state
    crosses MANY chunks of 8."""
    rng = np.random.default_rng(seed)
    x = rng.normal(size=(bsz, seq, h, p)).astype(np.float32)
    b = rng.normal(size=(bsz, seq, g, n)).astype(np.float32)
    c = rng.normal(size=(bsz, seq, g, n)).astype(np.float32)
    dt = np.exp(rng.uniform(*np.log(steps), (bsz, seq, h))).astype(np.float32)
    a = -rng.uniform(1.0, 16.0, (h,)).astype(np.float32)
    d = rng.normal(size=(h,)).astype(np.float32)
    return tuple(jnp.asarray(t) for t in (x, dt, a, b, c, d))


def _token_by_token(x, dt, a, b, c, d, runs=None):
    """``ssd_chunked``'s result by the recurrence itself (the reference's)."""
    bsz, s, h, p = x.shape
    g = b.shape[2]
    xg = x.reshape(bsz, s, g, h // g, p)
    dtg = dt.reshape(bsz, s, g, h // g)
    log_decay = dtg * a.reshape(g, h // g)
    if runs is not None:
        starts = jnp.pad(runs[:, 1:] != runs[:, :-1], ((0, 0), (1, 0)))
        log_decay = jnp.where(starts[..., None, None], -jnp.inf, log_decay)
    y = recurrence(xg * dtg[..., None], log_decay, b, c, block=5)
    return (y + xg * d.reshape(g, h // g, 1)).reshape(bsz, s, h, p)


#: (heads, head size, groups, state size): the hybrid family's shape in small
#: (a head as wide as it likes, B and C in 2 groups) and the pattern family's
#: (many narrow heads, a head size that is NOT the state's, B and C in 8
#: groups of two heads each)
HEAD_SHAPES = {"hybrid": dict(h=4, p=8, g=2, n=6), "pattern": dict(h=16, p=4, g=8, n=8)}


@pytest.mark.parametrize("shape", list(HEAD_SHAPES))
@pytest.mark.parametrize("seq", [64, 53, 8, 3], ids=lambda s: f"rows{s}")
def test_chunked_scan_is_the_recurrence_forward_and_gradients(seq, shape):
    """At decays near 1 the carry between chunks of 8 IS the result: the
    chunked form equals the token-by-token recurrence, values and gradients
    of every input, whole chunks or a ragged last one — at both families'
    head shapes, across up to eight chunks."""
    args = _scan_inputs(seq, **HEAD_SHAPES[shape])

    def loss(fn, *a):
        y = fn(*a)
        return (y * jnp.cos(jnp.arange(y.size).reshape(y.shape))).sum(), y

    (_, got), g_got = jax.value_and_grad(
        lambda *a: loss(lambda *t: ssm.ssd_chunked(*t, chunk=8), *a),
        argnums=(0, 1, 2, 3, 4, 5), has_aux=True)(*args)
    (_, want), g_want = jax.value_and_grad(
        lambda *a: loss(_token_by_token, *a),
        argnums=(0, 1, 2, 3, 4, 5), has_aux=True)(*args)
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-4)
    for a, b in zip(g_got, g_want):
        np.testing.assert_allclose(a, b, rtol=2e-3, atol=2e-3 * float(jnp.abs(b).max()))


@pytest.mark.parametrize("shape", list(HEAD_SHAPES))
def test_the_carry_between_chunks_weighs_in_this_test(shape):
    """The same inputs with every chunk's entering state dropped (each chunk
    alone) give another result by far: the test above holds the carry."""
    x, dt, a, b, c, d = _scan_inputs(64, **HEAD_SHAPES[shape])
    d = jnp.zeros_like(d)           # the skip is no part of the state
    whole = ssm.ssd_chunked(x, dt, a, b, c, d, chunk=8)
    alone = jnp.concatenate([
        ssm.ssd_chunked(*(t[:, i:i + 8] for t in (x, dt)), a,
                        *(t[:, i:i + 8] for t in (b, c)), d, chunk=8)
        for i in range(0, 64, 8)], axis=1)
    gap = jnp.abs(whole - alone)[:, 8:].mean() / jnp.abs(whole).mean()
    assert gap > 0.2, float(gap)


@pytest.mark.parametrize("chunk", [4, 8, 16, 128])
def test_the_chunk_size_does_not_change_the_result(chunk):
    args = _scan_inputs(40, seed=1)
    np.testing.assert_allclose(ssm.ssd_chunked(*args, chunk=chunk),
                               ssm.ssd_chunked(*args, chunk=40),
                               rtol=2e-4, atol=2e-4)


def test_large_steps_never_overflow():
    """A pair ``s > t`` has a positive exponent, here up to 8 * 50 * 16: it is
    excluded before ``exp``, so values and gradients stay finite."""
    x, dt, a, b, c, d = _scan_inputs(32, seed=2)
    dt = dt * 500.0

    def total(x, dt):
        return ssm.ssd_chunked(x, dt, a, b, c, d, chunk=8).sum()

    value, grads = jax.value_and_grad(total, argnums=(0, 1))(x, dt)
    assert np.isfinite(value) and all(np.isfinite(g).all() for g in grads)


RUNS = np.asarray([[0] * 5 + [1] * 14 + [2] * 9 + [3] * 12,
                   [0] * 24 + [1] * 1 + [2] * 15])


def test_packed_scan_restarts_at_a_document_boundary():
    """Documents packed into rows of 40 (boundaries inside chunks, on a chunk's
    edge, a document of one row, one that spans three chunks) = the
    token-by-token recurrence with its state zeroed at each boundary = every
    document scanned alone."""
    x, dt, a, b, c, d = _scan_inputs(40, seed=3)
    runs = jnp.asarray(RUNS)
    got = ssm.ssd_chunked(x, dt, a, b, c, d, runs, chunk=8)
    np.testing.assert_allclose(got, _token_by_token(x, dt, a, b, c, d, runs),
                               rtol=2e-4, atol=2e-4)
    for row in range(2):
        for doc in np.unique(RUNS[row]):
            at = np.flatnonzero(RUNS[row] == doc)
            alone = ssm.ssd_chunked(
                *(t[row:row + 1, at] for t in (x, dt)), a,
                *(t[row:row + 1, at] for t in (b, c)), d, chunk=8)
            np.testing.assert_allclose(got[row:row + 1, at], alone,
                                       rtol=2e-4, atol=2e-4)


def test_document_runs_count_changes_of_id_whatever_the_ids():
    ids = jnp.asarray([[7, 7, 3, 3, 3, 7, 0, 0], [1, 1, 1, 1, 1, 1, 1, 2]])
    np.testing.assert_array_equal(
        ssm.document_runs(ids), [[0, 0, 1, 1, 1, 2, 3, 3], [0, 0, 0, 0, 0, 0, 0, 1]])


def test_convolution_is_causal_and_sees_zeros_before_a_document():
    rng = np.random.default_rng(4)
    x = jnp.asarray(rng.normal(size=(1, 9, 3)).astype(np.float32))
    w = jnp.asarray(rng.normal(size=(4, 3)).astype(np.float32))
    bias = jnp.asarray(rng.normal(size=(3,)).astype(np.float32))
    want = np.stack([
        bias + sum(w[3 - j] * x[0, t - j] for j in range(4) if t - j >= 0)
        for t in range(9)])
    np.testing.assert_allclose(ssm.causal_conv(x, w, bias)[0], want, rtol=1e-5,
                               atol=1e-6)
    runs = jnp.asarray([[0, 0, 0, 0, 0, 1, 1, 1, 1]])
    packed = ssm.causal_conv(x, w, bias, runs)
    np.testing.assert_allclose(packed[:, :5], ssm.causal_conv(x[:, :5], w, bias),
                               rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(packed[:, 5:], ssm.causal_conv(x[:, 5:], w, bias),
                               rtol=1e-5, atol=1e-6)


# ---- the Pallas kernels (ops/pallas/ssd_scan.py), interpreter mode -------------

#: both families' head shapes cut small where a block of the kernels still
#: tiles: a group's 16 heads kept, (heads, head size, groups, state size)
KERNEL_SHAPES = {"hybrid": dict(h=16, p=128, g=1, n=256),
                 "pattern": dict(h=32, p=64, g=2, n=128)}
#: a chunk of the kernels is 128 rows, sixteen of the chunks above: step
#: sizes a tenth of the family's keep a state alive across as many CHUNKS
KERNEL_STEPS = (1e-4, 1e-2)
ALL_SIX = (0, 1, 2, 3, 4, 5)


def _kernel(*args, runs=None):
    x, b = args[0], args[3]
    heads = ssd_scan.heads_per_block(x.shape[2], x.shape[3], *b.shape[2:], 128)
    assert heads in (8, 16)
    return ssd_scan.ssd_scan_pallas(
        *args, runs, chunk=128, heads_per_block=heads, interpret=True)


def _weighed(fn, *args):
    y = fn(*args)
    return (y * jnp.cos(jnp.arange(y.size).reshape(y.shape))).sum(), y


def _value_and_grads(fn, args):
    (_, y), grads = jax.jit(jax.value_and_grad(
        lambda *a: _weighed(fn, *a), argnums=ALL_SIX, has_aux=True))(*args)
    return y, grads


def _assert_close(got, want, rel):
    """Within ``rel`` of the wanted array's largest magnitude."""
    np.testing.assert_allclose(got, want, rtol=0,
                               atol=rel * float(jnp.abs(want).max()))


#: documents packed into rows of 512 = four chunks of 128: a boundary inside
#: a chunk, one at a chunk's edge, a whole chunk (rows 256..383) of another
#: document than its neighbours, a document of one row
KERNEL_RUNS = np.asarray([[0] * 70 + [1] * 58 + [2] * 128 + [3] * 128 + [4] * 128,
                          [0] * 300 + [1] * 1 + [2] * 211])


@pytest.mark.parametrize("shape", list(KERNEL_SHAPES))
@pytest.mark.parametrize("seq", [512, 300, 128, 3], ids=lambda s: f"rows{s}")
def test_scan_kernel_is_the_chunked_form_and_the_recurrence(seq, shape):
    """The kernels in interpreter mode against ``ssd_chunked`` AND the
    token-by-token recurrence: values and the gradients of all six inputs, at
    decays near 1 across up to four chunks, whole chunks or a ragged last
    one, one chunk, a row shorter than a chunk."""
    args = _scan_inputs(seq, bsz=1, steps=KERNEL_STEPS, **KERNEL_SHAPES[shape])
    got, g_got = _value_and_grads(_kernel, args)
    form, g_form = _value_and_grads(
        lambda *a: ssm.ssd_chunked(*a, chunk=128), args)
    want, g_want = _value_and_grads(_token_by_token, args)
    _assert_close(got, form, 1e-5)
    _assert_close(got, want, 2e-4)
    for mine, theirs, exact in zip(g_got, g_form, g_want):
        _assert_close(mine, theirs, 2e-5)
        _assert_close(mine, exact, 2e-3)


@pytest.mark.parametrize("shape", list(KERNEL_SHAPES))
def test_the_carry_between_chunks_weighs_in_the_kernels_test(shape):
    """At the kernel tests' step sizes every chunk of 128 alone gives another
    result by far: the tests above hold the state the kernel carries in VMEM."""
    x, dt, a, b, c, d = _scan_inputs(512, bsz=1, steps=KERNEL_STEPS,
                                     **KERNEL_SHAPES[shape])
    d = jnp.zeros_like(d)
    whole = _kernel(x, dt, a, b, c, d)
    alone = jnp.concatenate([
        ssm.ssd_chunked(*(t[:, i:i + 128] for t in (x, dt)), a,
                        *(t[:, i:i + 128] for t in (b, c)), d, chunk=128)
        for i in range(0, 512, 128)], axis=1)
    gap = jnp.abs(whole - alone)[:, 128:].mean() / jnp.abs(whole).mean()
    assert gap > 0.2, float(gap)


@pytest.mark.parametrize("shape", list(KERNEL_SHAPES))
def test_scan_kernel_restarts_at_document_boundaries(shape):
    """``runs`` inside the kernels: a boundary inside a chunk, at a chunk's
    edge, a whole chunk of another document and a document of one row zero
    what ``ssd_chunked`` zeroes — values and all six gradients equal its own
    and the recurrence's with the state zeroed at each boundary."""
    args = _scan_inputs(512, seed=3, bsz=2, steps=KERNEL_STEPS,
                        **KERNEL_SHAPES[shape])
    runs = jnp.asarray(KERNEL_RUNS)
    got, g_got = _value_and_grads(
        lambda *a: _kernel(*a, runs=runs), args)
    form, g_form = _value_and_grads(
        lambda *a: ssm.ssd_chunked(*a, runs, chunk=128), args)
    want, g_want = _value_and_grads(
        lambda *a: _token_by_token(*a, runs), args)
    _assert_close(got, form, 1e-5)
    _assert_close(got, want, 2e-4)
    for mine, theirs, exact in zip(g_got, g_form, g_want):
        _assert_close(mine, theirs, 2e-5)
        _assert_close(mine, exact, 2e-3)
    # and without the runs the documents DO leak into each other
    assert float(jnp.abs(_kernel(*args) - got).max()) > 1e-2


@pytest.mark.parametrize("shape", list(KERNEL_SHAPES))
def test_scan_kernel_rounds_where_the_chunked_form_rounds(shape):
    """bf16 inputs: the kernels' products take the operands ``ssd_chunked``
    rounds, so the two agree far inside what bf16 costs either against the
    float32 recurrence — values and the gradients of all six inputs."""
    x, dt, a, b, c, d = _scan_inputs(384, seed=5, bsz=1, steps=KERNEL_STEPS,
                                     **KERNEL_SHAPES[shape])
    args = (x.astype(jnp.bfloat16), dt, a, b.astype(jnp.bfloat16),
            c.astype(jnp.bfloat16), d)
    got, g_got = _value_and_grads(_kernel, args)
    form, g_form = _value_and_grads(
        lambda *a: ssm.ssd_chunked(*a, chunk=128), args)
    assert got.dtype == form.dtype == jnp.float32
    assert [g.dtype for g in g_got] == [g.dtype for g in g_form]
    _assert_close(got, form, 2e-3)
    for mine, theirs in zip(g_got, g_form):
        _assert_close(mine.astype(jnp.float32), theirs.astype(jnp.float32), 2e-2)


def test_scan_kernel_large_steps_never_overflow():
    """Exponents up to 128 * 50 * 16 for a pair ``s > t``: excluded before
    ``exp`` in the forward kernel and the reverse one, with and without
    document boundaries."""
    x, dt, a, b, c, d = _scan_inputs(256, seed=2, bsz=1, **KERNEL_SHAPES["pattern"])
    dt = dt * 500.0
    for runs in (None, jnp.asarray(KERNEL_RUNS[:1, :256])):
        value, grads = jax.value_and_grad(
            lambda x, dt: _kernel(x, dt, a, b, c, d, runs=runs).sum(),
            argnums=(0, 1))(x, dt)
        assert np.isfinite(value) and all(np.isfinite(g).all() for g in grads)


def test_scan_kernel_sums_a_groups_blocks_of_heads():
    """Eight heads a block where a group has sixteen: the group's two blocks
    each make the scores, and their cotangents for B and C add up."""
    args = _scan_inputs(256, seed=6, bsz=1, steps=KERNEL_STEPS,
                        **KERNEL_SHAPES["pattern"])

    def blocks_of(heads):
        return lambda *a: ssd_scan.ssd_scan_pallas(
            *a, chunk=128, heads_per_block=heads, interpret=True)

    got, g_got = _value_and_grads(blocks_of(8), args)
    want, g_want = _value_and_grads(blocks_of(16), args)
    _assert_close(got, want, 1e-6)
    for mine, theirs in zip(g_got, g_want):
        _assert_close(mine, theirs, 1e-5)


def test_a_kernels_call_is_built_once_and_its_body_traced_with_room():
    """A scanned, rematerialised stack traces its mixer seven times a step:
    the kernels' calls are built once for their shapes, so Pallas traces each
    body once; and the body is traced from a frame larger than one of
    CPython's 16 KiB chunks of frames (``call_with_room``: a kernel's body at
    a chunk's end maps and unmaps a chunk at every call it makes)."""
    from finetune_controller_tpu.ops.pallas import call_with_room

    args = _scan_inputs(256, seed=7, bsz=1, **KERNEL_SHAPES["hybrid"])
    jax.make_jaxpr(jax.grad(lambda *a: _kernel(*a).sum()))(*args)
    built = [f.cache_info().misses
             for f in (ssd_scan._forward_call, ssd_scan._backward_call)]
    jax.make_jaxpr(jax.grad(lambda *a: 2 * _kernel(*a).sum()))(*args)
    assert built == [f.cache_info().misses
                     for f in (ssd_scan._forward_call, ssd_scan._backward_call)]
    assert call_with_room(lambda a, b=1: a - b, 5, b=2) == 3
    assert 8 * call_with_room.__code__.co_stacksize >= 2 * 16 * 1024


# ---- every head its own B and C (G = H): blocks that span groups -------------------

#: lightning linear attention's shape cut small (every head a group of one:
#: a block of 8 heads spans 8 groups) and two heads a group of 64-wide heads
#: (a group fills one lane tile: a block of 16 heads spans 8 groups)
SPANNING_SHAPES = {"lightning": dict(h=8, p=128, g=8, n=128),
                   "pairs": dict(h=16, p=64, g=8, n=128)}


def _lightning_inputs(seq, shape, seed=0):
    """``_scan_inputs`` as the lightning mixer calls the scan: a step size of
    1, ``D`` = 0 and the fixed slopes — the last head's decay is 0.996 a row,
    0.61 a chunk: its state crosses MANY chunks."""
    x, dt, a, b, c, d = _scan_inputs(seq, seed, bsz=1, **shape)
    return (x, jnp.ones_like(dt), ssm.lightning_log_decay(shape["h"]), b, c,
            jnp.zeros_like(d))


@pytest.mark.parametrize("seq,shape", [(640, "lightning"), (300, "lightning"),
                                       (300, "pairs")])
def test_scan_kernel_at_a_group_a_head_is_the_recurrence_token_by_token(seq, shape):
    """The kernels in interpreter mode where a block of heads SPANS groups,
    against ``ssd_chunked`` and the token-by-token recurrence: values and the
    gradients of all six inputs, at the lightning mixer's decays across five
    chunks (a ragged last one at 300 rows), with and without document
    boundaries."""
    args = _lightning_inputs(seq, SPANNING_SHAPES[shape])
    runs = None if seq == 640 else jnp.asarray(
        [[0] * 70 + [1] * 58 + [2] * 128 + [3] * 44])
    got, g_got = _value_and_grads(lambda *a: _kernel(*a, runs=runs), args)
    form, g_form = _value_and_grads(
        lambda *a: ssm.ssd_chunked(*a, runs, chunk=128), args)
    want, g_want = _value_and_grads(lambda *a: _token_by_token(*a, runs), args)
    _assert_close(got, form, 1e-5)
    _assert_close(got, want, 2e-4)
    for mine, theirs, exact in zip(g_got, g_form, g_want):
        _assert_close(mine, theirs, 2e-5)
        _assert_close(mine, exact, 2e-3)
    if runs is None:    # the carry weighs: each chunk alone is another result
        alone = jnp.concatenate([
            ssm.ssd_chunked(*(t[:, i:i + 128] for t in args[:2]), args[2],
                            *(t[:, i:i + 128] for t in args[3:5]), args[5],
                            chunk=128) for i in range(0, 640, 128)], axis=1)
        assert float(jnp.abs(got - alone)[:, 128:].mean()
                     / jnp.abs(got).mean()) > 0.2


#: sha256 of the jaxpr text of value_and_grad of the kernels' call at the two
#: accepted cells' shapes (heads, head size, groups, state), one row of 8,192
#: in bf16, with and without document marks — taken from ``git archive`` of
#: PR 47's tree (711fafd) by the function below: a block of one group traces
#: the body, the grid and the index maps it always did
PARENT_SCAN_JAXPRS = {
    "hybrid": ((32, 128, 2, 256), {
        False: "72f67078267afcfb2ea9eafb2a19741869304bde40183ed0bda3f6beb27609e7",
        True: "a2b649671715e1702a801e5bf83c5ca0e83eba2f7154ac878484e35056de823b"}),
    "pattern": ((128, 64, 8, 128), {
        False: "c2079f5e125d5b10439e56996d22085f5ac4fb44ba717c6dac93b9da8f6c6c2e",
        True: "b8caf6a69fc1fa3fbd0cee3f78d5dda58b1b6618fe377bba21b833544c983f93"}),
}


def _scan_jaxpr(h, p, g, n, marks: bool, rows=8192) -> str:
    shaped = jax.ShapeDtypeStruct
    x = shaped((1, rows, h, p), jnp.bfloat16)
    dt = shaped((1, rows, h), jnp.float32)
    b = shaped((1, rows, g, n), jnp.bfloat16)
    a = shaped((h,), jnp.float32)
    runs = (shaped((1, rows), jnp.int32),) if marks else ()

    def loss(x, dt, b, c, a, d, *runs):
        y = ssd_scan.ssd_scan_pallas(
            x, dt, a, b, c, d, runs[0] if runs else None, chunk=128,
            heads_per_block=ssd_scan.heads_per_block(h, p, g, n, 128),
            interpret=False)
        return jnp.sum(y ** 2)

    return str(jax.make_jaxpr(jax.value_and_grad(loss, argnums=(0, 1, 2, 3)))(
        x, dt, b, b, a, a, *runs))


@pytest.mark.parametrize("marks", [False, True], ids=["whole", "documents"])
@pytest.mark.parametrize("cell", list(PARENT_SCAN_JAXPRS))
def test_a_block_of_one_group_traces_the_parents_kernels(cell, marks):
    """Adapting, not a path: at the accepted cells' shapes the chooser still
    gives a group's 16 heads and the calls trace the parent's program to the
    character (shapes, primitives, index maps and kernel names; no file names
    or line numbers).  The lightning shape's call is another text under the
    same two names."""
    import hashlib

    dims, parents = PARENT_SCAN_JAXPRS[cell]
    text = _scan_jaxpr(*dims, marks)
    assert hashlib.sha256(text.encode()).hexdigest() == parents[marks]
    spanning = _scan_jaxpr(32, 128, 32, 128, marks, rows=1024)
    for name in ("ssd_scan_fwd", "ssd_scan_bwd"):
        assert f"name={name}" in text and f"name={name}" in spanning


#: (heads, head size, groups, state size, chunk) -> heads a block, or 0
@pytest.mark.parametrize("dims,heads", [
    ((128, 64, 8, 128, 128), 16),      # the pattern family as published
    ((32, 128, 2, 256, 128), 16),      # the hybrid family as published
    ((32, 128, 32, 128, 128), 16),     # lightning attention as published: 16 groups of one
    ((8, 128, 8, 128, 128), 8),        # all eight heads, eight groups
    ((16, 64, 8, 128, 128), 16),       # two heads of 64 a group: a lane tile each
    ((8, 64, 8, 128, 128), 0),         # a head of 64 a group: half a lane tile
    ((24, 128, 24, 128, 128), 8),      # 16 does not divide 24 groups: three blocks of 8
    ((32, 64, 2, 128, 128), 16), ((16, 128, 1, 256, 256), 16),
    ((4, 8, 2, 6, 8), 0),              # the tiny presets: nothing tiles
    ((32, 128, 2, 256, 64), 0),        # a chunk that is no whole lane tile
    ((32, 128, 2, 192, 128), 0),       # a state that is none
    ((24, 64, 8, 128, 128), 0),        # three heads a group: no whole sublanes
    ((64, 128, 2, 1024, 128), 8),      # a group's 32 x 128 x 1024 states: a quarter of it
], ids=lambda v: "x".join(map(str, v)) if isinstance(v, tuple) else str(v))
def test_heads_per_block_is_a_group_where_it_tiles_and_fits(dims, heads):
    assert ssd_scan.heads_per_block(*dims) == heads


def test_chooser_takes_the_kernels_on_a_tpu_without_a_mesh_only(devices8):
    """``ssd_scan_impl``'s table: the CPU -> ``xla``; a described TPU with
    no mesh or a one-device mesh -> ``pallas`` and a group's heads; a ``tp``
    or ``fsdp`` mesh of several devices -> ``xla`` (a Mosaic call cannot be
    partitioned); shapes that do not tile -> ``xla`` wherever."""
    from finetune_controller_tpu.parallel.mesh import MeshSpec
    from finetune_controller_tpu.parallel.ring import ring_mesh

    pattern, hybrid = (128, 64, 8, 128, 128), (32, 128, 2, 256, 128)
    assert ssd_scan.ssd_scan_impl(*pattern) == ("xla", 0)          # here: the CPU
    assert ssd_scan.ssd_scan_impl(*pattern, backend="tpu") == ("pallas", 16)
    assert ssd_scan.ssd_scan_impl(*hybrid, backend="tpu") == ("pallas", 16)
    assert ssd_scan.ssd_scan_impl(32, 128, 32, 128, 128, backend="tpu") == (
        "pallas", 16)                   # lightning attention: every head a group
    assert ssd_scan.ssd_scan_impl(4, 8, 2, 6, 8, backend="tpu") == ("xla", 0)
    with ring_mesh(MeshSpec(fsdp=1).build(devices8[:1])):
        assert ssd_scan.ssd_scan_impl(*hybrid, backend="tpu") == ("pallas", 16)
    for spec, n_devices in ((MeshSpec(tp=2), 2), (MeshSpec(fsdp=2, tp=2), 4)):
        with ring_mesh(spec.build(devices8[:n_devices])):
            assert ssd_scan.ssd_scan_impl(*hybrid, backend="tpu") == ("xla", 0)


def test_mixer_on_the_kernels_is_the_mixer_on_the_chunked_form(monkeypatch):
    """``Mamba2Mixer`` calls the chooser's ONE function: made to choose the
    kernels (interpreted here), a mixer whose shapes tile gives the output and
    the gradients — input, adapters, and the frozen ``A_log``, ``dt_bias``
    and ``D`` a full fine-tune trains — that it gives on ``ssd_chunked``,
    packed documents included."""
    cfg = TINY.replace(d_model=64, ssm_n_heads=16, ssm_head_dim=8,
                       ssm_d_state=128, ssm_n_groups=1, ssm_chunk=128)
    mixer = ssm.Mamba2Mixer(cfg)
    u = jax.random.normal(jax.random.PRNGKey(1), (2, 200, 64))
    seg = jnp.asarray(np.repeat([[1] * 90 + [2] * 110], 2, axis=0))
    variables = mixer.init(jax.random.PRNGKey(0), u)
    variables = {**variables, "lora": jax.tree.map(
        lambda a: 0.05 * jax.random.normal(jax.random.PRNGKey(a.size), a.shape),
        variables["lora"])}

    def grads():      # a new function a call: traced again, the chooser asked again
        return jax.jit(jax.value_and_grad(
            lambda v, u: jnp.sum(jnp.sin(mixer.apply(v, u, seg))),
            argnums=(0, 1)))(variables, u)

    want = grads()
    seen = []
    monkeypatch.setattr(
        ssd_scan, "ssd_scan_impl",
        lambda h, p, g, n, chunk, **kw: seen.append((h, p, g, n, chunk))
        or ("pallas", ssd_scan.heads_per_block(h, p, g, n, chunk)))
    got = grads()
    assert seen == [(16, 8, 1, 128, 128)]
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_allclose(a, b, rtol=2e-4,
                                   atol=2e-5 * float(jnp.abs(b).max()) + 1e-7)
    moved = got[1][0]["params"]
    assert all(float(jnp.abs(moved[k][leaf]).max()) > 0 for k, leaf in (
        ("A_log", "bias"), ("dt_bias", "bias"), ("D", "scale")))


# ---- the model -------------------------------------------------------------------


def _variables(cfg=TINY, seed=0, seq=24):
    model = LlamaForCausalLM(cfg)
    tokens = jax.random.randint(jax.random.PRNGKey(seed + 1), (2, seq), 0,
                                cfg.vocab_size)
    variables = model.init({"params": jax.random.PRNGKey(seed)}, tokens)
    # adapters of a job in mid-training: a zero B would hide A's gradient
    lora = jax.tree_util.tree_map_with_path(
        lambda p, a: 0.05 * jax.random.normal(
            # crc32, not hash(): a str's hash changes with every process, and
            # one draw in many left a multiplier's loss within rounding
            jax.random.PRNGKey(zlib.crc32(str(p).encode())), a.shape, a.dtype),
        variables["lora"])
    return model, {"params": variables["params"], "lora": lora}, tokens


def _loss(cfg, variables, tokens, **kw):
    logits = LlamaForCausalLM(cfg).apply(variables, tokens, **kw)
    return next_token_loss(logits, tokens, None)[0]


def test_packed_documents_are_the_same_documents_alone():
    """Three documents packed into one row with ``segment_ids`` give each the
    logits it gets alone (attention, the convolution and the scan all restart;
    positions restart with the document)."""
    model, variables, _ = _variables()
    lengths = (7, 12, 5)
    docs = [jax.random.randint(jax.random.PRNGKey(10 + i), (1, n), 0, 256)
            for i, n in enumerate(lengths)]
    packed = jnp.concatenate(docs, axis=1)
    seg = jnp.concatenate([jnp.full((1, n), i + 1) for i, n in enumerate(lengths)], 1)
    pos = jnp.concatenate([jnp.arange(n)[None] for n in lengths], axis=1)
    got = model.apply(variables, packed, positions=pos, segment_ids=seg)
    at = 0
    for doc in docs:
        alone = model.apply(variables, doc)
        np.testing.assert_allclose(got[:, at:at + doc.shape[1]], alone,
                                   rtol=2e-4, atol=2e-5)
        at += doc.shape[1]
    # and without the ids the documents DO leak into each other
    leaked = model.apply(variables, packed, positions=pos)
    assert float(jnp.abs(leaked - got)[:, lengths[0]:].max()) > 1e-4


def test_block_is_attention_plus_mixer_under_one_norm_not_in_sequence():
    """The block's first half, rebuilt from its parts on the SAME normed
    input: ``h + attn(u) * a_out + mixer(u) * s_out``; feeding the mixer the
    attention's result instead (one after the other) gives another block."""
    cfg = TINY.replace(scan_layers=False, n_layers=1, remat=False)
    model, variables, tokens = _variables(cfg)
    block = {c: variables[c]["layer_0"] for c in ("params", "lora")}
    x = jax.random.normal(jax.random.PRNGKey(5), (2, 24, cfg.d_model))
    pos = jnp.broadcast_to(jnp.arange(24), (2, 24))
    got = llama.Block(cfg).apply(block, x, pos, None)

    def part(name, module, *args):
        return module.apply({c: block[c][name] for c in ("params", "lora")
                             if name in block[c]}, *args)

    u = part("attn_norm", llama.RMSNorm(cfg.rms_eps, cfg.dtype), x)
    attn = part("attn", llama.Attention(cfg), u * cfg.attention_in_multiplier,
                pos, None) * cfg.attention_out_multiplier
    mixer = part("mamba", ssm.Mamba2Mixer(cfg), u) * cfg.ssm_out_multiplier
    h = x + attn + mixer
    want = h + part("mlp", llama.MLP(cfg),
                    part("mlp_norm", llama.RMSNorm(cfg.rms_eps, cfg.dtype), h))
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)
    assert float(jnp.abs(attn).mean()) > 0 and float(jnp.abs(mixer).mean()) > 0
    in_sequence = x + attn
    in_sequence = in_sequence + part(
        "mamba", ssm.Mamba2Mixer(cfg),
        part("attn_norm", llama.RMSNorm(cfg.rms_eps, cfg.dtype), in_sequence)
    ) * cfg.ssm_out_multiplier
    assert float(jnp.abs(in_sequence - h).max()) > 1e-4


def _moved(cfg, name):
    if "[" in name:
        field, at = name[:-1].split("[")
        values = list(getattr(cfg, field))
        values[int(at)] *= 1.5
        return cfg.replace(**{field: tuple(values)})
    return cfg.replace(**{name: getattr(cfg, name) * 1.5})


#: every multiplier of order one and no two alike (at the published values
#: some move a tiny model's float32 loss by less than its rounding)
LIVE = TINY.replace(
    embedding_multiplier=1.3, lm_head_multiplier=0.9, attention_in_multiplier=1.2,
    attention_out_multiplier=0.8, key_multiplier=1.4, ssm_in_multiplier=0.7,
    ssm_out_multiplier=1.1, ssm_multipliers=(0.6, 1.5, 0.75, 1.25, 0.85),
    mlp_multipliers=(1.35, 0.65))


@pytest.mark.parametrize("name", MULTIPLIERS)
def test_every_multiplier_is_live(name):
    """Each of the fourteen muP scalars, moved alone, changes the loss: none
    is dropped on the way from the configuration to the block."""
    _, variables, tokens = _variables()
    base = float(_loss(LIVE, variables, tokens))
    moved = float(_loss(_moved(LIVE, name), variables, tokens))
    assert abs(moved - base) > 1e-5 * abs(base), (name, base, moved)


def test_multipliers_of_one_trace_no_operation():
    """A model without multipliers traces the program it always did: the
    accepted cells' steps keep their operations."""
    plain = PRESETS["tiny-test"].replace(dtype=jnp.float32)
    tokens = jnp.zeros((1, 8), jnp.int32)
    model = LlamaForCausalLM(plain)
    variables = model.init({"params": jax.random.PRNGKey(0)}, tokens)
    ops = str(jax.make_jaxpr(lambda v: model.apply(v, tokens))(variables))
    ones = plain.replace(mlp_multipliers=(1.0, 1.0), key_multiplier=1.0,
                         embedding_multiplier=1.0, lm_head_multiplier=1.0)
    assert ops == str(jax.make_jaxpr(
        lambda v: LlamaForCausalLM(ones).apply(v, tokens))(variables))
    scaled = plain.replace(mlp_multipliers=(0.5, 1.0))
    assert ops.count(" mul ") < str(jax.make_jaxpr(
        lambda v: LlamaForCausalLM(scaled).apply(v, tokens))(variables)).count(" mul ")


@pytest.mark.parametrize("policy", ["full", "mlp", "none"])
def test_scanned_remat_stack_computes_the_unrolled_models_gradients(policy):
    """The mixer inside the scanned stack under a remat policy (its scan runs
    forward, again in the recompute, and its transpose on the way back) gives
    the unrolled, un-rematerialised model's loss and adapter gradients."""
    cfg = TINY.replace(remat_policy=policy)
    _, variables, tokens = _variables(cfg)
    seg = jnp.asarray(np.repeat([[1] * 10 + [2] * 14], 2, axis=0))

    def grads(c, v):
        return jax.value_and_grad(
            lambda lora: _loss(c, {"params": v["params"], "lora": lora}, tokens,
                               segment_ids=seg))(v["lora"])

    loss, got = grads(cfg, variables)
    flat = cfg.replace(scan_layers=False, remat=False)
    unrolled = {c: {**{k: v for k, v in variables[c].items() if k != "blocks"},
                    **{f"layer_{i}": jax.tree.map(lambda a: a[i],
                                                  variables[c]["blocks"]["block"])
                       for i in range(cfg.n_layers)}}
                for c in ("params", "lora")}
    want_loss, want = grads(flat, unrolled)
    np.testing.assert_allclose(loss, want_loss, rtol=1e-6)
    for i in range(cfg.n_layers):
        for a, b in zip(
                jax.tree.leaves(jax.tree.map(lambda t: t[i], got["blocks"]["block"])),
                jax.tree.leaves(want[f"layer_{i}"])):
            np.testing.assert_allclose(a, b, rtol=2e-4, atol=1e-9)


def test_every_mixer_leaf_is_frozen_but_both_projections_carry_adapters():
    _, variables, _ = _variables()
    mamba = variables["params"]["blocks"]["block"]["mamba"]
    assert {k: sorted(v) for k, v in mamba.items()} == {
        "A_log": ["bias"], "D": ["scale"], "dt_bias": ["bias"],
        "conv1d": ["bias", "kernel"], "in_proj": ["kernel"], "norm": ["scale"],
        "out_proj": ["kernel"]}
    assert mamba["in_proj"]["kernel"].shape == (2, 64, 64 + 64 + 2 * 2 * 8 + 4)
    assert mamba["conv1d"]["kernel"].shape == (2, 4, 64 + 2 * 2 * 8)
    assert sorted(variables["lora"]["blocks"]["block"]["mamba"]) == [
        "in_proj", "out_proj"]
    # the family's initialisation: A in [1, 16], step sizes in [0.001, 0.1]
    fresh = LlamaForCausalLM(TINY).init(
        {"params": jax.random.PRNGKey(3)}, jnp.zeros((1, 8), jnp.int32))
    fresh = fresh["params"]["blocks"]["block"]["mamba"]
    a = np.exp(np.asarray(fresh["A_log"]["bias"]))
    step = np.log1p(np.exp(np.asarray(fresh["dt_bias"]["bias"])))
    assert (a >= 1).all() and (a <= 16).all()
    assert (step >= 1e-3 * 0.999).all() and (step <= 1e-1 * 1.001).all()


def test_param_counts_know_the_mixer():
    _, variables, _ = _variables()
    held = sum(a.size for a in jax.tree.leaves(variables["params"]))
    assert TINY.param_count() == TINY.active_param_count() == held
    # the published widths, one layer: ISSUE 36's arithmetic
    big = llama.LlamaConfig(
        vocab_size=32640, d_model=5120, n_layers=9, n_heads=20, n_kv_heads=4,
        d_ff=21504, head_dim_override=128, ssm_n_heads=32,
        ssm_head_dim=128, ssm_d_state=256, ssm_n_groups=2)
    assert big._attention_params() == 31_457_280
    assert big._mixer_params() == 47_349_760 + 20_971_520 + 29_792 == 68_351_072
    assert big.param_count() == 9 * (31_457_280 + 68_351_072 + 330_301_440
                                     + 2 * 5120) + 2 * 32640 * 5120 + 5120
    assert big.param_count() == pytest.approx(4.205e9, rel=2e-4)


def test_decode_raises():
    model, variables, tokens = _variables()
    with pytest.raises(NotImplementedError, match="decode"):
        model.apply(variables, tokens, decode=True, mutable=["cache"])
    with pytest.raises(NotImplementedError, match="decode"):
        ssm.Mamba2Mixer(TINY).apply(
            {c: variables[c]["blocks"]["block"]["mamba"] for c in variables},
            jnp.zeros((1, 4, 64)), decode=True)


def test_mixer_stands_beside_grouped_query_attention_only():
    mla = PRESETS["tiny-mla-moe-test"].replace(
        ssm_n_heads=4, ssm_head_dim=16, ssm_d_state=8)
    with pytest.raises(ValueError, match="grouped-query"):
        LlamaForCausalLM(mla).init({"params": jax.random.PRNGKey(0)},
                                   jnp.zeros((1, 8), jnp.int32))
    with pytest.raises(ValueError, match="groups do not divide"):
        LlamaForCausalLM(TINY.replace(ssm_n_heads=3)).init(
            {"params": jax.random.PRNGKey(0)}, jnp.zeros((1, 8), jnp.int32))


def test_pipeline_stage_refuses_a_mixer():
    with pytest.raises(NotImplementedError, match="pipeline"):
        llama.make_block_stage_fn(TINY)


@pytest.mark.parametrize("axis", ["sp", "pp"])
def test_trainer_refuses_a_split_sequence_and_a_pipeline(axis, devices8):
    from finetune_controller_tpu.parallel.mesh import MeshSpec
    from finetune_controller_tpu.train.trainer import TrainConfig, Trainer

    mesh = MeshSpec(**{axis: 2}).build(devices8[:2])
    with pytest.raises(ValueError, match="sp = pp = 1"):
        Trainer(TINY, TrainConfig(mode="lora", batch_size=2, seq_len=16,
                                  total_steps=2), mesh=mesh)


def test_mixer_refuses_a_sequence_split_over_sp(devices8):
    from finetune_controller_tpu.parallel.mesh import MeshSpec
    from finetune_controller_tpu.parallel.ring import ring_mesh

    model, variables, tokens = _variables()
    with ring_mesh(MeshSpec(sp=2).build(devices8[:2])):
        with pytest.raises(NotImplementedError, match="sequence-parallel"):
            model.apply(variables, tokens)


def test_trainer_steps_under_tensor_parallelism_as_on_one_device(devices8):
    """The mixer's partition rules (projections over ``tp`` and ``fsdp``, the
    small leaves whole): two steps on a 2 x 2 mesh give one device's losses,
    and ``train-started`` carries the mixer's counters."""
    from finetune_controller_tpu.parallel.mesh import MeshSpec
    from finetune_controller_tpu.train.trainer import TrainConfig, Trainer

    def run(mesh):
        trainer = Trainer(TINY, TrainConfig(
            mode="lora", batch_size=4, seq_len=24, total_steps=4,
            learning_rate=0.01, warmup_steps=0, frozen_dtype="bfloat16",
            log_every=10**9, checkpoint_every=10**9), mesh=mesh)
        state = trainer.init_state()
        rng = np.random.default_rng(0)
        losses = []
        for _ in range(2):
            tokens = rng.integers(0, 256, (4, 24)).astype(np.int32)
            state, m = trainer.step(state, trainer._shard_batch(
                {"tokens": tokens, "loss_mask": np.ones((4, 24), np.float32),
                 "segment_ids": np.repeat([[1] * 9 + [2] * 15], 4, 0).astype(np.int32)}))
            losses.append(float(m["loss"]))
        return trainer, state, losses

    one, state, want = run(MeshSpec(fsdp=1).build(devices8[:1]))
    attrs = one._runtime_attrs()
    assert (attrs["ssm_layers"], attrs["ssm_chunks_per_row"],
            attrs["ssm_state_bytes_per_row"]) == (2, 3, 4 * 4 * 16 * 8)
    # the form the recurrence runs in: off a TPU the plain one, no block
    assert (attrs["ssm_scan_impl"], attrs["ssm_scan_heads_per_block"]) == ("xla", 0)
    mamba = state.frozen["params"]["blocks"]["block"]["mamba"]
    assert {mamba[k][leaf].dtype for k, leaf in (
        ("A_log", "bias"), ("D", "scale"), ("dt_bias", "bias"))} == {
            jnp.dtype(jnp.bfloat16)}
    _, _, got = run(MeshSpec(fsdp=2, tp=2).build(devices8[:4]))
    np.testing.assert_allclose(got, want, rtol=2e-5)
    assert got[1] != got[0]


def test_merged_export_refuses_a_mixer_before_writing(tmp_path):
    """No transformers layout is written for the hybrid block yet: the
    exporter refuses (the trainer then ships the adapter alone) instead of
    writing a Llama checkpoint that silently lacks the mixer."""
    from finetune_controller_tpu.models.hf_export import export_merged_checkpoint

    with pytest.raises(NotImplementedError, match="state-space mixer"):
        export_merged_checkpoint(TINY, {"params": {}}, tmp_path / "nope")
    assert not (tmp_path / "nope").exists()
