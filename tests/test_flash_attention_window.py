"""The flash kernels' second frontier and the sink (ISSUE 43), in interpreter
mode on the CPU: a static ``window`` (key ``s`` serves query ``t`` iff ``t -
window < s <= t``) and a per-head sink logit in all three kernels, against the
XLA form (``ops/attention.py``), against the plain reference's written-out
mask and concatenated column (``benchmarks/reference/mimo_v2.py``) — values
and the gradients of q, k, v AND the sink — the window's edge, the sink's
share, and the static count of what a window call computes over its need."""

import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))

from benchmarks.reference import mimo_v2 as ref  # noqa: E402
from finetune_controller_tpu.ops.attention import xla_causal_attention  # noqa: E402
from finetune_controller_tpu.ops.pallas import flash_attention as fa  # noqa: E402

BLOCK, BAND = 16, 8


@pytest.fixture(autouse=True)
def _small_bands(monkeypatch):
    """Bands of 8 in blocks of 16: the banded paths at toy sizes."""
    monkeypatch.setattr(fa, "DIAG_TILE", BAND)
    monkeypatch.setattr(fa, "WINDOW_LANES", BAND)


def _written_out(q, k, v, window, sink, segment_ids=None):
    """The reference's form: an explicit ``[S, S]`` mask, the sink one more
    column before a plain softmax, dropped after it."""
    b, s, h, d = q.shape
    group = h // k.shape[2]
    kk, vv = jnp.repeat(k, group, axis=2), jnp.repeat(v, group, axis=2)
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, kk) * d ** -0.5
    t = jnp.arange(s)
    mask = t[:, None] >= t[None, :]
    if window is not None:
        mask = mask & (t[:, None] - t[None, :] < window)
    mask = mask[None, None]
    if segment_ids is not None:
        mask = mask & (segment_ids[:, None, :, None] == segment_ids[:, None, None, :])
    probs = ref.attention_weights(
        scores, mask, None if sink is None else sink[None, :, None, None])
    return jnp.einsum("bhqk,bkhd->bqhd", probs, vv)


def _operands(s, h, hkv, d, dv, sink, batch=1, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    q = jax.random.normal(ks[0], (batch, s, h, d))
    k = jax.random.normal(ks[1], (batch, s, hkv, d))
    v = jax.random.normal(ks[2], (batch, s, hkv, dv))
    g = jax.random.normal(ks[3], (batch, s, h, dv))
    b = None if sink is None else sink + 0.5 * jax.random.normal(ks[4], (h,))
    return q, k, v, g, b


#: (rows, window, heads, key/value heads, q/k width, v width, sink's mean |
#: None, segments?)
CASES = {
    "window-under-a-band": (70, 3, 4, 2, 16, 16, 0.0, False),
    "window-a-band-ragged-tail": (37, 8, 4, 2, 16, 16, 0.0, False),
    "window-no-multiple-of-a-band-qk-24-v-16": (70, 11, 4, 2, 24, 16, 0.0, False),
    "window-a-block-sink-large": (70, 16, 4, 2, 16, 16, 8.0, False),
    "window-over-a-block-sink-small": (70, 21, 4, 2, 16, 16, -8.0, False),
    "window-over-two-blocks-group-of-8": (64, 40, 8, 1, 16, 16, 0.0, False),
    "rows-shorter-than-the-window-group-of-16": (5, 8, 16, 1, 16, 16, 0.0, False),
    "document-boundary-in-the-window": (70, 6, 4, 2, 16, 16, 0.0, True),
    "no-sink": (70, 5, 4, 2, 16, 16, None, False),
    "sink-and-segments-without-a-window": (70, None, 4, 2, 24, 16, 1.0, True),
}


@pytest.mark.parametrize("case", list(CASES))
def test_window_kernels_are_the_xla_form_and_the_written_out_mask(case):
    s, window, h, hkv, d, dv, sink, segments = CASES[case]
    q, k, v, g, b = _operands(s, h, hkv, d, dv, sink)
    seg = None
    if segments:
        seg = jnp.asarray(np.repeat([0, 1, 2], [s // 3, 3, s - s // 3 - 3])[None])

    def kernels(q, k, v, b):
        return fa.flash_attention(q, k, v, segment_ids=seg, sink=b, window=window,
                                  block_q=BLOCK, block_k=BLOCK, interpret=True)

    def xla(q, k, v, b):
        return xla_causal_attention(q, k, v, segment_ids=seg, window=window, sink=b)

    def written(q, k, v, b):
        return _written_out(q, k, v, window, b, seg)

    args = (q, k, v, b)
    wrt = (0, 1, 2, 3) if b is not None else (0, 1, 2)

    def value_and_grads(form):
        out, vjp = jax.vjp(form, *args)
        return out, vjp(g)[:len(wrt)]

    want, want_grads = value_and_grads(written)
    for form in (kernels, xla):
        out, grads = value_and_grads(form)
        np.testing.assert_allclose(out, want, atol=2e-5, err_msg=form.__name__)
        for name, got, exp in zip(("dq", "dk", "dv", "dsink"), grads, want_grads):
            np.testing.assert_allclose(
                got, exp, atol=5e-5, err_msg=f"{form.__name__} {name}")
            assert float(jnp.abs(exp).max()) > 0, name


def test_the_logsumexp_holds_the_sink_and_its_cotangent_reaches_it():
    """``lse = log(sum_s exp(s_ts) + exp(b_h))``, and a cotangent on it flows
    into the sink as into the scores (the ring path's merge reads ``lse``)."""
    q, k, v, g, b = _operands(40, 4, 2, 16, 16, 0.5)

    def lse_sum(q, k, b, form):
        if form == "kernels":
            _, lse = fa.flash_attention_with_lse(
                q, k, v, sink=b, window=5, block_q=BLOCK, block_k=BLOCK,
                interpret=True)
            return (lse[..., 0] * jnp.cos(jnp.arange(40.0))).sum()
        kk = jnp.repeat(k, 2, axis=2)
        scores = jnp.einsum("bqhd,bkhd->bhqk", q, kk) * 16 ** -0.5
        t = jnp.arange(40)
        mask = (t[:, None] >= t[None, :]) & (t[:, None] - t[None, :] < 5)
        scores = jnp.where(mask, scores, -jnp.inf)
        lse = jax.nn.logsumexp(jnp.concatenate(
            [scores, jnp.broadcast_to(b[None, :, None, None], scores.shape[:-1] + (1,))],
            axis=-1), axis=-1)
        return (lse * jnp.cos(jnp.arange(40.0))).sum()

    got = jax.value_and_grad(lse_sum, (0, 1, 2))(q, k, b, "kernels")
    want = jax.value_and_grad(lse_sum, (0, 1, 2))(q, k, b, "plain")
    for a, e in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_allclose(a, e, rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("form", ["kernels", "xla"])
def test_a_rows_weights_sum_to_one_less_the_sinks_share(form):
    """With every value 1 the output IS the sum of a row's weights: ``1 -
    p_sink``, and row 0 (one key, its own) reads ``1 - sigmoid(b_h - s_00)``."""
    q, k, _, _, b = _operands(40, 4, 2, 16, 16, 0.0)
    v = jnp.ones((1, 40, 2, 16))
    if form == "kernels":
        out = fa.flash_attention(q, k, v, sink=b, window=5, block_q=BLOCK,
                                 block_k=BLOCK, interpret=True)
    else:
        out = xla_causal_attention(q, k, v, window=5, sink=b)
    kk = jnp.repeat(k, 2, axis=2)
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, kk) * 16 ** -0.5
    t = jnp.arange(40)
    mask = (t[:, None] >= t[None, :]) & (t[:, None] - t[None, :] < 5)
    lse = jax.nn.logsumexp(jnp.where(mask, scores, -jnp.inf), axis=-1)  # (b,h,s)
    p_sink = jax.nn.sigmoid(b[None, :, None] - lse)
    np.testing.assert_allclose(out[..., 0], jnp.moveaxis(1 - p_sink, 1, 2), atol=2e-6)
    s00 = scores[:, :, 0, 0]
    np.testing.assert_allclose(out[:, 0, :, 0], 1 - jax.nn.sigmoid(b[None] - s00),
                               atol=2e-6)
    assert float(out.max()) < 1.0


@pytest.mark.parametrize("form", ["kernels", "xla"])
def test_the_windows_edge(form):
    """Moving key ``t - window`` changes nothing at query ``t``; moving key
    ``t - window + 1`` does; without a window both do."""
    window, t = 11, 37
    q, k, v, _, b = _operands(48, 4, 2, 16, 16, 0.0, batch=1)

    def out(k, v, window):
        if form == "kernels":
            return fa.flash_attention(q, k, v, sink=b, window=window, block_q=BLOCK,
                                      block_k=BLOCK, interpret=True)[0, t]
        return xla_causal_attention(q, k, v, window=window, sink=b)[0, t]

    def moved(at):
        return k.at[0, at].add(1.0), v.at[0, at].add(1.0)

    for w, outside in ((window, True), (None, False)):
        base = out(k, v, w)
        just_out = float(jnp.abs(out(*moved(t - window), w) - base).max())
        just_in = float(jnp.abs(out(*moved(t - window + 1), w) - base).max())
        assert just_in > 1e-4
        assert (just_out == 0.0) if outside else (just_out > 1e-4)
    # and no key after the query, window or not
    assert float(jnp.abs(out(*moved(t + 1), window) - out(k, v, window)).max()) == 0.0


def test_a_window_call_refuses_what_it_does_not_compute():
    q, k, v, _, _ = _operands(32, 4, 2, 16, 16, None)
    with pytest.raises(ValueError, match="window"):
        fa.flash_attention(q, k, v, window=5, block_q=16, block_k=8, interpret=True)
    with pytest.raises(ValueError, match="window"):
        fa.flash_attention(q, k, v, window=0, block_q=16, block_k=16, interpret=True)
    with pytest.raises(ValueError, match="window"):
        fa.flash_attention(q, k, v, window=5, block_q=16, block_k=16, interpret=True,
                           selection=jnp.zeros((1, 32, 128), jnp.int32))
    from finetune_controller_tpu.ops.attention import causal_attention

    for impl in ("ring", "ulysses"):
        # plain attention without an sp axis; with one they refuse
        causal_attention(q, k, v, impl=impl, window=5)


def test_sequence_parallel_paths_refuse_a_window_and_a_sink(devices8):
    from jax.sharding import Mesh

    from finetune_controller_tpu.ops.attention import causal_attention
    from finetune_controller_tpu.parallel.ring import ring_mesh

    q, k, v, _, b = _operands(32, 4, 2, 16, 16, 0.0)
    mesh = Mesh(np.asarray(devices8[:2]).reshape(2), ("sp",))
    with ring_mesh(mesh):
        for impl in ("ring", "ulysses", "xla"):
            with pytest.raises(NotImplementedError, match="window"):
                causal_attention(q, k, v, impl=impl, window=5)
            with pytest.raises(NotImplementedError, match="sink"):
                causal_attention(q, k, v, impl=impl, sink=b)


def test_window_work_over_need(monkeypatch):
    """At the published window of 128 a band of 128 meets its own diagonal
    tile and the one the trailing edge crosses: twice the need, where a 1,024
    block in bands of 256 computes four times it and whole blocks sixteen."""
    monkeypatch.setattr(fa, "DIAG_TILE", 256)
    monkeypatch.setattr(fa, "WINDOW_LANES", 128)
    need = sum(min(t + 1, 128) for t in range(16384))
    assert need == 2_089_024
    assert need / (16384 * 16384 / 2) == pytest.approx(0.0156, abs=1e-4)
    got = fa.window_work_over_need(16384, 128, head_widths=(192, 128))
    assert got == pytest.approx(2.0, abs=1e-3)
    assert got == fa.window_work_over_need(16384, 128, 1024)
    # the count follows the band: 256-wide bands, then whole 1,024 blocks
    monkeypatch.setattr(fa, "WINDOW_LANES", 256)
    assert fa.window_work_over_need(16384, 128, 1024) == pytest.approx(4.0, abs=0.03)
    monkeypatch.setattr(fa, "WINDOW_LANES", 1024)
    monkeypatch.setattr(fa, "DIAG_TILE", 1024)
    assert fa.window_work_over_need(16384, 128, 1024) == pytest.approx(15.6, abs=0.2)
    # a window as long as the rows is the causal triangle, computed whole
    monkeypatch.setattr(fa, "DIAG_TILE", 256)
    monkeypatch.setattr(fa, "WINDOW_LANES", 128)
    assert fa.window_work_over_need(2048, 2048, 1024) == pytest.approx(
        fa.causal_work_over_need(2048, 1024, 1024), rel=1e-3)


def test_tile_spans_cover_exactly_the_pairs_the_window_admits():
    """Every admitted pair lies in exactly one span, under its mask; no span
    holds only pairs the window excludes."""
    for window in (1, 3, 8, 11, 16, 17, 33):
        t, block = fa._window_band(window, BLOCK), BLOCK
        for back in range(fa._window_blocks_back(window, block) + 1):
            for key_bands in (False, True):
                covered = np.zeros((block, block), int)
                for i, spans in fa._window_tile_spans(
                        t, block, back * block, window, key_bands):
                    for first, stop, lower, upper in spans:
                        band = np.arange(i * t, (i + 1) * t)
                        other = np.arange(first * t, stop * t)
                        rows, keys = (other, band) if key_bands else (band, other)
                        e = (rows[:, None] % t) - (keys[None, :] % t)
                        ok = np.ones(e.shape, bool)
                        if lower is not None:
                            ok &= e >= lower
                        if upper is not None:
                            ok &= e <= upper
                        assert ok.any()
                        covered[np.ix_(rows, keys)] += ok
                diff = back * block + np.arange(block)[:, None] - np.arange(block)[None, :]
                np.testing.assert_array_equal(
                    covered, ((diff >= 0) & (diff < window)).astype(int))
