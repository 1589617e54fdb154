"""The Mamba-2 state-space mixer, as ``models/moe.py`` is to the expert
layer: of a hybrid block (``models/llama.py``: ``Block`` runs it BESIDE
attention under one norm, with the family's muP multipliers) and, ALONE under
its own norm, of an ``M`` layer of a pattern model (``Block(kind="M")``: no
multiplier, so none of the products by one below is traced).

For a row ``u`` of the block's normed input::

    [z | xBC | dt] = in_proj(u * in_multiplier) * mu      # d_inner | d_inner + 2GN | H
    xBC = silu(conv(xBC) + b)                             # causal, depthwise, width d_conv
    x, B, C = split(xBC)                                  # H heads of P | G groups of N, twice
    delta = softplus(dt + dt_bias),  A = -exp(A_log)      # a head, float32
    S_t = exp(delta_t A) S_{t-1} + delta_t x_t (x) B_t    # a head: a P x N state
    y_t = S_t C_t + D x_t
    out = out_proj(RMSNorm_grouped(y * silu(z)))          # gate first, then the norm

``mu`` is the family's per-segment muP vector (one multiplier each for z, x,
B, C, dt); head ``i`` reads group ``i // (H / G)`` of B and C.

**The recurrence runs in its chunked (SSD) form**: within a chunk of ``Q``
rows the masked product ``(L o C B^T)(delta x)`` with ``L[t, s] = exp(sum_{s <
r <= t} delta_r A)``, across chunks the ``P x N`` states, each carried by its
chunk's total decay.  Decays, cumulative sums and the carried states are
float32; the products take their operands in the compute type and accumulate
in float32, as every projection does.  A masked exponent is set to ``-inf``
BEFORE ``exp`` (a pair ``s > t`` has a positive exponent that may overflow:
zeroing it afterwards would give ``inf * 0``).  **Where it runs**: the mixer
calls ``ops/pallas/ssd_scan.py::ssd_scan``, which chooses — on a TPU, with no
mesh of several devices and shapes that tile, two Pallas kernels (one walks a
row's chunks with the states in VMEM scratch, one walks them in reverse for
the backward pass: no decay, score or chunk state reaches HBM); everywhere
else :func:`ssd_chunked` below, the same arithmetic in plain ``jnp`` with a
``lax.scan`` over the chunks, which is also what the tests hold the kernels
to.

**Packed rows restart.**  ``segment_ids`` are read as runs: at a change of id
a new document begins, the decay into it is zero (no state crosses) and the
convolution sees zeros for the rows before it.  (The published implementation
carries state and convolution across packed documents; ``data/loader.py`` packs
documents and attention already keeps them apart, so the mixer does too.)

Leaves, under the module's name (``mamba`` in a block): ``in_proj`` and
``out_proj`` are ``LoRADense`` (adapters where the job targets them);
``conv1d/{kernel (d_conv, channels), bias}``; ``A_log/bias``, ``dt_bias/bias``
and ``D/scale`` — one-parameter modules, a head each, stored like any frozen
leaf (bf16 under a LoRA job) and used in float32; ``norm/scale``.
``A_log`` starts at ``log`` of a draw from [1, 16] and ``dt_bias`` at the
inverse softplus of a step size drawn log-uniformly from [0.001, 0.1], the
family's initialisation: decays near 1, a state that crosses many chunks.

Training and evaluation only: ``decode=True`` raises (serving needs a state
cache beside keys and values, ``ROADMAP.md`` B13), and so does a sequence
split over ``sp`` (a scan over a split sequence needs a state hand-off).

**Lightning linear attention is the same recurrence** (:class:`LightningMixer`,
an ``L`` layer's mixer): ``S_t = lambda_h S_{t-1} + k_t^T v_t``, ``o_t = q_t
S_t`` is ``x`` = v, ``B`` = k, ``C`` = q at a step size of 1, ``A_h = log
lambda_h``, ``D`` = 0 — with every head its own ``B`` and ``C`` (``G = H``) and
a decay fixed by the head's place, not learned.
"""

from __future__ import annotations

import math
from typing import Any

import flax.linen as nn
import jax
import jax.numpy as jnp

from ..ops.pallas.ssd_scan import ssd_scan, ssd_scan_impl
from .llama import (_Leaves, _head_norm, _proj, gated_output,
                    plain_inv_freqs, rotate_columns, RMSNorm, times)


def run_description(cfg, seq_len: int) -> dict:
    """What a model with state-space mixers says of them at ``train-started``:
    how many, the chain of chunk states a row's scan walks in each, the
    float32 state a row carries, and the form the recurrence runs in under
    the mesh in scope (the Pallas kernels | the plain ``jnp`` one) with the
    heads a step of the kernels' grid holds."""
    impl, heads = ssd_scan_impl(cfg.ssm_n_heads, cfg.ssm_head_dim,
                                cfg.ssm_n_groups, cfg.ssm_d_state, cfg.ssm_chunk)
    return {
        "ssm_layers": (cfg.layer_pattern.count("M") if cfg.layer_pattern
                       else cfg.n_layers),
        "ssm_chunks_per_row": -(-seq_len // cfg.ssm_chunk),
        "ssm_state_bytes_per_row": (
            4 * cfg.ssm_n_heads * cfg.ssm_head_dim * cfg.ssm_d_state),
        "ssm_scan_impl": impl,
        "ssm_scan_heads_per_block": heads,
    }


def lightning_log_decay(heads: int) -> jax.Array:
    """``log lambda_h = -2^(-8 h / H)``, ``h = 1 .. H``: Lightning
    Attention-2's slopes, the same in every layer; float32."""
    return -jnp.exp2(-8.0 * jnp.arange(1, heads + 1, dtype=jnp.float32) / heads)


def lightning_run_description(cfg, seq_len: int) -> dict:
    """What a model with lightning layers says of their scan at
    ``train-started``: its form under the mesh in scope, the heads a step of
    the kernels' grid holds, the chain of chunk states a row walks."""
    impl, heads = ssd_scan_impl(
        cfg.lightning_n_heads, cfg.lightning_head_dim, cfg.lightning_n_heads,
        cfg.lightning_head_dim, cfg.ssm_chunk)
    return {"lightning_scan_impl": impl, "lightning_heads_per_block": heads,
            "lightning_chunks_per_row": -(-seq_len // cfg.ssm_chunk)}


def _refuse_split_sequence(what: str) -> None:
    """Raise under a mesh whose ``sp`` axis splits the rows."""
    from ..parallel.ring import get_ring_mesh

    mesh = get_ring_mesh()
    if mesh is not None and mesh.shape.get("sp", 1) > 1:
        raise NotImplementedError(
            f"{what} has no sequence-parallel path: a scan over a split "
            "sequence needs a state hand-off (ROADMAP.md B13)")


def document_runs(segment_ids: jax.Array) -> jax.Array:
    """``(B, S)`` ids -> the index of the run each row lies in: 0 for a row's
    first document, one more at every change of id (non-decreasing along a
    row, whatever the ids are)."""
    changed = segment_ids[:, 1:] != segment_ids[:, :-1]
    return jnp.pad(jnp.cumsum(changed.astype(jnp.int32), axis=1), ((0, 0), (1, 0)))


def causal_conv(x, kernel, bias, runs=None):
    """Depthwise causal convolution along the sequence: ``y_t = bias +
    sum_j kernel[K - 1 - j] * x_{t - j}``, ``x: (B, S, C)``, ``kernel: (K,
    C)``, in float32.  With ``runs`` (:func:`document_runs`) a row of another
    document counts as zero."""
    k = kernel.shape[0]
    s = x.shape[1]
    x32 = x.astype(jnp.float32)
    w = kernel.astype(jnp.float32)
    out = x32 * w[k - 1] + bias.astype(jnp.float32)
    for j in range(1, k):
        shifted = jnp.pad(x32, ((0, 0), (j, 0), (0, 0)))[:, :s]
        if runs is not None:
            same = jnp.pad(runs, ((0, 0), (j, 0)), constant_values=-1)[:, :s] == runs
            shifted = jnp.where(same[..., None], shifted, 0.0)
        out = out + shifted * w[k - 1 - j]
    return out


def _exp_where(keep, exponent):
    """``exp(exponent)`` where ``keep`` (None = everywhere), exactly 0
    elsewhere: the excluded exponent never reaches ``exp``."""
    if keep is None:
        return jnp.exp(exponent)
    return jnp.exp(jnp.where(keep, exponent, -jnp.inf))


def ssd_chunked(x, dt, a, b, c, d, runs=None, *, chunk: int):
    """The state-space recurrence in chunks of ``chunk`` rows.

    ``x: (B, S, H, P)``, ``b``, ``c: (B, S, G, N)`` in the compute type; ``dt:
    (B, S, H)`` (after softplus), ``a: (H,)`` (negative), ``d: (H,)`` float32;
    ``runs: (B, S)`` non-decreasing document indices or None.  Returns ``y:
    (B, S, H, P)`` float32.  ``S`` need not be a multiple of ``chunk``: the
    tail is padded with rows of step size zero, which neither decay nor feed
    the state."""
    bsz, s, h, p = x.shape
    g, n = b.shape[2:]
    hg = h // g
    dtype = x.dtype
    pad = -s % chunk
    if pad:
        x, dt, b, c = (jnp.pad(t, ((0, 0), (0, pad)) + ((0, 0),) * (t.ndim - 2))
                       for t in (x, dt, b, c))
        if runs is not None:
            runs = jnp.pad(runs, ((0, 0), (0, pad)), mode="edge")
    nc = (s + pad) // chunk
    xc = x.reshape(bsz, nc, chunk, g, hg, p)
    bc = b.reshape(bsz, nc, chunk, g, n)
    cc = c.reshape(bsz, nc, chunk, g, n)
    dtc = dt.astype(jnp.float32).reshape(bsz, nc, chunk, g, hg)
    # log-decay of a row, summed along its chunk: cs[t] = sum_{r <= t} dt_r A
    cs = jnp.cumsum(dtc * a.astype(jnp.float32).reshape(g, hg), axis=2)
    total = cs[:, :, -1]                                       # (B, nc, G, hg)

    at = jnp.arange(chunk)
    within = (at[:, None] >= at[None, :])[None, None]          # (1, 1, Q, Q)
    to_end = from_before = carried = None
    if runs is not None:
        rc = runs.reshape(bsz, nc, chunk)
        within = within & (rc[:, :, :, None] == rc[:, :, None, :])
        end = rc[:, :, -1]
        # the document the state entering a chunk belongs to (the first
        # chunk's is empty: any index serves)
        before = jnp.concatenate([rc[:, :1, 0], end[:, :-1]], axis=1)
        to_end = (rc == end[..., None])[..., None, None]       # row -> chunk's end
        from_before = (rc == before[..., None])[..., None, None]
        # runs never decrease: the ends agree only if the whole chunk does
        carried = (end == before)[..., None, None]

    # ---- within a chunk: (L o C B^T)(dt x) ---------------------------------
    decay = _exp_where(within[..., None, None],
                       cs[:, :, :, None] - cs[:, :, None, :])  # (B, nc, Q, Q, G, hg)
    scores = jnp.einsum("bcqgn,bcsgn->bcqsg", cc, bc,
                        preferred_element_type=jnp.float32)
    fed = xc.astype(jnp.float32) * dtc[..., None]              # dt x, float32
    y = jnp.einsum("bcqsgj,bcsgjp->bcqgjp",
                   (scores[..., None] * decay).astype(dtype), fed.astype(dtype),
                   preferred_element_type=jnp.float32)

    # ---- a chunk's own state at its end, then the carry across chunks ------
    reach = _exp_where(to_end, total[:, :, None] - cs)         # (B, nc, Q, G, hg)
    own = jnp.einsum("bcsgn,bcsgjp->bcgjpn", bc,
                     (fed * reach[..., None]).astype(dtype),
                     preferred_element_type=jnp.float32)
    through = _exp_where(carried, total)                       # (B, nc, G, hg)

    def carry(state, chunk_in):
        kept, added = chunk_in
        return state * kept[..., None, None] + added, state

    _, entering = jax.lax.scan(
        carry, jnp.zeros((bsz, g, hg, p, n), jnp.float32),
        (jnp.moveaxis(through, 1, 0), jnp.moveaxis(own, 1, 0)))
    entering = jnp.moveaxis(entering, 0, 1)                    # (B, nc, G, hg, P, N)

    # ---- the state read: what entered the chunk, decayed to each row -------
    read = jnp.einsum("bcqgn,bcgjpn->bcqgjp", cc, entering.astype(dtype),
                      preferred_element_type=jnp.float32)
    y = y + read * _exp_where(from_before, cs)[..., None]
    y = y + xc.astype(jnp.float32) * d.astype(jnp.float32).reshape(g, hg, 1)
    return y.reshape(bsz, nc * chunk, h, p)[:, :s]


def gated_group_norm(y, z, scale, groups: int, eps: float):
    """``RMSNorm(y * silu(z))`` over each of ``groups`` runs of channels, times
    ``scale``; float32 in, float32 out."""
    gated = y * jax.nn.silu(z.astype(jnp.float32))
    shape = gated.shape
    grouped = gated.reshape(shape[:-1] + (groups, shape[-1] // groups))
    normed = grouped * jax.lax.rsqrt(
        jnp.mean(grouped * grouped, axis=-1, keepdims=True) + eps)
    return normed.reshape(shape) * scale.astype(jnp.float32)


def _a_log_init(key, shape, dtype):
    return jnp.log(jax.random.uniform(key, shape, jnp.float32, 1.0, 16.0)).astype(dtype)


def _dt_bias_init(key, shape, dtype):
    step = jnp.exp(jax.random.uniform(
        key, shape, jnp.float32, math.log(1e-3), math.log(1e-1)))
    return (step + jnp.log(-jnp.expm1(-step))).astype(dtype)   # softplus^-1


class Mamba2Mixer(nn.Module):
    """The mixer; ``cfg``: a ``LlamaConfig`` with ``ssm_n_heads`` set."""

    cfg: Any

    @nn.compact
    def __call__(self, u, segment_ids=None, deterministic=True, decode=False,
                 adapter_ids=None):
        cfg = self.cfg
        if decode:
            raise NotImplementedError(
                "the state-space mixer has no decode path yet: serving it "
                "needs a state cache beside keys and values (ROADMAP.md B13); "
                "train and evaluate only")
        _refuse_split_sequence("the state-space mixer")
        bsz, s, _ = u.shape
        h, p = cfg.ssm_n_heads, cfg.ssm_head_dim
        g, n = cfg.ssm_n_groups, cfg.ssm_d_state
        inner, gn = cfg.ssm_d_inner, g * n
        if h % g:
            raise ValueError(f"{g} groups do not divide the mixer's {h} heads")

        def leaves(name, **shapes):
            return _Leaves(tuple((leaf, shape, init) for leaf, (shape, init)
                                 in shapes.items()), cfg.param_dtype, name=name)()

        zxbcdt = _proj(cfg, "in_proj", 2 * inner + 2 * gn + h)(
            times(u, cfg.ssm_in_multiplier), deterministic, adapter_ids)
        if any(m != 1.0 for m in cfg.ssm_multipliers):
            mz, mx, mb, mc, mdt = cfg.ssm_multipliers
            mu = jnp.concatenate([
                jnp.full((width,), m, jnp.float32) for width, m in
                ((inner, mz), (inner, mx), (gn, mb), (gn, mc), (h, mdt))])
            zxbcdt = (zxbcdt.astype(jnp.float32) * mu).astype(zxbcdt.dtype)
        z = zxbcdt[..., :inner]
        xbc = zxbcdt[..., inner:2 * inner + 2 * gn]
        dt = zxbcdt[..., 2 * inner + 2 * gn:]
        runs = None if segment_ids is None else document_runs(segment_ids)

        with jax.named_scope("ssm_conv"):
            conv = leaves(
                "conv1d",
                kernel=((cfg.ssm_d_conv, inner + 2 * gn), nn.initializers.lecun_normal()),
                bias=((inner + 2 * gn,), nn.initializers.zeros_init()))
            xbc = jax.nn.silu(causal_conv(
                xbc, conv["kernel"], conv["bias"], runs)).astype(cfg.dtype)
        x = xbc[..., :inner].reshape(bsz, s, h, p)
        b = xbc[..., inner:inner + gn].reshape(bsz, s, g, n)
        c = xbc[..., inner + gn:].reshape(bsz, s, g, n)

        with jax.named_scope("ssd_scan"):
            a_log = leaves("A_log", bias=((h,), _a_log_init))["bias"]
            dt_bias = leaves("dt_bias", bias=((h,), _dt_bias_init))["bias"]
            skip = leaves("D", scale=((h,), nn.initializers.ones_init()))["scale"]
            delta = jax.nn.softplus(
                dt.astype(jnp.float32) + dt_bias.astype(jnp.float32))
            y = ssd_scan(x, delta, -jnp.exp(a_log.astype(jnp.float32)), b, c,
                         skip, runs, chunk=cfg.ssm_chunk)

        with jax.named_scope("ssm_gate_norm"):
            scale = leaves("norm", scale=((inner,), nn.initializers.ones_init()))["scale"]
            y = gated_group_norm(y.reshape(bsz, s, inner), z, scale, g,
                                 cfg.rms_eps).astype(cfg.dtype)
        return _proj(cfg, "out_proj", cfg.d_model)(y, deterministic, adapter_ids)


class LightningMixer(nn.Module):
    """An ``L`` layer's mixer, lightning linear attention: ``q, k, v = W x``
    (``lightning_n_heads`` heads of ``lightning_head_dim`` each, every head
    its own keys), a learned RMSNorm over each q and k head, half-split
    rotary embedding at ``rope_theta`` on all of a head's columns, then
    ``S_t = lambda_h S_{t-1} + k_t^T v_t``, ``o_t = head_dim^-0.5 q_t S_t``
    with the state float32 and ``lambda_h`` fixed
    (:func:`lightning_log_decay`) — :func:`ssd_scan` at ``x`` = v, ``B`` = k,
    ``C`` = q, a step size of 1 and ``D`` = 0 —, a learned RMSNorm over all of
    ``o``'s channels (``o_norm``), the output gate and ``o_proj``
    (``models/llama.py::gated_output``).  The state restarts at a change of
    ``segment_ids``.  Training and evaluation only."""

    cfg: Any

    @nn.compact
    def __call__(self, u, positions, segment_ids=None, deterministic=True,
                 decode=False, adapter_ids=None):
        cfg = self.cfg
        if decode:
            raise NotImplementedError(
                "lightning attention has no decode path yet: serving it needs "
                "a state cache beside keys and values (ROADMAP.md B13); train "
                "and evaluate only")
        _refuse_split_sequence("lightning attention")
        bsz, s, _ = u.shape
        h, p = cfg.lightning_n_heads, cfg.lightning_head_dim

        def heads(name):
            return _proj(cfg, name, h * p)(u, deterministic, adapter_ids).reshape(
                bsz, s, h, p)

        q, k, v = heads("q_proj"), heads("k_proj"), heads("v_proj")
        q, k = _head_norm(cfg, "q_norm")(q), _head_norm(cfg, "k_norm")(k)
        if cfg.rope_theta:
            with jax.named_scope("rope"):
                inv_freqs = plain_inv_freqs(cfg.rope_theta, p // 2)
                q = rotate_columns(q, positions, inv_freqs, 0, p, False)
                k = rotate_columns(k, positions, inv_freqs, 0, p, False)
        runs = None if segment_ids is None else document_runs(segment_ids)
        with jax.named_scope("ssd_scan"):
            y = ssd_scan(v, jnp.ones((bsz, s, h), jnp.float32),
                         lightning_log_decay(h), k, q,
                         jnp.zeros((h,), jnp.float32), runs, chunk=cfg.ssm_chunk)
            y = (y * p ** -0.5).reshape(bsz, s, h * p)
        y = RMSNorm(cfg.rms_eps, cfg.dtype, cfg.param_dtype, cfg.norm_offset,
                    name="o_norm")(y)
        return gated_output(cfg, y.astype(cfg.dtype), u, deterministic,
                            adapter_ids)
