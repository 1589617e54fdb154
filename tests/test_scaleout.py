"""Tests for scale-out compute: Pallas flash attention, ring attention (SP),
MoE expert parallelism — the strategies SURVEY.md §2.3 lists as greenfield
obligations (SP/CP, EP) plus the hand-written kernel path.

All run on the 8-virtual-device CPU mesh (Pallas in interpreter mode).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from finetune_controller_tpu.models.llama import PRESETS, LlamaForCausalLM
from finetune_controller_tpu.ops.attention import causal_attention, xla_causal_attention
from finetune_controller_tpu.ops.pallas.flash_attention import flash_attention
from finetune_controller_tpu.parallel.mesh import MeshSpec
from finetune_controller_tpu.parallel.ring import ring_attention_sharded, ring_mesh
from finetune_controller_tpu.parallel.sharding import LLAMA_RULES


def _qkv(b=2, s=64, h=4, hkv=2, d=16, dtype=jnp.float32, dv=None):
    q = jax.random.normal(jax.random.PRNGKey(0), (b, s, h, d), dtype)
    k = jax.random.normal(jax.random.PRNGKey(1), (b, s, hkv, d), dtype)
    v = jax.random.normal(jax.random.PRNGKey(2), (b, s, hkv, dv or d), dtype)
    return q, k, v


#: q/k and v head sizes: equal (every dense preset) and latent attention's
#: uneven pair (q/k = nope + rope wider than v), at toy size
HEAD_SIZES = pytest.mark.parametrize(
    "d,dv", [(16, 16), (24, 16)], ids=["equal", "qk24_v16"])


# ---------------------------------------------------------------------------
# Pallas flash attention
# ---------------------------------------------------------------------------


@HEAD_SIZES
def test_flash_attention_matches_xla(d, dv):
    q, k, v = _qkv(d=d, dv=dv)
    seg = (jnp.arange(64)[None, :] // 32).astype(jnp.int32).repeat(2, 0)
    ref = xla_causal_attention(q, k, v, segment_ids=seg)
    out = flash_attention(q, k, v, segment_ids=seg, block_q=16, block_k=16)
    np.testing.assert_allclose(out, ref, atol=1e-5)


@HEAD_SIZES
def test_flash_attention_grads_match_xla(d, dv):
    q, k, v = _qkv(s=32, d=d, dv=dv)

    def loss_flash(q, k, v):
        return (flash_attention(q, k, v, block_q=8, block_k=8) ** 2).sum()

    def loss_ref(q, k, v):
        return (xla_causal_attention(q, k, v) ** 2).sum()

    g1 = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    g2 = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g1, g2):
        np.testing.assert_allclose(a, b, atol=1e-4)


@HEAD_SIZES
def test_flash_attention_grads_match_xla_gqa_segments_uneven(d, dv):
    """Pallas backward (dQ + dK/dV kernels) vs XLA autodiff with everything
    turned on at once: GQA group reduction, segment masks, ragged tail block."""
    q, k, v = _qkv(s=40, d=d, dv=dv)
    seg = (jnp.arange(40)[None, :] // 20).astype(jnp.int32).repeat(2, 0)

    def loss_flash(q, k, v):
        out = flash_attention(q, k, v, segment_ids=seg, block_q=16, block_k=16)
        return (out ** 2).sum()

    def loss_ref(q, k, v):
        return (xla_causal_attention(q, k, v, segment_ids=seg) ** 2).sum()

    g1 = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    g2 = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g1, g2):
        np.testing.assert_allclose(a, b, atol=1e-4)


def test_flash_attention_uneven_blocks():
    # S=48 with block 32: remainder block exercises the causal frontier math
    q, k, v = _qkv(s=48)
    ref = xla_causal_attention(q, k, v)
    out = flash_attention(q, k, v, block_q=32, block_k=32)
    np.testing.assert_allclose(out, ref, atol=1e-5)


def _masked_reference(q, k, v, seg, kv_seg):
    """Plain causal attention under query / key segment ids that may differ:
    a row no key answers gives zeros (the kernels' masked-row rule)."""
    b, s, h, d = q.shape
    hkv = k.shape[2]
    qr = q.reshape(b, s, hkv, h // hkv, d) * d ** -0.5
    scores = jnp.einsum("bskgd,btkd->bkgst", qr, k).astype(jnp.float32)
    pos = jnp.arange(s)
    mask = (pos[:, None] >= pos[None, :]) & (seg[:, :, None] == kv_seg[:, None, :])
    mask = mask[:, None, None]
    scores = jnp.where(mask, scores, -1e30)
    p = jnp.exp(scores - scores.max(-1, keepdims=True)) * mask
    p = p / jnp.maximum(p.sum(-1, keepdims=True), 1e-30)
    return jnp.einsum("bkgst,btkd->bskgd", p, v).reshape(b, s, h, v.shape[-1])


def _segments(s, *bounds, b=2):
    """(b, s) segment ids 0, 1, … changing at each of ``bounds``."""
    ids = sum((jnp.arange(s) >= at).astype(jnp.int32) for at in bounds)
    return jnp.broadcast_to(ids, (b, s))


#: name -> (shape of _qkv, blocks, segment boundaries, sub-tiles a side of a
#: diagonal block at DIAG_TILE = 8; 1 = today's whole-block masked path)
DIAGONAL_CASES = {
    # block 4 x the sub-tile, three blocks a side, one kv head for four q heads
    "subtiled-gqa4": (dict(s=96, h=4, hkv=1), (32, 32), (), 4),
    # latent attention's heads: the 192-wide contraction stays whole
    "subtiled-qk192-v128": (dict(s=64, h=2, hkv=2, d=192, dv=128), (32, 32), (), 4),
    # 80 = 2.5 blocks: the last diagonal block holds 16 real and 16 padded rows
    "subtiled-padded-tail": (dict(s=80), (32, 32), (), 4),
    # a boundary at 44: inside the second diagonal block, inside a sub-tile
    "subtiled-two-segments": (dict(s=64), (32, 32), (44,), 4),
    "subtiled-three-segments": (dict(s=96), (32, 32), (12, 50), 4),
    "subtiled-tail-and-segments": (dict(s=80, h=4, hkv=1), (32, 32), (27, 70), 4),
    "blocks-differ-falls-back": (dict(s=64), (32, 16), (40,), 1),
    "block-no-wider-than-tile-falls-back": (dict(s=40), (8, 8), (20,), 1),
}


@pytest.mark.parametrize("case", [*DIAGONAL_CASES, "subtiled-fully-masked-rows"])
def test_flash_diagonal_blocks_match_xla(monkeypatch, case):
    """Output and q/k/v gradients against the XLA path where a diagonal block
    is computed as the sub-tiles under its diagonal (and where it must not
    be): the skip is by position alone, tail and segment masks still apply."""
    from finetune_controller_tpu.ops.pallas import flash_attention as fa

    monkeypatch.setattr(fa, "DIAG_TILE", 8)
    masked_rows = case == "subtiled-fully-masked-rows"
    shape, (bq, bk), bounds, tiles = (
        (dict(s=64), (32, 32), (), 4) if masked_rows else DIAGONAL_CASES[case])
    q, k, v = _qkv(**shape)
    assert fa._diag_tiles(bq, bk) == tiles
    if masked_rows:
        # queries 36..43 (inside a diagonal block) are of a segment no key has
        seg = jnp.where((jnp.arange(64) >= 36) & (jnp.arange(64) < 44), 7, 0)
        seg = jnp.broadcast_to(seg.astype(jnp.int32), (2, 64))
        kv_seg = jnp.zeros((2, 64), jnp.int32)

        def run(q, k, v):
            return fa.flash_attention_with_lse(
                q, k, v, segment_ids=seg, kv_segment_ids=kv_seg,
                block_q=bq, block_k=bk)[0]

        def ref(q, k, v):
            return _masked_reference(q, k, v, seg, kv_seg)
    else:
        seg = _segments(q.shape[1], *bounds) if bounds else None

        def run(q, k, v):
            return flash_attention(q, k, v, segment_ids=seg, block_q=bq, block_k=bk)

        def ref(q, k, v):
            return xla_causal_attention(q, k, v, segment_ids=seg)

    weight = jax.random.normal(jax.random.PRNGKey(3), ref(q, k, v).shape)
    out, grads = jax.value_and_grad(
        lambda *a: (run(*a) * weight).sum(), argnums=(0, 1, 2))(q, k, v)
    want, want_grads = jax.value_and_grad(
        lambda *a: (ref(*a) * weight).sum(), argnums=(0, 1, 2))(q, k, v)
    np.testing.assert_allclose(run(q, k, v), ref(q, k, v), atol=2e-5)
    np.testing.assert_allclose(out, want, rtol=1e-5)
    for got, exp in zip(grads, want_grads):
        np.testing.assert_allclose(got, exp, atol=1e-4)
    if masked_rows:
        assert not np.asarray(run(q, k, v))[:, 36:44].any()
        assert not np.asarray(grads[0])[:, 36:44].any()


@pytest.mark.parametrize("seq", [2048, 4096, 8192])
def test_flash_excluded_steps_name_a_resident_block(seq):
    """The clamped index maps at the cells' grids (block 1024): a step the
    causal frontier admits names its own block; an excluded step names the
    block of its computing neighbour — the last admitted one before it in
    the forward / dQ sweep over keys, the first admitted one after it in
    the dK/dV sweep over queries — so no copy is issued for it."""
    from finetune_controller_tpu.ops.pallas.flash_attention import (
        DEFAULT_BLOCK as B,
        _kv_block_index,
        _q_block_index,
    )

    n, group = seq // B, 4
    excluded = 0
    for iq in range(n):
        for ik in range(n):
            named = int(_kv_block_index(iq, ik, B, B))
            if ik <= iq:
                assert named == ik
            else:
                excluded += 1
                assert named == int(_kv_block_index(iq, ik - 1, B, B)) == iq
    assert excluded == n * (n - 1) // 2  # 1 of 4, 6 of 16, 28 of 64
    for ik in range(n):
        for j in reversed(range(group * n)):
            named = int(_q_block_index(ik, j, n, B, B))
            if j % n >= ik:
                assert named == j % n
            else:
                assert named == int(_q_block_index(ik, j + 1, n, B, B)) == ik


def test_flash_index_maps_with_blocks_that_differ():
    """Frontier arithmetic for bq != bk: the clamp is the kernels' own
    ``needed`` — every admitted step keeps its block, every other names an
    admitted one."""
    from finetune_controller_tpu.ops.pallas.flash_attention import (
        _kv_block_index,
        _q_block_index,
    )

    for bq, bk in [(32, 16), (16, 32)]:
        nq, nk = 96 // bq, 96 // bk
        for iq in range(nq):
            for ik in range(nk):
                needed = ik * bk <= (iq + 1) * bq - 1
                named = int(_kv_block_index(iq, ik, bq, bk))
                assert named == ik if needed else (
                    named < ik and named * bk <= (iq + 1) * bq - 1)
                named = int(_q_block_index(ik, iq, nq, bq, bk))
                assert named == iq if needed else (
                    named > iq and (named + 1) * bq - 1 >= ik * bk)


def test_flash_causal_work_over_need():
    """The static counter: whole 1024-wide blocks compute n(n+1)/2 of the
    triangle's n²/2; with 256-wide sub-tiles 10 of a diagonal block's 16."""
    from finetune_controller_tpu.ops.pallas import flash_attention as fa

    assert fa.DEFAULT_BLOCK // min(fa.DIAG_TILE, fa.DEFAULT_BLOCK) == 4
    got = [fa.causal_work_over_need(s) for s in (2048, 4096, 8192)]
    assert got == [1.125, 1.0625, 1.03125]
    # blocks that fall back are computed whole
    assert fa.causal_work_over_need(2048, 1024, 512) == 1.5
    assert fa.causal_work_over_need(4096, 256, 256) == 17 / 16


def test_flash_whole_block_work_is_the_parents(monkeypatch):
    """With no sub-tile narrower than the block: n(n+1)/2 blocks for n²/2."""
    from finetune_controller_tpu.ops.pallas import flash_attention as fa

    monkeypatch.setattr(fa, "DIAG_TILE", fa.DEFAULT_BLOCK)
    got = [fa.causal_work_over_need(s) for s in (2048, 4096, 8192)]
    assert got == [1.5, 1.25, 1.125]


@pytest.mark.parametrize("call,dots", [
    ("causal-small-block", 4),   # masked + interior, whole blocks
    ("causal-1024", 16),         # 4 diagonal + 3 left updates, + interior
])
def test_flash_forward_kernel_paths_by_products(call, dots):
    """Blocks no wider than the sub-tile trace the whole-block forward kernel
    — two products in each of its two paths — and only a sub-tiled call holds
    the band loop's."""
    from finetune_controller_tpu.ops.pallas.flash_attention import (
        flash_attention_with_lse,
    )

    s, block = (64, 16) if call == "causal-small-block" else (2048, None)
    q = jax.ShapeDtypeStruct((1, s, 2, 16), jnp.float32)
    text = str(jax.make_jaxpr(lambda q, k, v: flash_attention_with_lse(
        q, k, v, block_q=block, block_k=block,
        interpret=True))(q, q, q))
    assert text.count("dot_general") == dots


def test_flash_tuning_defaults_resolution():
    """Unset knobs resolve to the defaults the three ledger cells run
    (block 1024; exp dtype following the input dtype)."""
    from finetune_controller_tpu.ops.pallas.flash_attention import (
        DEFAULT_BLOCK,
        _resolve_tuning,
    )

    q_bf16 = jnp.zeros((1, 8, 1, 4), jnp.bfloat16)
    q_f32 = jnp.zeros((1, 8, 1, 4), jnp.float32)
    assert DEFAULT_BLOCK == 1024
    assert _resolve_tuning(q_bf16, None, None, None) == (
        DEFAULT_BLOCK, DEFAULT_BLOCK, "bfloat16")
    assert _resolve_tuning(q_f32, None, None, None) == (
        DEFAULT_BLOCK, DEFAULT_BLOCK, "float32")
    # explicit values always win over the defaults
    assert _resolve_tuning(q_bf16, 256, 128, "float32") == (256, 128, "float32")


@pytest.mark.parametrize("impl,seq_len,backend,sp,want", [
    ("auto", 4096, "cpu", 1, "xla"),
    ("auto", 512, "tpu", 1, "xla"),
    ("auto", 1024, "tpu", 1, "pallas"),
    ("auto", 2048, "tpu", 1, "pallas"),   # mistral-7b-qlora.train-sft-2k
    ("auto", 4096, "tpu", 1, "pallas"),   # joyai-llm-flash-lora.train-sft-4k
    ("auto", 8192, "tpu", 1, "pallas"),   # mistral-7b-qlora.train-sft-8k
    ("xla", 8192, "tpu", 1, "xla"),
    ("pallas", 16, "cpu", 1, "pallas"),
    ("auto", 8192, "tpu", 2, "ring"),
    ("xla", 64, "cpu", 2, "ring"),
    ("pallas", 64, "cpu", 2, "ring"),
    ("ring", 64, "cpu", 2, "ring"),
    ("ulysses", 64, "cpu", 2, "ulysses"),
    ("ring", 64, "cpu", 1, "xla"),
    ("ulysses", 64, "tpu", 1, "xla"),
])
def test_resolve_attention_impl(devices8, impl, seq_len, backend, sp, want):
    """The one rule: ``auto`` is the flash kernels on a TPU from
    ``PALLAS_MIN_SEQ``; an ``sp`` axis sends every choice that is not
    sequence-parallel to ``ring``; without one ``ring``/``ulysses`` are plain
    attention; an explicit kernel is kept."""
    from finetune_controller_tpu.ops.attention import resolve_attention_impl

    mesh = MeshSpec(dp=1, fsdp=1, sp=sp).build(devices8[:sp])
    assert resolve_attention_impl(
        impl, seq_len, mesh=mesh, backend=backend) == want
    # the mesh the trainer installs is the default
    with ring_mesh(mesh):
        assert resolve_attention_impl(impl, seq_len, backend=backend) == want


def test_resolve_attention_impl_refuses_unknown_name():
    from finetune_controller_tpu.ops.attention import resolve_attention_impl

    with pytest.raises(ValueError, match="vulkan"):
        resolve_attention_impl("vulkan", 2048)
    q, k, v = _qkv(s=16)
    with pytest.raises(ValueError, match="unknown attention impl"):
        causal_attention(q, k, v, impl="vulkan")


@pytest.mark.parametrize("where,want", [
    ("no_mesh", True),
    ("one_device_mesh", True),
    ("four_devices", False),
    ("inside_shard_map", True),
])
def test_bare_mosaic_call_predicate_is_shared(devices8, monkeypatch, where, want):
    """One predicate says where a Mosaic call may be issued bare; the flash
    dispatch and the grouped expert product both follow it."""
    from jax.sharding import PartitionSpec as P

    from finetune_controller_tpu.models import moe
    from finetune_controller_tpu.ops import attention as attn_mod
    from finetune_controller_tpu.ops.pallas import bare_mosaic_call_ok

    # the grouped product's other conditions held true: a TPU, rows % 128 == 0
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    wrapped = []
    real_shard_map = jax.shard_map
    monkeypatch.setattr(
        attn_mod.jax, "shard_map",
        lambda *a, **kw: wrapped.append(1) or real_shard_map(*a, **kw))
    q, k, v = _qkv(b=4, s=16)

    def probe():
        before = len(wrapped)
        jax.eval_shape(
            lambda q, k, v: attn_mod._flash_attention_on_mesh(q, k, v, None),
            q, k, v)
        flash_bare = len(wrapped) == before
        return bare_mosaic_call_ok(), flash_bare, moe._pallas_grouped_dot_ok(256)

    if where == "no_mesh":
        got = probe()
    else:
        n = 1 if where == "one_device_mesh" else 4
        mesh = MeshSpec(dp=1, fsdp=n).build(devices8[:n])
        with ring_mesh(mesh):
            if where == "inside_shard_map":
                seen = []

                def body(x):
                    seen.append(probe())
                    return x

                real_shard_map(
                    body, mesh=mesh, in_specs=P("fsdp"), out_specs=P("fsdp"),
                    check_vma=False,   # a pallas_call declares no vma
                )(jnp.zeros((4,)))
                (got,) = seen
            else:
                got = probe()
    assert got == (want, want, want)
    assert not moe._pallas_grouped_dot_ok(100)   # its own row-multiple rule


def test_flash_attention_bf16_default_exp_matches_xla():
    """bf16 inputs take the bf16-exp path by default; parity vs the f32-exp
    XLA oracle stays within bf16 rounding noise."""
    q, k, v = _qkv(dtype=jnp.bfloat16)
    ref = xla_causal_attention(q, k, v)
    out = flash_attention(q, k, v, block_q=16, block_k=16)
    np.testing.assert_allclose(
        out.astype(np.float32), ref.astype(np.float32), atol=3e-2)


def test_dispatcher_pallas_path():
    q, k, v = _qkv(s=32)
    out = causal_attention(q, k, v, impl="pallas")
    ref = causal_attention(q, k, v, impl="xla")
    np.testing.assert_allclose(out, ref, atol=1e-5)


# ---------------------------------------------------------------------------
# Ring attention (sequence/context parallelism)
# ---------------------------------------------------------------------------


def test_ring_attention_matches_xla(devices8):
    mesh = MeshSpec(dp=2, fsdp=1, sp=4).build(devices8)
    q, k, v = _qkv(b=4, s=64)
    seg = (jnp.arange(64)[None, :] // 16).astype(jnp.int32).repeat(4, 0)
    ref = xla_causal_attention(q, k, v, segment_ids=seg)
    out = ring_attention_sharded(q, k, v, segment_ids=seg, mesh=mesh)
    np.testing.assert_allclose(out, ref, atol=1e-5)


def test_ring_attention_grads(devices8):
    mesh = MeshSpec(dp=1, fsdp=2, sp=4).build(devices8)
    q, k, v = _qkv(b=2, s=32)

    g1 = jax.grad(
        lambda q, k, v: (ring_attention_sharded(q, k, v, mesh=mesh) ** 2).sum(),
        argnums=(0, 1, 2),
    )(q, k, v)
    g2 = jax.grad(
        lambda q, k, v: (xla_causal_attention(q, k, v) ** 2).sum(),
        argnums=(0, 1, 2),
    )(q, k, v)
    for a, b in zip(g1, g2):
        np.testing.assert_allclose(a, b, atol=1e-4)


def test_ring_dispatch_through_model_config(devices8):
    """attention_impl='ring' + installed mesh flows through a full model."""
    mesh = MeshSpec(dp=1, fsdp=2, sp=4).build(devices8)
    cfg = PRESETS["tiny-test"].replace(attention_impl="ring", remat=False)
    model = LlamaForCausalLM(cfg)
    tokens = jax.random.randint(jax.random.PRNGKey(0), (2, 32), 0, cfg.vocab_size)
    variables = model.init({"params": jax.random.PRNGKey(1)}, tokens)
    with ring_mesh(mesh):
        logits_ring = model.apply(variables, tokens)
    logits_ref = model.apply(
        variables, tokens,
    )  # without mesh installed the ring impl falls back to plain attention
    # bf16 compute: ring and dense paths differ by accumulation order only
    np.testing.assert_allclose(logits_ring, logits_ref, atol=0.15)


# ---------------------------------------------------------------------------
# MoE expert parallelism
# ---------------------------------------------------------------------------


def test_moe_model_forward_and_aux():
    cfg = PRESETS["tiny-moe-test"].replace(remat=False)
    model = LlamaForCausalLM(cfg)
    tokens = jax.random.randint(jax.random.PRNGKey(0), (2, 16), 0, cfg.vocab_size)
    variables = model.init({"params": jax.random.PRNGKey(1)}, tokens)
    logits, collections = model.apply(tokens=tokens, variables=variables, mutable=("moe_aux",))
    assert logits.shape == (2, 16, cfg.vocab_size)
    from finetune_controller_tpu.models.moe import moe_aux_loss

    aux = moe_aux_loss(collections)
    # Switch aux loss is >= 1 (equals 1 at perfectly uniform routing)
    assert float(aux) >= 0.9 * cfg.n_layers


def test_moe_params_have_expert_axis_sharding(devices8):
    mesh = MeshSpec(dp=1, fsdp=2, ep=4).build(devices8)
    cfg = PRESETS["tiny-moe-test"].replace(remat=False)
    model = LlamaForCausalLM(cfg)
    tokens = jnp.zeros((1, 8), jnp.int32)
    shapes = jax.eval_shape(lambda: model.init({"params": jax.random.PRNGKey(0)}, tokens))
    shardings = LLAMA_RULES.tree_specs(shapes)
    flat = {
        "/".join(str(getattr(k, "key", k)) for k in kp): spec
        for kp, spec in jax.tree_util.tree_flatten_with_path(
            shardings, is_leaf=lambda x: isinstance(x, jax.sharding.PartitionSpec)
        )[0]
    }
    gate_specs = [s for p, s in flat.items() if "experts/gate_proj" in p]
    assert gate_specs, flat.keys()
    # leading layer-scan axis is None, then experts over 'ep'
    assert all(s[1] == "ep" or s[0] == "ep" for s in gate_specs), gate_specs


def test_moe_trains_end_to_end(devices8):
    """Full trainer loop on the MoE preset over an ep mesh — loss decreases."""
    from finetune_controller_tpu.data.synthetic import synthetic_batches
    from finetune_controller_tpu.train.trainer import TrainConfig, Trainer

    mesh = MeshSpec(dp=1, fsdp=2, ep=4).build(devices8)
    cfg = PRESETS["tiny-moe-test"]
    tcfg = TrainConfig(
        mode="full", learning_rate=5e-2, warmup_steps=2, total_steps=12,
        batch_size=8, seq_len=16, log_every=4, checkpoint_every=1000,
    )
    trainer = Trainer(cfg.replace(lora=cfg.lora), tcfg, mesh=mesh)
    batches = synthetic_batches(
        batch_size=tcfg.batch_size, seq_len=tcfg.seq_len,
        vocab_size=cfg.vocab_size, task="increment", seed=0,
    )
    state = trainer.init_state()
    losses = []
    it = iter(batches)
    for _ in range(tcfg.total_steps):
        state, metrics = trainer.step(state, next(it))
        losses.append(float(metrics["loss"]))
    assert losses[-1] < losses[0] * 0.8, losses
    assert "moe_aux" in metrics

# ---------------------------------------------------------------------------
# int4 QLoRA
# ---------------------------------------------------------------------------


def test_int4_quantization_roundtrip():
    from finetune_controller_tpu.models.quant import dequantize_int4, quantize_int4

    w = jax.random.normal(jax.random.PRNGKey(0), (128, 32), jnp.float32) * 0.1
    packed, scales = quantize_int4(w, block_size=64)
    assert packed.shape == (64, 32) and packed.dtype == jnp.uint8
    assert scales.shape == (2, 32)
    deq = dequantize_int4(packed, scales, dtype=jnp.float32)
    # int4 with blockwise scales: relative error bounded by scale/2 per element
    err = np.abs(np.asarray(deq - w))
    bound = np.asarray(scales, np.float32).repeat(64, axis=0) * 0.51
    assert (err <= bound + 1e-6).all()
    # memory: ~4.25 bits/weight
    nbytes = packed.nbytes + scales.nbytes
    assert nbytes < w.nbytes / 6


def test_qlora_model_trains_and_shrinks_memory(devices8):
    from finetune_controller_tpu.data.synthetic import synthetic_batches
    from finetune_controller_tpu.train.trainer import TrainConfig, Trainer
    from finetune_controller_tpu.models.lora import LoRAConfig

    cfg = PRESETS["tiny-test"].replace(
        quantize_base=True, lora=LoRAConfig(rank=8), remat=False
    )
    tcfg = TrainConfig(
        mode="lora", learning_rate=1e-1, warmup_steps=2, total_steps=25,
        batch_size=8, seq_len=16, log_every=5, checkpoint_every=1000,
    )
    mesh = MeshSpec(dp=1, fsdp=2, tp=2).build(devices8[:4])
    trainer = Trainer(cfg, tcfg, mesh=mesh)
    state = trainer.init_state()
    # frozen projection kernels are stored packed uint8
    flat = jax.tree_util.tree_flatten_with_path(state.frozen)[0]
    packed = [v for kp, v in flat if "kernel_packed" in str(kp)]
    assert packed and all(v.dtype == jnp.uint8 for v in packed)
    assert not [kp for kp, _ in flat
                if str(kp).endswith("q_proj'], key='kernel')")]
    batches = synthetic_batches(
        batch_size=8, seq_len=16, vocab_size=cfg.vocab_size, task="increment",
        seed=0,
    )
    it = iter(batches)
    losses = []
    for _ in range(25):
        state, metrics = trainer.step(state, next(it))
        losses.append(float(metrics["loss"]))
    # compare window means: single steps are noisy at toy scale, and rank-8
    # adapters on a frozen random base move the loss slowly
    assert np.mean(losses[-5:]) < np.mean(losses[:5]) - 0.1, losses


def test_ulysses_attention_matches_xla(devices8):
    """Ulysses SP (all-to-all head sharding) is bit-exact vs the unsharded
    oracle — the local kernel computes the same full-sequence attention."""
    from finetune_controller_tpu.parallel.ulysses import (
        ulysses_attention_sharded,
    )

    mesh = MeshSpec(dp=2, fsdp=1, sp=2).build(devices8[:4])
    q, k, v = _qkv(b=2, s=64)
    seg = (jnp.arange(64)[None, :] // 24).astype(jnp.int32).repeat(2, 0)

    ref = xla_causal_attention(q, k, v, segment_ids=seg)
    out = ulysses_attention_sharded(q, k, v, segment_ids=seg, mesh=mesh)
    np.testing.assert_allclose(out, ref, atol=1e-6)

    g_u = jax.grad(
        lambda q, k, v: (ulysses_attention_sharded(
            q, k, v, segment_ids=seg, mesh=mesh) ** 2).sum(),
        argnums=(0, 1, 2),
    )(q, k, v)
    g_ref = jax.grad(
        lambda q, k, v: (xla_causal_attention(
            q, k, v, segment_ids=seg) ** 2).sum(),
        argnums=(0, 1, 2),
    )(q, k, v)
    for a, b in zip(g_u, g_ref):
        np.testing.assert_allclose(a, b, atol=1e-6)


def test_ulysses_requires_kv_head_divisibility(devices8):
    from finetune_controller_tpu.parallel.ulysses import (
        ulysses_attention_sharded,
    )

    mesh = MeshSpec(dp=1, fsdp=2, sp=4).build(devices8)
    q, k, v = _qkv(b=2, s=64)  # hkv=2 < sp=4
    with pytest.raises(ValueError, match="divide n_kv_heads"):
        ulysses_attention_sharded(q, k, v, mesh=mesh)


def test_ulysses_dispatch_through_model_config(devices8):
    """attention_impl='ulysses' trains through the full model on an sp mesh
    and matches the XLA attention reference."""
    mesh = MeshSpec(dp=1, fsdp=2, sp=2).build(devices8[:4])
    cfg = PRESETS["tiny-test"].replace(attention_impl="ulysses", remat=False)
    model = LlamaForCausalLM(cfg)
    tokens = jax.random.randint(jax.random.PRNGKey(0), (2, 32), 0, cfg.vocab_size)
    variables = model.init({"params": jax.random.PRNGKey(1)}, tokens)
    with ring_mesh(mesh):
        logits_u = model.apply(variables, tokens)
    logits_ref = model.apply(
        variables, tokens,
        deterministic=True,
    )
    np.testing.assert_allclose(
        np.asarray(logits_u), np.asarray(logits_ref), atol=2e-4)
