"""Learned sparse attention (ISSUE 32) at toy size on the CPU: the program's
indexer, selection and IndexShare against the plain reference
(``benchmarks/reference/mla_dsa_moe.py``), the selection's travel from a full
layer to the shared ones after it, and the flash kernels over a selection
against the masked XLA path.  Every tolerance names its reason."""

import json
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))

from benchmarks.harness import program, weights  # noqa: E402
from benchmarks.harness.programs import mla_dsa_moe as prog  # noqa: E402
from benchmarks.reference import mla_dsa_moe as ref  # noqa: E402
from benchmarks.reference import mla_moe as ref_mla  # noqa: E402
from benchmarks.reference.model import head_logits, top_weights  # noqa: E402
from finetune_controller_tpu.models import llama  # noqa: E402
from finetune_controller_tpu.models.llama import LlamaForCausalLM  # noqa: E402
from finetune_controller_tpu.ops.attention import (  # noqa: E402
    causal_attention, pack_selection, unpack_selection, xla_causal_attention)
from finetune_controller_tpu.ops.pallas.flash_attention import (  # noqa: E402
    _default_block, flash_attention)

#: ``[full, shared, shared, full, shared]`` behind one leading dense layer
#: (itself the first full layer), top-8 of 32 keys, half of 16 experts held
CONF = json.loads(
    (ROOT / "tests/benchmarks/fixtures/configs/tiny-dsa-moe.json").read_text())
SEED = 2**31 + 32
SEQ = 32


def _tokens(batch=2, seq=SEQ, seed=0):
    return np.random.default_rng(seed).integers(
        0, CONF["vocab_size"], (batch, seq)).astype(np.int32)


def _seeded(cfg, seed=SEED):
    """The program's variables with the benchmark's seeded weights: frozen
    base in bf16, adapters in float32 (as ``tests/test_mla_moe.py``)."""
    model = LlamaForCausalLM(cfg)
    shapes = jax.eval_shape(
        lambda: model.init({"params": jax.random.PRNGKey(0)},
                           jnp.zeros((1, 8), jnp.int32)))
    shapes = {
        "params": jax.tree.map(
            lambda s: jax.ShapeDtypeStruct(s.shape, jnp.bfloat16), shapes["params"]),
        "lora": shapes["lora"]}
    return model, program.fill(shapes, weights.root_key(seed), 64)


def _flat(tree):
    return {program.canonical(p): v for p, v in
            jax.tree_util.tree_flatten_with_path(tree)[0]}


def _config(conf=CONF, **overrides):
    return prog.model_config(conf, dtype=jnp.float32, remat=False, **overrides)


def _reference_logits_and_selections(arch, key, lora, tokens):
    b = arch.base
    top = top_weights(b, key)
    x = top["embedding"][tokens].astype(jnp.float32)
    pos = jnp.broadcast_to(jnp.arange(x.shape[1]), x.shape[:2])
    chosen, used = None, []
    for l in range(b.n_layers):
        prefix, index, dense = ref_mla._place(b, l)
        w = ref_mla.layer_weights(b, key, prefix, index, dense)
        lora_l = ref_mla.layer_lora(lora, prefix, index)
        if arch.kinds[l] == "full":
            chosen = ref.layer_selection(
                arch, w, ref.indexer_weights(arch, key, l), lora_l, x, pos)
        used.append(chosen)
        x = ref.layer_forward(arch, w, lora_l, x, pos, dense, chosen)
    return head_logits(b, top, x), used


def _program_selections(model, variables, tokens):
    """The selection every layer USED, by layer, from the model's
    intermediates: ``dsa_selection`` is what attention was handed."""
    _, state = model.apply(variables, tokens, capture_intermediates=(
        lambda mdl, name: isinstance(mdl, llama.MLAttention) and name == "__call__"))
    inter = state["intermediates"]
    used = [inter["layer_0"]["attn"]["__call__"][0][1]]
    stacked = inter["blocks"]["block"]["attn"]["__call__"][0][1]
    return used + list(stacked)


# ---- program against reference ------------------------------------------------


def test_program_matches_the_reference_sets_logits_loss_and_adapter_gradients():
    """The whole program in float32 — a leading dense full layer outside the
    scanned stack, then ``[shared, shared, full, shared]`` scanned, the full
    one's indexer in ``blocks_indexer`` — against the plain reference: the
    SAME keys selected in every layer (float32 on both sides, so no near-tie
    at the 8th key falls two ways at this size), logits to 2e-5 (float32
    sums in another order, as ``tests/test_mla_moe.py``), the loss to 1e-5,
    every adapter's gradient to 2e-3 relative (gradients of 1e-6..1e-2
    through five layers of float32 products)."""
    cfg = _config()
    assert cfg.indexer_kinds() == ("full", "shared", "shared", "full", "shared")
    assert (cfg.index_topk, cfg.experts_held, cfg.n_experts) == (8, (0, 8), 16)
    model, variables = _seeded(cfg)
    tokens = jnp.asarray(_tokens())
    arch = ref.Arch.from_config(CONF)
    key = weights.root_key(SEED)
    lora0 = ref_mla.init_lora(arch.base, key)
    assert set(lora0) == set(_flat(variables["lora"]))

    want_logits, want_sets = _reference_logits_and_selections(arch, key, lora0, tokens)
    got_sets = _program_selections(model, variables, tokens)
    assert len(got_sets) == len(want_sets) == 5
    for l, (got, want) in enumerate(zip(got_sets, want_sets)):
        np.testing.assert_array_equal(unpack_selection(got, SEQ), want, err_msg=str(l))
        assert int(want.sum()) == 2 * sum(min(t + 1, 8) for t in range(SEQ))
    np.testing.assert_allclose(model.apply(variables, tokens), want_logits, atol=2e-5)

    def mean_nll(lora):
        logits = model.apply({"params": variables["params"], "lora": lora},
                             tokens)[:, :-1]
        logp = jax.nn.log_softmax(logits, axis=-1)
        return -jnp.take_along_axis(logp, tokens[:, 1:, None], -1).mean()

    loss, grads = jax.value_and_grad(mean_nll)(variables["lora"])
    ref_loss, ref_grads, notes = ref.make_loss_and_grads(arch, rows_per_block=1)(
        key, lora0, np.asarray(tokens))
    assert abs(float(loss) - ref_loss) < 1e-5
    assert 0.0 <= notes["selected_keys_moved_by_bf16"] < 0.05
    for name, g in _flat(grads).items():
        assert float(jnp.abs(ref_grads[name]).max()) > 0, name
        np.testing.assert_allclose(g, ref_grads[name], rtol=2e-3, atol=2e-8,
                                   err_msg=name)


def test_a_selection_of_every_key_is_the_latent_attention_model_bit_for_bit():
    """``index_topk >= S`` selects every earlier key: the logits are those of
    the model without an indexer, bit for bit (the mask ANDs in nothing) —
    which ties the selection's path to the one the latent-attention cell
    guards.  The indexer's leaves are the only ones the other lacks."""
    tokens = jnp.asarray(_tokens())
    sparse, variables = _seeded(_config(index_topk=SEQ))
    plain = LlamaForCausalLM(_config(index_topk=0))
    params = jax.tree_util.tree_map_with_path(lambda p, a: a, variables["params"])
    params = {k: v for k, v in params.items() if k != "blocks_indexer"}
    params["layer_0"] = dict(params["layer_0"], attn={
        k: v for k, v in params["layer_0"]["attn"].items() if k != "indexer"})
    assert (set(_flat(plain.init({"params": jax.random.PRNGKey(0)}, tokens)["params"]))
            == set(_flat(params)))
    np.testing.assert_array_equal(
        sparse.apply(variables, tokens),
        plain.apply({"params": params, "lora": variables["lora"]}, tokens))


# ---- IndexShare: who owns an indexer, whose selection a layer takes ------------


@pytest.mark.parametrize("scan", [True, False], ids=["scanned", "unrolled"])
def test_shared_layers_hold_no_indexer_and_take_the_full_layers_selection(scan):
    """Indexer leaves exist for the full layers alone — layer 0's under its
    ``attn/indexer``, the scanned stack's ONE full layer in ``blocks_indexer``
    with a leading axis of 1 — and a shared layer attends the selection of the
    nearest full layer before it: layers 1-2 layer 0's, ACROSS the boundary
    between the unrolled layer and the scanned stack, layer 4 layer 3's."""
    cfg = _config(scan_layers=scan)
    model, variables = _seeded(cfg)
    names = [n for n in _flat(variables["params"]) if "indexer" in n]
    leaves = ["k_norm/bias", "k_norm/scale", "weights_proj/kernel",
              "wk/kernel", "wq_b/kernel"]
    if scan:
        assert sorted(names) == sorted(
            [f"layer_0/attn/indexer/{n}" for n in leaves]
            + [f"blocks_indexer/{n}" for n in leaves])
        assert _flat(variables["params"])["blocks_indexer/wq_b/kernel"].shape == (
            1, 48, 4 * 16)
        sets = _program_selections(model, variables, jnp.asarray(_tokens()))
        for shared, full in ((1, 0), (2, 0), (4, 3)):
            np.testing.assert_array_equal(sets[shared], sets[full])
        assert not np.array_equal(sets[3], sets[0])
    else:
        assert sorted(names) == sorted(
            f"layer_{l}/attn/indexer/{n}" for l in (0, 3) for n in leaves)
    assert not [n for n in _flat(variables["lora"]) if "indexer" in n]


def test_scanned_and_unrolled_stacks_compute_the_same_model():
    """The indexer stack and the ``cond`` a scanned layer takes are plumbing:
    the same weights laid out for the unrolled model (layer ``i`` = row ``i -
    1`` of the stack, layer 3's indexer = row 0 of ``blocks_indexer``) give
    the same logits, to float32 rounding of another fusion order (1e-5)."""
    tokens = jnp.asarray(_tokens())
    scanned, variables = _seeded(_config())

    def unrolled(collection):
        tree = {k: v for k, v in variables[collection].items()
                if not k.startswith("blocks")}
        for i in range(1, 5):
            tree[f"layer_{i}"] = jax.tree.map(
                lambda a: a[i - 1], variables[collection]["blocks"]["block"])
        return tree

    params = unrolled("params")
    params["layer_3"]["attn"]["indexer"] = jax.tree.map(
        lambda a: a[0], variables["params"]["blocks_indexer"])
    np.testing.assert_allclose(
        scanned.apply(variables, tokens),
        LlamaForCausalLM(_config(scan_layers=False)).apply(
            {"params": params, "lora": unrolled("lora")}, tokens), atol=1e-5)


def test_indexer_leaves_get_no_gradient():
    """The selection is discrete and its inputs are stop_gradient-ed: a
    gradient taken with respect to the FROZEN leaves is exactly zero at every
    indexer leaf (and not elsewhere)."""
    model, variables = _seeded(_config())
    tokens = jnp.asarray(_tokens())
    params = jax.tree.map(lambda a: a.astype(jnp.float32), variables["params"])
    grads = _flat(jax.grad(lambda p: (model.apply(
        {"params": p, "lora": variables["lora"]}, tokens) ** 2).mean())(params))
    indexer = {n: g for n, g in grads.items() if "indexer" in n}
    assert len(indexer) == 10
    for name, g in indexer.items():
        assert not np.asarray(g).any(), name
    assert np.asarray(grads["layer_0/attn/q_a_proj/kernel"]).any()


def test_model_with_an_indexer_refuses_what_it_cannot_carry_a_selection_through():
    with pytest.raises(ValueError, match="first full"):
        _config(indexer_types=("shared",) + ("full",) * 4).indexer_kinds()
    with pytest.raises(ValueError, match="latent attention"):
        _config(attention_kind="gqa").indexer_kinds()
    with pytest.raises(NotImplementedError, match="pipeline"):
        llama.make_block_stage_fn(_config())


def test_sequence_parallel_attention_refuses_a_selection():
    """A query's selection ranges over ALL keys, so it cannot ride a ring of
    sequence shards: under a mesh whose ``sp`` axis shards the sequence the
    call is refused, not run as plain attention."""
    from finetune_controller_tpu.parallel.mesh import MeshSpec
    from finetune_controller_tpu.parallel.ring import ring_mesh

    if jax.device_count() < 2:
        pytest.skip("needs two devices for an sp axis")
    q = jnp.zeros((1, 8, 2, 4))
    with ring_mesh(MeshSpec(sp=2).build(jax.devices()[:2])):
        with pytest.raises(NotImplementedError, match="selection"):
            causal_attention(q, q, q, selection=jnp.zeros((1, 8, 128), jnp.int32))


# ---- the top-k itself -----------------------------------------------------------


def _sorted_top(scores, admitted, top):
    """The specification, by a full stable sort in numpy."""
    out = np.zeros(scores.shape, bool)
    for b in range(scores.shape[0]):
        for t in range(scores.shape[1]):
            keys = np.flatnonzero(admitted[b, t])
            best = keys[np.argsort(-scores[b, t, keys], kind="stable")][:top]
            out[b, t, best] = True
    return out


def test_ties_go_to_the_lower_position():
    """Scores drawn from four values tie everywhere, and -0.0 ties +0.0: the
    bit-by-bit threshold plus the tied keys ranked by position select exactly
    what a stable descending sort selects."""
    rng = np.random.default_rng(3)
    scores = rng.choice([-1.5, -0.0, 0.0, 2.0], size=(2, 200, 200)).astype(np.float32)
    at = np.arange(200)
    causal = np.broadcast_to(at[:, None] >= at[None, :], scores.shape)
    got = np.asarray(llama._top_keys(jnp.asarray(scores), None, 70))
    np.testing.assert_array_equal(got, _sorted_top(scores, causal, 70))
    assert (got.sum(-1) == np.minimum(at + 1, 70)).all()


def test_distinct_scores_select_what_a_sort_selects_by_the_same_work():
    """Rows longer than one run of 128 positions, no tie anywhere — and the
    program that selects is the one that breaks ties: no ``cond`` on the
    scores, so a step's work cannot follow the data (the cell's rate did, by
    24.5 ms a full layer that met a tie, until PR 32 took the ``cond`` out)."""
    rng = np.random.default_rng(4)
    scores = rng.normal(size=(1, 300, 300)).astype(np.float32)
    at = np.arange(300)
    causal = np.broadcast_to(at[:, None] >= at[None, :], scores.shape)
    np.testing.assert_array_equal(
        llama._top_keys(jnp.asarray(scores), None, 10), _sorted_top(scores, causal, 10))
    text = str(jax.make_jaxpr(lambda x: llama._top_keys(x, None, 10))(jnp.asarray(scores)))
    assert "cond" not in text


@pytest.mark.parametrize("n", [5, 128, 300])
def test_count_up_to_is_the_cumulative_sum(n):
    flags = np.random.default_rng(n).random((2, 3, n)) < 0.4
    np.testing.assert_array_equal(
        llama._count_up_to(jnp.asarray(flags)), np.cumsum(flags, axis=-1))


def test_packed_rows_never_select_across_documents():
    """Two documents packed into one row: a query's keys all lie in its own
    document, and a document's first queries take every key they may see."""
    rng = np.random.default_rng(5)
    scores = rng.normal(size=(1, 32, 32)).astype(np.float32)
    segments = np.asarray([[0] * 20 + [1] * 12])
    at = np.arange(32)
    admitted = (at[:, None] >= at[None, :])[None] & (
        segments[:, :, None] == segments[:, None, :])
    got = np.asarray(llama._top_keys(jnp.asarray(scores), jnp.asarray(segments), 5))
    assert not (got & ~admitted).any()
    np.testing.assert_array_equal(got, _sorted_top(scores, admitted, 5))
    assert got[0, 20].sum() == 1 and got[0, 23].sum() == 4 and got[0, 31].sum() == 5


def test_selection_words_round_trip_and_count():
    rng = np.random.default_rng(6)
    for seq in (8, 160, 4100):
        mask = rng.random((1, 5, seq)) < 0.3
        words = pack_selection(jnp.asarray(mask))
        assert words.shape == (1, 5, -(-seq // 4096) * 128) and words.dtype == jnp.int32
        np.testing.assert_array_equal(unpack_selection(words, seq), mask)
        assert int(jax.lax.population_count(words).sum()) == mask.sum()


# ---- the flash kernels over a selection ------------------------------------------


def _attention_inputs(seq, heads, kv_heads, d, dv, seed=0):
    rng = np.random.default_rng(seed)
    q = jnp.asarray(rng.normal(size=(1, seq, heads, d)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(1, seq, kv_heads, d)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(1, seq, kv_heads, dv)), jnp.float32)
    mask = rng.random((1, seq, seq)) < 0.3
    mask |= np.eye(seq, dtype=bool)[None]           # a query always keeps itself
    return q, k, v, pack_selection(jnp.asarray(mask))


@pytest.mark.parametrize("block,segments", [(128, False), (256, True), (512, False)],
                         ids=["whole_blocks", "segments", "sub_tiled_diagonal"])
def test_flash_kernels_over_a_selection_match_the_masked_xla_path(block, segments):
    """Forward, dQ and dK/dV in interpret mode at q/k and v heads of 256 with
    a selection as their further operand, against the XLA path under the same
    mask: blocks of 128 (one run of keys a block), 256 with packed segments,
    and 512 (the block head sizes of 256 + 256 get; its diagonal in 256-wide
    sub-tiles).  Float32 both sides: 2e-5 absolute is float32 sums in the
    kernels' block order."""
    seq = 512
    q, k, v, selection = _attention_inputs(seq, 2, 1, 256, 256)
    seg = jnp.asarray(np.repeat([[0, 1]], seq // 2, axis=1).reshape(1, seq)
                      ) if segments else None

    def kernels(q, k, v):
        return (flash_attention(q, k, v, segment_ids=seg, selection=selection,
                                block_q=block, block_k=block, interpret=True) ** 2).sum()

    def masked(q, k, v):
        return (xla_causal_attention(q, k, v, segment_ids=seg,
                                     selection=selection) ** 2).sum()

    got = jax.value_and_grad(kernels, argnums=(0, 1, 2))(q, k, v)
    want = jax.value_and_grad(masked, argnums=(0, 1, 2))(q, k, v)
    np.testing.assert_allclose(got[0], want[0], rtol=1e-5)
    for g, w in zip(got[1], want[1]):
        np.testing.assert_allclose(g, w, rtol=2e-4, atol=5e-5)


def test_a_selection_needs_key_blocks_of_whole_runs():
    q, k, v, selection = _attention_inputs(64, 1, 1, 8, 8)
    with pytest.raises(ValueError, match="key block"):
        flash_attention(q, k, v, selection=selection, block_q=64, block_k=64,
                        interpret=True)


def test_head_sizes_of_256_beside_256_get_blocks_of_512_and_the_cells_keep_1024():
    assert _default_block(256, 256) == 512
    assert [_default_block(*sizes) for sizes in ((128, 128), (192, 128), (64, 64))] == [
        1024, 1024, 1024]


#: sha256 of the jaxpr text of value_and_grad of a flash call with NO
#: selection, in bf16, at the three accepted cells' shapes (B, S, H, Hkv, D,
#: Dv) — taken from ``git archive`` of PR 31's tree (bda1197) by the function
#: below: with no selection the kernels trace the bodies they always did
PARENT_JAXPRS = {
    "mistral-2k": ((8, 2048, 32, 8, 128, 128),
                   "613c3f5e61da28948fa4f707e65208b1cdb4c50a7f66bfbb9a15b483f477852a"),
    "mistral-8k": ((2, 8192, 32, 8, 128, 128),
                   "be9d140c71e926f20b0598f76a8f559a0e29b8fe354e104f455a7a079f903318"),
    "joyai-4k": ((2, 4096, 32, 32, 192, 128),
                 "9a77e3a796bbf41f31dab104cd1cf4fc5e1f262f9a4aba2b95a0e66e0d093382"),
}


def _flash_jaxpr(shape, selection=False) -> str:
    b, s, h, hkv, d, dv = shape
    q = jax.ShapeDtypeStruct((b, s, h, d), jnp.bfloat16)
    k = jax.ShapeDtypeStruct((b, s, hkv, d), jnp.bfloat16)
    v = jax.ShapeDtypeStruct((b, s, hkv, dv), jnp.bfloat16)
    words = (jax.ShapeDtypeStruct((b, s, -(-s // 4096) * 128), jnp.int32),
             ) if selection else ()

    def loss(q, k, v, *words):
        out = flash_attention(q, k, v, interpret=False,
                              selection=words[0] if words else None)
        return jnp.sum(out.astype(jnp.float32) ** 2)

    return str(jax.make_jaxpr(jax.value_and_grad(loss, argnums=(0, 1, 2)))(
        q, k, v, *words))


@pytest.mark.parametrize("cell", list(PARENT_JAXPRS))
def test_without_a_selection_the_kernels_trace_the_parents_bodies(cell):
    """Adapting, not a path: the accepted cells hand no selection, and what
    they trace is the parent's program to the character.  (The text holds
    shapes, primitives and kernel names, no file names or line numbers; a new
    JAX may print it differently, and then this pin is taken again from the
    same commit.)  With a selection the three kernels gain one operand."""
    import hashlib

    shape, parents = PARENT_JAXPRS[cell]
    text = _flash_jaxpr(shape)
    assert hashlib.sha256(text.encode()).hexdigest() == parents
    sparse = _flash_jaxpr(shape, selection=True)
    for name in ("flash_fwd", "flash_bwd_dq", "flash_bwd_dkv"):
        assert f"name={name}" in text and f"name={name}" in sparse
    assert sparse != text


# ---- a selection for each key/value head (ISSUE 49) -------------------------------


def _per_head_inputs(seq=256, heads=4, kv_heads=2, d=32, block=8, seed=0):
    """q, k, v in float32 and a selection of BLOCKS for each key/value head's
    group, ``(1, kv_heads, S, S / block)`` bool, a query's own block in it."""
    rng = np.random.default_rng(seed)
    q = jnp.asarray(rng.normal(size=(2, seq, heads, d)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(2, seq, kv_heads, d)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(2, seq, kv_heads, d)), jnp.float32)
    blocks = rng.random((2, kv_heads, seq, seq // block)) < 0.4
    blocks |= np.arange(seq)[:, None] // block == np.arange(seq // block)[None, :]
    return q, k, v, blocks


@pytest.mark.parametrize("segments", [False, True], ids=["whole", "segments"])
def test_a_selection_for_each_key_value_head_through_the_kernels_and_the_xla_form(
        segments):
    """``(B, Hkv, S, W)`` words: each key/value head's group of query heads
    masks by its OWN set, in the three kernels (interpret mode: the words
    read in place through the index maps) and in the XLA form, which is the
    per-head call with that head's set, head by head."""
    from finetune_controller_tpu.ops.attention import pack_block_selection

    q, k, v, blocks = _per_head_inputs()
    selection = pack_block_selection(jnp.asarray(blocks), 8)
    assert selection.shape == (2, 2, 256, 128)
    seg = jnp.asarray(np.repeat([[0] * 100 + [1] * 156], 2, 0)) if segments else None

    def weighed(fn):
        def loss(q, k, v):
            out = fn(q, k, v)
            return (out * jnp.cos(jnp.arange(out.size).reshape(out.shape))).sum(), out
        return jax.value_and_grad(loss, argnums=(0, 1, 2), has_aux=True)(q, k, v)

    (_, want), g_want = weighed(lambda q, k, v: xla_causal_attention(
        q, k, v, selection=selection, segment_ids=seg))
    (_, got), g_got = weighed(lambda q, k, v: flash_attention(
        q, k, v, selection=selection, segment_ids=seg, block_q=128, block_k=128,
        interpret=True))
    np.testing.assert_allclose(got, want, atol=2e-5)
    for g, w in zip(g_got, g_want):
        np.testing.assert_allclose(g, w, rtol=2e-4, atol=5e-5)
    mask = np.repeat(blocks, 8, axis=-1)
    for head in range(4):
        group = head // 2
        alone = xla_causal_attention(
            q[:, :, head:head + 1], k[:, :, group:group + 1],
            v[:, :, group:group + 1], segment_ids=seg,
            selection=pack_selection(jnp.asarray(mask[:, group])))
        np.testing.assert_array_equal(alone[:, :, 0], want[:, :, head])


def test_one_set_for_all_heads_is_still_the_parents_call():
    """The 16k sparse cell hands ``(B, S, W)`` words: value and gradient of
    its flash call (one row of 16,384, 64 heads of 256 beside 256, document
    marks) trace the parent's program to the character — sha256 of the jaxpr
    text from ``git archive`` of PR 47's tree (711fafd)."""
    import hashlib

    b, s, h, d = 1, 16384, 64, 256
    shaped = jax.ShapeDtypeStruct
    qkv = shaped((b, s, h, d), jnp.bfloat16)

    def loss(q, k, v, words, seg):
        return jnp.sum(flash_attention(
            q, k, v, interpret=False, selection=words, segment_ids=seg
        ).astype(jnp.float32) ** 2)

    text = str(jax.make_jaxpr(jax.value_and_grad(loss, argnums=(0, 1, 2)))(
        qkv, qkv, qkv, shaped((b, s, 4 * 128), jnp.int32), shaped((b, s), jnp.int32)))
    assert hashlib.sha256(text.encode()).hexdigest() == (
        "ce948666acc069f01730b73c2e20a7fe7036e0bc2e7eba3ee1001fe1554600ab")


def test_a_selection_that_does_not_split_the_query_heads_is_refused():
    from finetune_controller_tpu.ops.attention import pack_block_selection

    q, k, v, blocks = _per_head_inputs(heads=4, kv_heads=1)
    three = pack_block_selection(jnp.asarray(np.repeat(blocks, 3, axis=1)), 8)
    with pytest.raises(ValueError, match="key/value heads"):
        flash_attention(q, k, v, selection=three, block_q=128, block_k=128,
                        interpret=True)
