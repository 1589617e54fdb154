"""The sparse / lightning hybrid (ISSUE 49) at toy size on the CPU: whole blocks
that differ by their mixer — block-sparse attention without positions, a
selection of blocks for each key/value head (``S``), beside lightning linear
attention (``L``) — against the plain reference
(``benchmarks/reference/minicpm_sala.py``: the recurrence token by token, the
selection by a full sort, attention a query head at a time) on logits, loss
and adapter gradients above and below ``dense_len``; the selection itself block
for block; packed documents; the counters; what the model refuses."""

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
from benchmarks.harness.counting import minicpm_sala as counting  # noqa: E402
from benchmarks.harness.programs import minicpm_sala as prog  # noqa: E402
from benchmarks.reference import minicpm_sala as ref  # noqa: E402
from finetune_controller_tpu.models import llama, ssm  # noqa: E402
from finetune_controller_tpu.models.llama import PRESETS, LlamaForCausalLM  # noqa: E402
from finetune_controller_tpu.models.lora import LoRAConfig  # noqa: E402
from finetune_controller_tpu.ops.attention import unpack_selection  # noqa: E402

CONF = json.loads(
    (ROOT / "tests/benchmarks/fixtures/configs/tiny-minicpm-sala.json").read_text())
TARGETS = tuple(CONF["run"]["lora_targets"])
TINY = PRESETS["tiny-minicpm-sala-test"].replace(
    dtype=jnp.float32, lora=LoRAConfig(rank=4, targets=TARGETS))
#: the tests' model: four of the preset's eight layers, a lone sparse layer at
#: either end of a stack of two lightning layers
SMALL = TINY.replace(layer_pattern="SLLS", n_layers=4)
SEED = 2**31 + 49
#: a row above the fixture's dense_len of 32 (ten blocks of 8, of which a
#: query keeps 6; ten chunks of 8 in a lightning layer) and one below it
ROWS = {"sparse": 80, "dense": 24}


def _tokens(batch=2, seq=80, seed=0):
    return np.random.default_rng(seed).integers(
        0, CONF["vocab_size"], (batch, seq)).astype(np.int32)


def _seeded(cfg, seed=SEED):
    """The program's variables with the benchmark's seeded weights: the frozen
    base stored in bf16, the adapters in float32."""
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
    return {program.canonical(path): leaf for path, leaf in
            jax.tree_util.tree_flatten_with_path(tree)[0]}


def _variables(cfg=SMALL, seq=80):
    model = LlamaForCausalLM(cfg)
    tokens = jnp.asarray(_tokens(2, seq) % cfg.vocab_size)
    variables = model.init({"params": jax.random.PRNGKey(0)}, tokens)
    return model, {c: variables[c] for c in ("params", "lora")}, tokens


def _mean_nll(model, params, tokens, **kw):
    def loss(lora):
        logits = model.apply({"params": params, "lora": lora}, tokens,
                             mutable=("sparse_stats",), **kw)[0][:, :-1]
        logp = jax.nn.log_softmax(logits, axis=-1)
        return -jnp.take_along_axis(logp, tokens[:, 1:, None], -1).mean()
    return loss


# ---- the pattern is data -----------------------------------------------------------


def test_the_cut_and_the_published_mixer_types_both_build():
    """``mixer_types`` reads as a pattern in ``S`` / ``L``: the cut's is a lone
    sparse layer before a scanned stack of three lightning layers; the
    published 32 entries are nine runs, two of them stacks of SPARSE layers —
    and the reference places every leaf where the program keeps it."""
    real = json.loads((ROOT / "benchmarks/configs/minicpm-sala-lora.json").read_text())
    cut = prog.model_config(real)
    assert cut.layer_pattern == "SLLL"
    assert cut.pattern_runs() == (("S", 1), ("L", 3))
    published = prog.model_config({
        **real, "num_hidden_layers": 32, "vocab_size": 73448,
        "mixer_types": real["published"]["mixer_types"]})
    assert published.pattern_runs() == (
        ("S", 1), ("L", 8), ("S", 1), ("L", 6), ("S", 2), ("L", 4), ("S", 1),
        ("L", 6), ("S", 3))
    assert published.layer_pattern.count("S") == 8
    assert round(published.param_count() / 1e9, 2) == 9.48
    assert cut.param_count() == 1_184_941_056
    # the branches are scaled for the model's depth, not the cut's
    assert cut.residual_multiplier == published.residual_multiplier == 1.4 / 32 ** 0.5
    assert cut.embedding_multiplier == 12.0 and cut.head_in_multiplier == 1 / 16
    for cfg in (cut, published, prog.model_config(CONF)):
        want, at, stacks = [], 0, 0
        for unit, repeats in cfg.pattern_runs():
            if repeats == 1:
                want.append((f"layer_{at}", 0, 0, unit))
            else:
                stack = f"blocks_{at}" if stacks else "blocks"
                want += [(f"{stack}/layer_{j}", r, repeats, k)
                         for r in range(repeats) for j, k in enumerate(unit)]
                stacks += 1
            at += len(unit) * repeats
        assert [tuple(p) for p in ref.places(cfg.layer_pattern)] == want


@pytest.mark.parametrize("bad", [
    dict(sparse_topk=0), dict(lightning_n_heads=0), dict(sparse_block=24),
    dict(sparse_topk=2), dict(sparse_stride=3), dict(layer_pattern="SLLLLLLM")],
    ids=["no_topk", "no_heads", "block", "forced_over_topk", "stride", "mixed"])
def test_a_pattern_the_model_cannot_build_is_refused(bad):
    with pytest.raises(ValueError, match="pattern|block-sparse"):
        TINY.replace(**bad).pattern_runs()


# ---- the whole model against the reference ---------------------------------------

_REFERENCE: dict = {}


def _reference(seq):
    """``(arch, key, adapters, fn, loss, gradients)`` of the plain reference on
    the fixture at ``_tokens(seq=seq)``, computed once a length."""
    if seq not in _REFERENCE:
        arch = ref.Arch.from_config(CONF)
        key = weights.root_key(SEED)
        lora = ref.init_lora(arch, key)
        fn = ref.make_loss_and_grads(arch, rows_per_block=2)
        _REFERENCE[seq] = (arch, key, lora, fn, *fn(key, lora, _tokens(seq=seq)))
    return _REFERENCE[seq]


@pytest.mark.parametrize("rows", list(ROWS))
def test_logits_loss_and_adapter_gradients_are_the_references(rows):
    """The program in float32 built from the fixture's published keys, seeded
    weights: its leaves are the ones the reference regenerates under the same
    names; logits, loss and every adapter gradient are the reference's — TIGHT
    (float32 sums in another order: the chunked scan against the token-by-token
    recurrence, the packed selection against the sorted one), in a row above
    ``dense_len`` (selection, ten chunks) and one below it (every earlier key)."""
    cfg = prog.model_config(CONF, ssm_chunk=8)      # ten chunks in a row of 80
    assert cfg.layer_pattern == "SLLS"
    model, variables = _seeded(cfg)
    tokens = _tokens(seq=ROWS[rows])
    arch, key, lora, fn, want_loss, want = _reference(ROWS[rows])
    mine = _flat(variables["lora"])
    assert sorted(mine) == sorted(lora)
    for name in lora:
        np.testing.assert_array_equal(mine[name], lora[name])
    drawn = {"embed_tokens/embedding", "final_norm/scale", "lm_head/kernel"}
    for place in ref.places(arch.pattern):
        drawn |= {f"{place.prefix}/{n}" for n in arch.other_shapes(place.kind)}
        drawn |= {f"{place.prefix}/{n}/kernel" for n in arch.proj_shapes(place.kind)}
    assert set(_flat(variables["params"])) == drawn

    loss, grads = jax.jit(jax.value_and_grad(
        _mean_nll(model, variables["params"], tokens)))(variables["lora"])
    assert float(loss) == pytest.approx(want_loss, rel=2e-6)
    logits = jax.jit(lambda v: model.apply(
        v, tokens, mutable=("sparse_stats",))[0])(variables)
    np.testing.assert_allclose(logits, fn.logits(key, lora, tokens), atol=5e-6)
    got = _flat(grads)
    scale = max(float(jnp.abs(g).max()) for g in want.values())
    for name in want:
        np.testing.assert_allclose(got[name], want[name], rtol=2e-3,
                                   atol=2e-5 * scale, err_msg=name)
        assert float(jnp.abs(want[name]).max()) > 0, name


def test_in_bf16_the_program_stays_within_the_stated_tolerance():
    """The cell's compute type: bf16 products with float32 sums, bf16
    activations between layers.  Against the float32 reference the loss moves
    by ~1e-4 relative and the adapters' gradient by ~2 % of its norm — bf16's
    2^-8 a rounding, a few dozen roundings a layer that mostly cancel; 5e-4
    and 6 % are the limits here, three times what this seed reads.  (On the
    chip the cell's own limits are read on 12 seeds, ``PERF.md`` section 4.)"""
    conf = {**CONF, "run": {**CONF["run"], "compute_dtype": "bfloat16"}}
    model, variables = _seeded(prog.model_config(conf, ssm_chunk=8))
    tokens = _tokens()
    loss, grads = jax.jit(jax.value_and_grad(
        _mean_nll(model, variables["params"], tokens)))(variables["lora"])
    *_, want_loss, want = _reference(80)
    assert float(loss) == pytest.approx(want_loss, rel=5e-4)
    got = _flat(grads)
    gap = np.sqrt(sum(float(jnp.sum((got[n] - want[n]) ** 2)) for n in want))
    norm = np.sqrt(sum(float(jnp.sum(want[n] ** 2)) for n in want))
    assert gap / norm < 0.06, gap / norm


# ---- the selection ---------------------------------------------------------------


def _qk(seq, seed=0, planted=False):
    """q (1, S, 8, 16) and k (1, S, 2, 16) in float32; ``planted``: the keys
    repeat with a period of one block, so every block's compressed keys — and
    its score, bit for bit — are the same: EVERY choice past the forced blocks
    is a tie, which goes to the lower block."""
    rng = np.random.default_rng(seed)
    q = rng.normal(size=(1, seq, 8, 16)).astype(np.float32)
    k = rng.normal(size=(1, seq, 2, 16)).astype(np.float32)
    if planted:
        k = np.tile(k[:, :TINY.sparse_block], (1, seq // TINY.sparse_block, 1, 1))
    return jnp.asarray(q), jnp.asarray(k)


@pytest.mark.parametrize("planted", [False, True], ids=["drawn", "planted_ties"])
@pytest.mark.parametrize("seq", [80, 128])
def test_the_selection_is_the_references_block_for_block(seq, planted):
    """``block_selection`` (a bit-by-bit threshold, ties by position) against
    the reference's full stable sort, on drawn scores and on scores where
    every block ties: the same blocks, and the packed words attention takes
    hold exactly their keys."""
    q, k = _qk(seq, planted=planted)
    arch = ref.Arch.from_config(CONF)
    picked, keys = llama.block_selection(TINY, q, k, None)
    want = ref.selected_blocks(arch, q, k)
    np.testing.assert_array_equal(picked, want)
    if planted:     # ties to the LOWER block: the first and the last two, then 1, 2, 3
        last = np.flatnonzero(np.asarray(picked[0, 0, seq - 1]))
        assert list(last) == [0, 1, 2, 3, seq // 8 - 2, seq // 8 - 1]
    from finetune_controller_tpu.ops.attention import pack_block_selection

    words = pack_block_selection(picked, TINY.sparse_block)
    assert words.shape == (1, 2, seq, 128) and words.dtype == jnp.int32
    np.testing.assert_array_equal(
        unpack_selection(words, seq), np.repeat(np.asarray(picked), 8, axis=-1))
    # the counter: whole blocks but the query's own, cut at the query
    at = np.arange(seq)
    np.testing.assert_array_equal(
        keys[0, 0], 8 * np.asarray(picked[0, 0]).sum(-1) - (7 - at % 8))
    assert float(keys.mean()) == counting.selected_keys_mean(CONF, seq)


def test_the_first_block_and_the_window_are_forced_whatever_the_scores():
    """A query keeps block 0 and the two blocks that end with its own
    (``window_size`` 16 of ``block_size`` 8) even where their scores are the
    lowest; ``min(causal blocks, topk)`` blocks in all, none after its own."""
    q, k = _qk(128, seed=3)
    # keys of the forced blocks point AWAY from every query: lowest scores
    k = k.at[:, :8].set(-10.0 * jnp.sign(q[:, :8, :1].mean((1, 2), keepdims=True)))
    picked = np.asarray(llama.block_selection(TINY, q, k, None)[0])
    own = np.arange(128) // 8
    for t in (0, 7, 8, 47, 48, 100, 127):
        mine = picked[0, :, t]
        assert mine[:, 0].all() and mine[:, own[t]].all()
        assert mine[:, max(own[t] - 1, 0)].all()
        assert not mine[:, own[t] + 1:].any()
        assert (mine.sum(-1) == min(own[t] + 1, TINY.sparse_topk)).all()
    assert TINY.sparse_blocks_forced() == 3


def test_two_documents_in_a_row_are_the_same_documents_alone():
    """Packed rows restart: a row that holds two documents of 40 tokens (five
    blocks each: the second one's blocks and compressed windows start where
    its own grid would) gives each the logits it has alone — the lightning
    state, the compressed keys, the forced first block and the attention all
    stay inside a document; with one id for the whole row the second
    document reads the first."""
    cfg = SMALL.replace(sparse_dense_len=16)
    model, variables, _ = _variables(cfg)
    tokens = jnp.asarray(_tokens(1, 80, seed=5))
    pos = jnp.asarray([list(range(40)) * 2])

    @jax.jit
    def packed(seg):
        return model.apply(variables, tokens, positions=pos, segment_ids=seg,
                           mutable=("sparse_stats",))[0]

    alone = jax.jit(lambda: model.apply(
        variables, tokens.reshape(2, 40), mutable=("sparse_stats",))[0])()
    marked = packed(jnp.asarray([[0] * 40 + [1] * 40]))
    np.testing.assert_allclose(marked[0, :40], alone[0], atol=2e-5)
    np.testing.assert_allclose(marked[0, 40:], alone[1], atol=2e-5)
    unmarked = packed(jnp.zeros((1, 80), jnp.int32))
    np.testing.assert_allclose(unmarked[0, :40], alone[0], atol=2e-5)
    assert float(jnp.abs(unmarked[0, 40:] - alone[1]).max()) > 1e-3


# ---- layers, remat and what is kept --------------------------------------------------


_UNROLLED: dict = {}


def _unrolled(cfg, variables, tokens):
    """Loss and adapter gradients of the unrolled, un-rematerialised model on
    the stacks' leaves, layer by layer — computed once."""
    if not _UNROLLED:
        plain = LlamaForCausalLM(cfg.replace(scan_layers=False, remat=False))
        flat = {c: {} for c in variables}
        for c in variables:
            for name, leaves in variables[c].items():
                if not name.startswith("blocks"):
                    flat[c][name] = leaves
                    continue
                first = 0 if name == "blocks" else int(name.split("_")[1])
                for stacked in leaves.values():
                    for r in range(jax.tree.leaves(stacked)[0].shape[0]):
                        flat[c][f"layer_{first + r}"] = jax.tree.map(
                            lambda a: a[r], stacked)
        _UNROLLED["got"] = jax.jit(jax.value_and_grad(
            _mean_nll(plain, flat["params"], tokens)))(flat["lora"])
    return _UNROLLED["got"]


@pytest.mark.parametrize("policy", ["full"])
def test_scanned_stacks_of_both_kinds_compute_the_unrolled_models_gradients(policy):
    """``SSLLS``: a stack of sparse layers (the published pattern has two)
    and one of lightning layers, each layer under its own remat, give the
    unrolled, un-rematerialised model's loss, counter and adapter gradients."""
    cfg = TINY.replace(layer_pattern="SSLLS", n_layers=5, remat_policy=policy)
    assert cfg.pattern_runs() == (("S", 2), ("L", 2), ("S", 1))
    model, variables, tokens = _variables(cfg)
    want, g_want = _unrolled(cfg, variables, tokens)
    got, g_got = jax.jit(jax.value_and_grad(
        _mean_nll(model, variables["params"], tokens)))(variables["lora"])
    assert float(got) == pytest.approx(float(want), rel=1e-6)
    np.testing.assert_allclose(
        g_got["layer_4"]["sparse_attn"]["q_proj"]["lora_a"],
        g_want["layer_4"]["sparse_attn"]["q_proj"]["lora_a"], rtol=1e-4, atol=1e-9)
    np.testing.assert_allclose(
        g_got["blocks"]["layer_0"]["sparse_attn"]["o_gate"]["lora_b"][1],
        g_want["layer_1"]["sparse_attn"]["o_gate"]["lora_b"], rtol=1e-4, atol=1e-9)
    np.testing.assert_allclose(
        g_got["blocks_2"]["layer_0"]["lightning"]["k_proj"]["lora_a"][1],
        g_want["layer_3"]["lightning"]["k_proj"]["lora_a"], rtol=1e-4, atol=1e-9)
    if policy == "full":
        _, sown = jax.jit(lambda v: model.apply(v, tokens, mutable=cfg.sown))(variables)
        counters = cfg.sown_readings(sown)[1]
        assert float(counters["sparse_selected_keys_mean"]) == pytest.approx(
            counting.selected_keys_mean(CONF, 80))


@pytest.mark.parametrize("policy", ["full", "mlp"])
def test_the_selection_is_made_once_a_step_and_kept_for_the_backward_pass(policy):
    """Under remat the backward pass replays a layer and NOT its selection:
    the gradient's program holds one bit-by-bit search (a ``while`` of 32
    rounds) a sparse layer, as the forward pass alone does."""
    cfg = SMALL.replace(remat_policy=policy)
    assert "sparse_selection" in llama.ALWAYS_KEPT
    model, variables, tokens = _variables(cfg)
    loss = _mean_nll(model, variables["params"], tokens)

    def searches(fn):
        text = str(jax.make_jaxpr(fn)(variables["lora"]))
        return text.count("cummax") or text.count("population_count"), text.count(
            "shift_left")

    forward, backward = searches(loss), searches(jax.grad(loss))
    assert forward[1] >= 2 and backward[1] == forward[1]


def test_no_gradient_flows_through_the_selections_scores():
    """The block scores are made of stopped q and k: nothing the selection
    returns carries a gradient to either."""
    q, k = _qk(80, seed=1)

    def through(q, k):
        picked, keys = llama.block_selection(TINY, q, k, None)
        return picked.astype(jnp.float32).sum() + keys.sum() + 0.0 * (q.sum() + k.sum())

    dq, dk = jax.grad(through, argnums=(0, 1))(q, k)
    assert not np.asarray(dq).any() and not np.asarray(dk).any()


# ---- the lightning mixer -----------------------------------------------------------


def test_lightning_decays_are_the_fixed_slopes():
    log_decay = np.asarray(ssm.lightning_log_decay(32))
    np.testing.assert_allclose(
        np.exp(log_decay), [np.exp(-2.0 ** (-8 * h / 32)) for h in range(1, 33)],
        rtol=1e-6)
    assert 0.42 < np.exp(log_decay[0]) < 0.44 and 0.996 < np.exp(log_decay[-1]) < 0.9962


def test_lightning_mixer_is_the_recurrence_token_by_token():
    """One mixer at the preset's sizes against the definition itself, a
    Python loop over tokens with a float32 state: ``S_t = lambda_h S_{t-1} +
    k_t^T v_t``, ``o_t = d^-0.5 q_t S_t`` — over ten chunks of 8."""
    cfg = TINY
    mixer = ssm.LightningMixer(cfg)
    u = jnp.asarray(np.random.default_rng(2).normal(size=(1, 80, 64)), jnp.float32)
    pos = jnp.arange(80)[None]
    variables = mixer.init({"params": jax.random.PRNGKey(1)}, u, pos)
    got = mixer.apply(variables, u, pos)
    p = variables["params"]

    def heads(name):
        return (u @ p[name]["kernel"]).reshape(1, 80, 4, 16)

    def normed(x, scale):
        return x * jax.lax.rsqrt((x * x).mean(-1, keepdims=True) + cfg.rms_eps) * scale

    q = llama.apply_rope(normed(heads("q_proj"), p["q_norm"]["scale"]), pos, 1e4)
    k = llama.apply_rope(normed(heads("k_proj"), p["k_norm"]["scale"]), pos, 1e4)
    v = heads("v_proj")
    decay = np.exp(-2.0 ** (-8 * np.arange(1, 5) / 4))
    state = np.zeros((4, 16, 16), np.float64)
    out = np.zeros((80, 4, 16))
    for t in range(80):
        state = state * decay[:, None, None] + np.einsum(
            "hn,hp->hnp", np.asarray(k[0, t], np.float64), np.asarray(v[0, t], np.float64))
        out[t] = np.einsum("hn,hnp->hp", np.asarray(q[0, t], np.float64), state) / 4.0
    y = jnp.asarray(out.reshape(1, 80, 64), jnp.float32)
    y = normed(y, p["o_norm"]["scale"])
    want = (y * jax.nn.sigmoid(u @ p["o_gate"]["kernel"])) @ p["o_proj"]["kernel"]
    np.testing.assert_allclose(got, want, atol=2e-5)


# ---- counters, refusals ------------------------------------------------------------


def test_run_description_carries_the_new_counters():
    from finetune_controller_tpu.parallel.mesh import MeshSpec

    mesh = MeshSpec(fsdp=1).build(jax.devices()[:1])
    real = prog.model_config(json.loads(
        (ROOT / "benchmarks/configs/minicpm-sala-lora.json").read_text()))
    attrs = real.run_description(seq_len=32768, tokens_per_microbatch=32768,
                                 attention_impl="xla", mesh=mesh)
    assert attrs["layer_pattern"] == "SLLL"
    assert attrs["layers_by_kind"] == {"L": 3, "S": 1}
    assert (attrs["sparse_blocks_kept"], attrs["sparse_block"],
            attrs["sparse_window"], attrs["sparse_dense_len"],
            attrs["sparse_kv_groups"]) == (64, 64, 2048, 8192, 2)
    assert attrs["lightning_scan_impl"] == "xla"          # here: the CPU
    assert attrs["lightning_chunks_per_row"] == 256
    assert "ssm_layers" not in attrs and "attention_pattern" not in attrs
    from finetune_controller_tpu.ops.pallas import ssd_scan

    assert ssd_scan.ssd_scan_impl(32, 128, 32, 128, 128, backend="tpu") == ("pallas", 16)


def test_every_new_leaf_is_frozen_and_the_projections_carry_adapters():
    _, variables, _ = _variables()
    sparse = variables["params"]["layer_0"]["sparse_attn"]
    lightning = variables["params"]["blocks"]["layer_0"]["lightning"]
    assert sorted(variables["params"]) == [
        "blocks", "embed_tokens", "final_norm", "layer_0", "layer_3", "lm_head"]
    assert sorted(sparse) == ["k_norm", "k_proj", "o_gate", "o_proj", "q_norm",
                              "q_proj", "v_proj"]
    assert sorted(lightning) == sorted([*sparse, "o_norm"])
    assert sparse["k_proj"]["kernel"].shape == (64, 32)         # 2 key/value heads
    assert lightning["k_proj"]["kernel"].shape == (2, 64, 64)   # every head its own
    for tree in (variables["lora"]["layer_3"]["sparse_attn"],
                 variables["lora"]["blocks"]["layer_0"]["lightning"]):
        assert sorted(tree) == ["k_proj", "o_gate", "o_proj", "q_proj", "v_proj"]
    n = sum(a.size for a in jax.tree.leaves(variables["params"]))
    assert n == SMALL.param_count() == SMALL.active_param_count()


def test_decode_pipeline_sequence_split_import_and_export_refuse_the_family():
    from finetune_controller_tpu.models import hf_export, hf_import

    model, variables, tokens = _variables()
    with pytest.raises(NotImplementedError, match="decode"):
        model.apply(variables, tokens[:, :8], decode=True, mutable=["cache"])
    for mixer, args in ((llama.SparseAttention(TINY), ()),
                        (ssm.LightningMixer(TINY), (jnp.arange(8)[None],))):
        with pytest.raises(NotImplementedError, match="decode"):
            mixer.init({"params": jax.random.PRNGKey(0)},
                       jnp.zeros((1, 8, 64)), *args, None, True, True)
    with pytest.raises(NotImplementedError, match="pattern"):
        llama.make_block_stage_fn(TINY)
    for axis in ("sp", "pp"):
        with pytest.raises(ValueError, match="sp = pp = 1"):
            TINY.refuse_mesh({axis: 2})
    with pytest.raises(NotImplementedError, match="pattern"):
        hf_export._hf_layout(TINY)
    with pytest.raises(NotImplementedError, match="pattern"):
        hf_import._map_llama_tensors([], TINY, jnp.float32)
