"""The window/full-attention expert model (ISSUE 43) at toy size on the CPU: a
model whose layers are whole blocks that differ by their ATTENTION's kind
(``LlamaConfig.layer_pattern`` in ``F`` / ``W``) against the plain reference
(``benchmarks/reference/mimo_v2.py``) on three patterns, the sink's share and
the window's edge through the model, rotary embedding on a part of a head at
each kind's base, the sixteen shares of one expert layer against the uncut
layer, the counts at the published keys, and what the model refuses."""

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
from benchmarks.harness.programs import mimo_v2 as prog  # noqa: E402
from benchmarks.reference import mimo_v2 as ref  # noqa: E402
from benchmarks.reference import train as ref_train  # noqa: E402
from benchmarks.reference.model import rope  # noqa: E402
from finetune_controller_tpu.models import llama  # noqa: E402
from finetune_controller_tpu.models.llama import PRESETS, LlamaForCausalLM  # noqa: E402
from finetune_controller_tpu.models.lora import LoRAConfig  # noqa: E402

CONF = json.loads(
    (ROOT / "tests/benchmarks/fixtures/configs/tiny-mimo-v2.json").read_text())
REAL = json.loads(
    (ROOT / "benchmarks/configs/mimo-v2-flash-lora.json").read_text())
TINY = PRESETS["tiny-mimo-v2-test"].replace(
    dtype=jnp.float32, lora=LoRAConfig(rank=4))
SEED = 2**31 + 43
#: the cut's pattern (a leading dense full layer, a stack of four window
#: layers, a full and a window layer by themselves), a two-period pattern
#: that makes a scanned UNIT of unlike layers (``WWF`` twice), and layers of
#: both kinds in another order (three layers, every one by itself)
PATTERNS = {"cut": [0, 1, 1, 1, 1, 0, 1], "unit": [0, 1, 1, 0, 1, 1, 0],
            "order": [0, 1, 0]}
_REFERENCE = {}


def _reference(pattern):
    """``(arch, key, lora, loss, grads)`` of the plain reference on a pattern,
    computed once a session (the tests below share it)."""
    if pattern not in _REFERENCE:
        conf = _conf(PATTERNS[pattern])
        arch = ref.Arch.from_config(conf)
        key = weights.root_key(SEED)
        lora = ref.init_lora(arch, key)
        loss, grads = ref.make_loss_and_grads(arch, rows_per_block=2)(
            key, lora, _tokens())
        _REFERENCE[pattern] = (arch, key, lora, loss, grads)
    return _REFERENCE[pattern]
DENSE_FIRST = [0, 1, 1, 1, 1, 1, 1]


def _conf(kinds, freq=DENSE_FIRST, **changes):
    return {**CONF, "hybrid_layer_pattern": list(kinds),
            "moe_layer_freq": list(freq)[:len(kinds)],
            "num_hidden_layers": len(kinds), **changes}


def _tokens(batch=2, seq=20, seed=0):
    return np.random.default_rng(seed).integers(
        0, CONF["vocab_size"], (batch, seq)).astype(np.int32)


def _seeded(cfg, seed=SEED):
    """The program's variables with the benchmark's seeded weights: the frozen
    base stored in bf16 (the sink float32, as the trainer keeps it), the
    adapters in float32."""
    model = LlamaForCausalLM(cfg)
    shapes = jax.eval_shape(
        lambda: model.init({"params": jax.random.PRNGKey(0)},
                           jnp.zeros((1, 8), jnp.int32)))

    def stored(path, s):
        sink = "sink" in program.canonical(path)
        return jax.ShapeDtypeStruct(s.shape, jnp.float32 if sink else jnp.bfloat16)

    shapes = {"params": jax.tree_util.tree_map_with_path(stored, shapes["params"]),
              "lora": shapes["lora"]}
    return model, program.fill(shapes, weights.root_key(seed), 64)


def _flat(tree):
    return {program.canonical(path): leaf for path, leaf in
            jax.tree_util.tree_flatten_with_path(tree)[0]}


def _variables(cfg=TINY, seq=24):
    model = LlamaForCausalLM(cfg)
    tokens = jnp.asarray(_tokens(2, seq) % cfg.vocab_size)
    variables = model.init({"params": jax.random.PRNGKey(0)}, tokens)
    return model, {c: variables[c] for c in ("params", "lora")}, tokens


# ---- the attention pattern is data -------------------------------------------------


def test_an_attention_pattern_reads_as_runs_of_repeated_units():
    def runs(pattern, dense=1):
        return TINY.replace(layer_pattern=pattern, n_layers=len(pattern),
                            first_k_dense=dense).pattern_runs()

    assert runs("FWWWWFW") == (("f", 1), ("W", 4), ("F", 1), ("W", 1))
    assert runs("FWWFWWF") == (("f", 1), ("WWF", 2))
    assert runs("FWFWWWW") == (("f", 1), ("W", 1), ("F", 1), ("W", 4))
    assert runs("WFWF", 2) == (("w", 1), ("f", 1), ("W", 1), ("F", 1))
    assert runs("FWFW", 0) == (("FW", 2),)
    # the published 48 layers: the leading dense layer, ONE scanned stack of
    # seven six-layer units, a stack of four window layers, the last full one
    published = "".join("FW"[k] for k in REAL["published"]["hybrid_layer_pattern"])
    assert published.count("F") == 9 and published.count("W") == 39
    assert runs(published) == (("f", 1), ("WWWWFW", 7), ("W", 4), ("F", 1))
    # the reference places the leaves where the program keeps them
    for kinds in PATTERNS.values():
        conf = _conf(kinds)
        cfg = prog.model_config(conf)
        want, at = [], 0
        for unit, repeats in cfg.pattern_runs():
            if repeats == 1:
                want.append((f"layer_{at}", 0, 0, unit))
            else:
                want += [(f"blocks/layer_{j}", r, repeats, k)
                         for r in range(repeats) for j, k in enumerate(unit)]
            at += len(unit) * repeats
        assert [tuple(p) for p in ref.places(ref.letters(conf))] == want


@pytest.mark.parametrize("bad", [
    dict(layer_pattern="FWX"), dict(layer_pattern="FW"),
    dict(layer_pattern="FW*"), dict(layer_pattern="FWE"),
    dict(layer_pattern="FWW", attention_kind="mla"),
    dict(layer_pattern="FWW", tie_embeddings=True),
    dict(layer_pattern="FWW", sliding_window=0),
    dict(layer_pattern="FWW", n_experts=0),
    dict(layer_pattern="FWW", ssm_n_heads=4, ssm_head_dim=16, ssm_d_state=8)],
    ids=["letter", "length", "mixed_star", "mixed_expert", "mla", "tied",
         "no_window", "dense_layer_without_experts", "mixer"])
def test_an_attention_pattern_the_model_cannot_build_is_refused(bad):
    with pytest.raises(ValueError, match="pattern"):
        TINY.replace(n_layers=3, **bad).pattern_runs()


# ---- the whole model against the reference ---------------------------------------


@pytest.mark.parametrize("pattern", list(PATTERNS))
def test_loss_gradients_and_one_adamw_step_are_the_references(pattern):
    """The program built from the fixture's published keys with the pattern,
    seeded weights: its leaves are the ones the reference regenerates, under
    the same names and — a kind's ``k_proj`` / ``v_proj`` — in that kind's
    shapes in that kind's layers alone; its loss, every adapter gradient and
    one clipped AdamW step are the reference's.  The reference walks the layers
    in a Python loop, so this also holds every scanned stack — the cut's four
    window layers, the two-period pattern's unit of unlike layers, each layer
    under its own remat — to the unrolled model's loss and gradients."""
    kinds = PATTERNS[pattern]
    conf = _conf(kinds)
    cfg = prog.model_config(conf)
    assert cfg.layer_pattern == "".join("FW"[k] for k in kinds)
    assert (cfg.rotary_dim, cfg.first_k_dense, cfg.n_shared_experts) == (8, 1, 0)
    model, variables = _seeded(cfg)
    tokens = _tokens()
    arch, key, lora, want_loss, want = _reference(pattern)
    mine = _flat(variables["lora"])
    assert sorted(mine) == sorted(lora)
    for name in lora:
        np.testing.assert_array_equal(mine[name], lora[name])
    frozen = _flat(variables["params"])
    drawn = {"embed_tokens/embedding", "final_norm/scale", "lm_head/kernel"}
    for place in ref.places(arch.pattern):
        w = ref.layer_weights(arch, key, place, 0)
        lead = (place.repeats,) if place.repeats else ()
        for name, leaf in w.items():
            full = {"attn_norm": "attn_norm/scale", "mlp_norm": "mlp_norm/scale",
                    "attn/sink": "attn/sink/bias", "moe/router": "moe/router/kernel",
                    }.get(name, f"{name}/kernel")
            full = f"{place.prefix}/{full}"
            drawn.add(full)
            assert frozen[full].shape == lead + leaf.shape, full
        # the kind's own key/value heads, and a sink in a window layer alone
        kv = (2, 4)[place.kind in "wW"]
        assert w["attn/k_proj"].shape == (64, kv * 24)
        assert w["attn/v_proj"].shape == (64, kv * 16)
        assert ("attn/sink" in w) == (place.kind in "wW")
        assert ("mlp/gate_proj" in w) == place.kind.islower()
    assert set(frozen) == drawn

    def mean_nll(lo):
        logits = model.apply({"params": variables["params"], "lora": lo},
                             tokens, mutable=("moe_stats",))[0][:, :-1]
        logp = jax.nn.log_softmax(logits, axis=-1)
        return -jnp.take_along_axis(logp, tokens[:, 1:, None], -1).mean()

    loss, grads = jax.value_and_grad(mean_nll)(variables["lora"])
    assert float(loss) == pytest.approx(want_loss, rel=2e-5)
    got = _flat(grads)
    scale = max(float(jnp.abs(g).max()) for g in want.values())
    for name in want:
        np.testing.assert_allclose(got[name], want[name], rtol=2e-3,
                                   atol=2e-4 * scale, err_msg=name)
        assert float(jnp.abs(want[name]).max()) > 0, name
    # one AdamW step on the clipped gradients, the trainer's optimizer
    import optax

    tx = optax.chain(optax.clip_by_global_norm(1.0),
                     optax.adamw(0.01, weight_decay=0.0))
    updates, _ = tx.update(grads, tx.init(variables["lora"]), variables["lora"])
    stepped = _flat(optax.apply_updates(variables["lora"], updates))
    after, _ = ref_train.AdamW(0.01, weight_decay=0.0, clip_norm=1.0).update(
        lora, want)
    for name in after:
        np.testing.assert_allclose(stepped[name], after[name], rtol=1e-3,
                                   atol=2e-4, err_msg=name)


def test_the_order_of_the_kinds_changes_the_loss():
    """Another order of the kinds is another model."""
    losses = {name: _reference(name)[3] for name in PATTERNS}
    assert len({round(v, 4) for v in losses.values()}) == 3, losses


# ---- one attention layer: the sink, the window, the rotary part ---------------------


def _attention(window, **changes):
    cfg = TINY.replace(lora=LoRAConfig(), **changes)
    module = llama.Attention(cfg, window=window)
    x = jax.random.normal(jax.random.PRNGKey(3), (1, 12, cfg.d_model))
    pos = jnp.arange(12)[None]
    variables = module.init({"params": jax.random.PRNGKey(4)}, x, pos, None)
    return cfg, module, variables, x, pos


def test_the_sink_is_a_leaf_of_a_window_layer_alone_and_is_dropped():
    """Row 0 of a window layer sees one key, its own: it returns ``v_0 x
    0.707 x (1 - p_sink)`` through ``o_proj``; a model built with the flag off
    holds no such leaf, nor does a full layer."""
    cfg, module, variables, x, pos = _attention(True)
    p = variables["params"]
    assert p["sink"]["bias"].shape == (8,) and p["sink"]["bias"].dtype == jnp.float32
    p = jax.tree.map(lambda a: a, p)
    p["sink"]["bias"] = jnp.linspace(-2.0, 2.0, 8)
    got = module.apply({"params": p}, x, pos, None)[0, 0]
    q = (x[0, 0] @ p["q_proj"]["kernel"]).reshape(8, 24)
    k = (x[0, 0] @ p["k_proj"]["kernel"]).reshape(4, 24)
    v = (x[0, 0] @ p["v_proj"]["kernel"]).reshape(4, 16) * 0.707
    s00 = (q * jnp.repeat(k, 2, axis=0)).sum(-1) * 24 ** -0.5   # position 0: no turn
    p_sink = jax.nn.sigmoid(p["sink"]["bias"] - s00)
    ctx = jnp.repeat(v, 2, axis=0) * (1 - p_sink)[:, None]
    np.testing.assert_allclose(got, ctx.reshape(-1) @ p["o_proj"]["kernel"],
                               rtol=2e-5, atol=2e-6)
    assert "sink" not in _attention(True, window_sink=False)[2]["params"]
    assert "sink" not in _attention(False)[2]["params"]
    # and the model's trees: sinks in the window layers, nowhere else
    def sinks(cfg):
        shapes = jax.eval_shape(lambda: LlamaForCausalLM(cfg).init(
            {"params": jax.random.PRNGKey(0)}, jnp.zeros((1, 8), jnp.int32)))
        return {n for n in _flat(shapes["params"]) if "sink" in n}

    assert sinks(TINY) == {"blocks/layer_0/attn/sink/bias", "layer_6/attn/sink/bias"}
    assert not sinks(TINY.replace(window_sink=False))


def test_a_window_layer_sees_four_keys_and_a_full_layer_all():
    def moved(window, at):
        _, module, variables, x, pos = _attention(window)
        base = module.apply(variables, x, pos, None)[0, 9]
        return float(jnp.abs(module.apply(
            variables, x.at[0, at].add(1.0), pos, None)[0, 9] - base).max())

    assert moved(True, 9 - 4) == 0.0 and moved(True, 9 - 3) > 1e-5
    assert moved(False, 9 - 4) > 1e-5 and moved(False, 0) > 1e-5
    assert moved(True, 10) == 0.0 and moved(False, 10) == 0.0


def test_rotary_turns_the_first_columns_at_each_kinds_own_base():
    """8 of 24 columns: the last 16 of every q and k head are the projections'
    own, the first 8 are the reference's rotate-half at the kind's base —
    5e6 in a full layer, 1e4 in a window layer."""
    x = jax.random.normal(jax.random.PRNGKey(5), (2, 12, 3, 24))
    pos = jnp.broadcast_to(jnp.arange(12) * 37, (2, 12))
    for window, theta in ((False, 5e6), (True, 1e4)):
        got = llama._rotate_leading(
            x, pos, llama.rope_inv_freqs(TINY, window), TINY.rotary_dim)
        np.testing.assert_array_equal(got[..., 8:], x[..., 8:])
        np.testing.assert_allclose(got[..., :8], rope(x[..., :8], pos, theta),
                                   rtol=1e-5, atol=1e-5)
        np.testing.assert_allclose(got, ref.rotate_leading(x, pos, theta, 8),
                                   rtol=1e-5, atol=1e-5)
    assert int(192 * 0.334) == 64 and TINY.rotary_dim == int(24 * 0.334)
    whole = llama._rotate_leading(x, pos, llama.rope_inv_freqs(
        TINY.replace(rotary_dim=0), False), 0)
    assert float(jnp.abs(whole[..., 8:] - x[..., 8:]).max()) > 1e-3


def test_the_value_scale_multiplies_the_values():
    _, module, variables, x, pos = _attention(False)
    _, plain, _, _, _ = _attention(False, attention_value_scale=1.0)
    np.testing.assert_allclose(module.apply(variables, x, pos, None),
                               0.707 * plain.apply(variables, x, pos, None),
                               rtol=1e-5, atol=1e-7)


# ---- the held share -------------------------------------------------------------------


def test_the_sixteen_shares_of_an_expert_layer_add_up_to_the_uncut_layer():
    """The shares ``(0, 1) ... (15, 1)`` of one expert layer's sixteen experts,
    each handed its own expert's kernels, add up to the uncut layer's result —
    forward and the gradient with respect to the input; there is no shared
    expert to count once."""
    cfg = TINY.replace(lora=LoRAConfig(), n_layers=1, layer_pattern="W",
                       first_k_dense=0)
    block = llama.Block(cfg, kind="W")
    x = jax.random.normal(jax.random.PRNGKey(7), (1, 24, cfg.d_model))
    pos = jnp.arange(24)[None]
    variables = block.init({"params": jax.random.PRNGKey(8)}, x, pos, None)
    variables = {"params": variables["params"]}
    moe = variables["params"]["moe"]
    assert sorted(moe) == ["experts", "router"]          # no shared expert

    def expert_part(v, b, xx):
        """The block's output less the residual stream after attention."""
        attn_only = xx + llama.Attention(cfg, window=True).apply(
            {"params": v["params"]["attn"]},
            llama.RMSNorm(cfg.rms_eps, cfg.dtype).apply(
                {"params": v["params"]["attn_norm"]}, xx), pos, None)
        return b.apply(v, xx, pos, None, mutable=("moe_stats",))[0] - attn_only

    g = jax.random.normal(jax.random.PRNGKey(9), x.shape)
    whole, whole_dx = jax.value_and_grad(
        lambda xx: (expert_part(variables, block, xx) * g).sum())(x)
    total, total_dx = 0.0, 0.0
    for first in range(16):
        share = llama.Block(cfg.replace(experts_held=(first, 1)), kind="W")
        held = jax.tree.map(lambda a: a, variables)
        held["params"]["moe"] = {
            "router": moe["router"],
            "experts": jax.tree.map(lambda a: a[first:first + 1], moe["experts"])}
        part, dx = jax.value_and_grad(
            lambda xx: (expert_part(held, share, xx) * g).sum())(x)
        total, total_dx = total + part, total_dx + dx
    np.testing.assert_allclose(total, whole, rtol=2e-4)
    np.testing.assert_allclose(total_dx, whole_dx, rtol=2e-3, atol=2e-5)


# ---- counts, counters, rules, refusals -----------------------------------------------


def test_param_counts_at_the_published_keys_and_at_the_cut():
    _, variables, _ = _variables()
    held = sum(a.size for a in jax.tree.leaves(variables["params"]))
    assert TINY.param_count() == held
    assert TINY.active_param_count() == held - 6 * (16 - 4) * 3 * 64 * 32
    published = {**REAL, **REAL["published"], "reduced": []}
    cfg = prog.model_config(published)
    assert cfg.n_layers == 48 and cfg.experts_held is None
    full, window = 89_128_960, 94_371_904                 # a window layer's 64 sinks
    assert cfg._attention_params() == full
    assert cfg._attention_params(window=True) == window
    expert_layer = 256 * 25_165_824 + 1_048_576
    assert cfg._expert_layer_params(256) == expert_layer
    assert cfg.param_count() == 308_778_768_832 == (
        9 * full + 39 * window + 201_326_592 + 47 * expert_layer
        + 48 * 2 * 4096 + 2 * 152_576 * 4096 + 4096)
    # with the selection bias as a leaf, 47 x 256 more
    assert cfg.replace(moe_select_bias=True).param_count() == 308_778_768_832 + 47 * 256
    assert cfg.active_param_count() == cfg.param_count() - 47 * 248 * 25_165_824
    assert cfg.active_param_count() == pytest.approx(15.45e9, rel=1e-3)
    # the cut the cell runs: 3,429,953,856 parameters, 6.86 GB of frozen bf16
    cut = prog.model_config(REAL)
    assert cut.param_count() == 3_429_953_856
    assert cut.experts_held == (0, 16) and cut.n_experts == 256
    assert cut.pattern_runs() == (("f", 1), ("W", 4), ("F", 1), ("W", 1))
    assert (cut.head_widths, cut.rotary_dim, cut.sliding_window) == ((192, 128), 64, 128)
    assert (cut.n_kv_heads, cut.window_kv_heads) == (4, 8)
    assert (cut.rope_theta, cut.window_rope_theta) == (5e6, 1e4)
    import dataclasses

    assert len(dataclasses.fields(cut)) == 91      # ROADMAP.md C6 (80 before PR 49)


def test_trainer_steps_under_a_mesh_as_on_one_device_and_reports_the_kinds(devices8):
    """The sink's partition rule and the kinds' unlike ``k_proj`` / ``v_proj``
    shapes under ``fsdp`` x ``tp``: two steps on a 2 x 2 mesh give one
    device's losses, the sink stays float32 beside a bf16 base, and
    ``train-started`` carries the attention pattern's counters."""
    from jax.sharding import PartitionSpec as P

    from finetune_controller_tpu.parallel.mesh import MeshSpec
    from finetune_controller_tpu.parallel.sharding import LLAMA_RULES
    from finetune_controller_tpu.train.trainer import TrainConfig, Trainer

    def run(mesh):
        trainer = Trainer(TINY.replace(experts_held=(0, 8)), TrainConfig(
            mode="lora", batch_size=4, seq_len=24, total_steps=4,
            learning_rate=0.01, warmup_steps=0, frozen_dtype="bfloat16",
            log_every=10**9, checkpoint_every=10**9), mesh=mesh)
        state = trainer.init_state()
        rng = np.random.default_rng(0)
        losses = []
        for _ in range(2):
            tokens = rng.integers(0, 256, (4, 24)).astype(np.int32)
            state, m = trainer.step(state, trainer._shard_batch(
                {"tokens": tokens, "loss_mask": np.ones((4, 24), np.float32)}))
            losses.append(float(m["loss"]))
        return trainer, state, m, losses

    one, state, metrics, want = run(MeshSpec(fsdp=1).build(devices8[:1]))
    frozen = _flat(state.frozen)
    assert frozen["layer_6/attn/sink/bias"].dtype == jnp.float32
    assert frozen["layer_6/attn/q_proj/kernel"].dtype == jnp.bfloat16
    attrs = one._runtime_attrs()
    assert attrs["attention_pattern"] == "FWWWWFW"
    assert attrs["attention_layers_by_kind"] == {"F": 2, "W": 5}
    assert (attrs["attention_window"], attrs["attention_sink_layers"]) == (4, 5)
    assert attrs["moe_experts_held"] == 8 and "layer_pattern" not in attrs
    assert attrs["moe_held_sum_form"] in ("rows", "choices")
    assert 0 < attrs["moe_held_rows_over_pairs"] <= 1
    assert "flash_window_work_over_need" not in attrs       # the XLA form here
    assert attrs["lora_joined_projections"]["of"] == 19    # 7 + 3 x 4 by leaf
    assert float(metrics["moe_pairs"]) > 0 and "moe_load_max_over_mean" in metrics
    assert float(metrics["moe_pairs_over_bound"]) == 0
    _, _, _, got = run(MeshSpec(fsdp=2, tp=2).build(devices8[:4]))
    np.testing.assert_allclose(got, want, rtol=2e-5)
    assert got[1] != got[0]
    assert LLAMA_RULES.spec_for("blocks/layer_0/attn/sink/bias") == P()


def test_the_flash_path_reports_the_window_kernels_work(monkeypatch):
    from finetune_controller_tpu.ops.pallas.flash_attention import (
        causal_work_over_need, window_work_over_need)
    from finetune_controller_tpu.parallel.mesh import MeshSpec
    from finetune_controller_tpu.train.trainer import TrainConfig, Trainer

    trainer = Trainer(TINY.replace(attention_impl="pallas"), TrainConfig(
        mode="lora", batch_size=2, seq_len=16, total_steps=2),
        mesh=MeshSpec(fsdp=1).build(jax.devices()[:1]))
    attrs = trainer._runtime_attrs()
    assert attrs["flash_window_work_over_need"] == window_work_over_need(
        16, 4, head_widths=(24, 16))
    assert attrs["flash_causal_work_over_need"] == causal_work_over_need(
        16, head_widths=(24, 16))


def test_the_flash_path_runs_the_window_kernels_under_their_own_names():
    """With ``attention_impl="pallas"`` a window layer calls the kernels with
    its window and its sink — ``flash_swa_*`` in the traced step, the full
    layer's calls under the names they always had — and the model's output is
    the XLA form's (the kernels' gradients: ``test_flash_attention_window``)."""
    cfg = TINY.replace(attention_impl="pallas", scan_layers=False, remat=False,
                       n_layers=2, layer_pattern="FW", first_k_dense=1)
    model, variables, tokens = _variables(cfg, seq=16)
    variables = jax.tree.map(lambda a: a, variables)
    variables["params"]["layer_1"]["attn"]["sink"]["bias"] = jnp.linspace(-1.0, 1.0, 8)

    def loss(lora, c):
        out = LlamaForCausalLM(c).apply(
            {"params": variables["params"], "lora": lora}, tokens,
            mutable=("moe_stats",))[0]
        return (out * jnp.cos(jnp.arange(out.size).reshape(out.shape))).mean()

    text = str(jax.make_jaxpr(jax.grad(loss), static_argnums=1)(
        variables["lora"], cfg))
    for name in ("flash_swa_fwd", "flash_swa_bwd_dq", "flash_swa_bwd_dkv",
                 "flash_fwd", "flash_bwd_dq", "flash_bwd_dkv"):
        assert name in text, name
    got = loss(variables["lora"], cfg)
    want = loss(variables["lora"], cfg.replace(attention_impl="xla"))
    np.testing.assert_allclose(got, want, rtol=2e-4)


def test_decode_raises():
    model, variables, tokens = _variables()
    with pytest.raises(NotImplementedError, match="decode"):
        model.apply(variables, tokens, decode=True, mutable=["cache"])
    cfg, module, v, x, pos = _attention(True)
    with pytest.raises(NotImplementedError, match="decode"):
        module.apply(v, x, pos, None, True, True, mutable=["cache"])


def test_pipeline_stage_refuses_an_attention_pattern():
    with pytest.raises(NotImplementedError, match="pattern"):
        llama.make_block_stage_fn(TINY)


@pytest.mark.parametrize("axis", ["sp", "pp"])
def test_trainer_refuses_a_split_sequence_and_a_pipeline(axis, devices8):
    from finetune_controller_tpu.parallel.mesh import MeshSpec
    from finetune_controller_tpu.train.trainer import TrainConfig, Trainer

    mesh = MeshSpec(**{axis: 2}).build(devices8[:2])
    with pytest.raises(ValueError, match="sp = pp = 1"):
        Trainer(TINY, TrainConfig(mode="lora", batch_size=2, seq_len=16,
                                  total_steps=2), mesh=mesh)


def test_export_and_import_refuse_an_attention_pattern(tmp_path):
    from finetune_controller_tpu.models.hf_export import export_merged_checkpoint
    from finetune_controller_tpu.models.hf_import import _map_llama_tensors

    with pytest.raises(NotImplementedError, match="pattern"):
        export_merged_checkpoint(TINY, {"params": {}}, tmp_path / "nope")
    assert not (tmp_path / "nope").exists()
    with pytest.raises(NotImplementedError, match="pattern"):
        _map_llama_tensors(iter(()), TINY, jnp.float32)


# ---- the program refuses what it does not compute -------------------------------------


@pytest.mark.parametrize("change, match", [
    (dict(n_group=2), "n_group"), (dict(topk_group=2), "topk_group"),
    (dict(norm_topk_prob=False), "norm_topk_prob"),
    (dict(n_shared_experts=1), "n_shared_experts"),
    (dict(attention_bias=True), "attention_bias"),
    (dict(add_full_attention_sink_bias=True), "add_full_attention_sink_bias"),
    (dict(scoring_func="softmax"), "scoring_func"),
    (dict(hybrid_layer_pattern=[0, 1, 1]), "hybrid_layer_pattern"),
    (dict(moe_layer_freq=[0, 1, 1]), "moe_layer_freq"),
    (dict(hybrid_layer_pattern=[0, 1, 1, 1, 1, 0, 2]), "hybrid_layer_pattern"),
    (dict(moe_layer_freq=[0, 1, 1, 1, 1, 0, 1]), "dense layer after"),
    (dict(sliding_window=8), "sliding_window"),
    (dict(swa_head_dim=32), "swa_head_dim"),
    (dict(swa_v_head_dim=8), "swa_v_head_dim"),
    (dict(swa_num_attention_heads=4), "swa_num_attention_heads"),
    (dict(layernorm_epsilon=1e-6), "layernorm_epsilon"),
    (dict(routed_scaling_factor=2.5), "scaling"),
    (dict(tie_word_embeddings=True), "tie_word_embeddings")])
def test_the_program_refuses_what_it_does_not_compute(change, match):
    with pytest.raises(ValueError, match=match):
        prog.model_config({**CONF, **change})
    prog.model_config(CONF)
