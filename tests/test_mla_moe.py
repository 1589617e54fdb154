"""The latent-attention expert model (ISSUE 27) at toy size on the CPU: the
program's blocks against the plain reference (``benchmarks/reference/
mla_moe.py``), dropless routing against a per-token loop, and the shares of
``experts_held`` against the uncut layer."""

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
from benchmarks.harness.programs import mla_moe as prog  # noqa: E402
from benchmarks.reference import mla_moe as ref  # noqa: E402
from benchmarks.reference.model import head_logits, top_weights  # noqa: E402
from finetune_controller_tpu.models.llama import (  # noqa: E402
    MLP, PRESETS, LlamaForCausalLM, apply_rope)
from finetune_controller_tpu.models.lora import MLA_TARGETS, LoRAConfig  # noqa: E402
from finetune_controller_tpu.models.moe import MoEMLP  # noqa: E402

CONF = json.loads(
    (ROOT / "tests/benchmarks/fixtures/configs/tiny-mla-moe.json").read_text())
SEED = 2**31 + 27


def _tokens(batch=2, seq=16, seed=0):
    return np.random.default_rng(seed).integers(
        0, CONF["vocab_size"], (batch, seq)).astype(np.int32)


def _seeded(cfg, seed=SEED):
    """The program's variables with the benchmark's seeded weights: the
    frozen base stored in bf16 (the configuration's ``frozen_dtype``), the
    adapters in float32."""
    model = LlamaForCausalLM(cfg)
    shapes = jax.eval_shape(
        lambda: model.init({"params": jax.random.PRNGKey(0)},
                           jnp.zeros((1, 8), jnp.int32)))
    shapes = {
        "params": jax.tree.map(
            lambda s: jax.ShapeDtypeStruct(s.shape, jnp.bfloat16), shapes["params"]),
        "lora": shapes["lora"]}
    return model, program.fill(shapes, weights.root_key(seed), 64)


def _program_loss(model, variables, tokens):
    def mean_nll(lora):
        logits = model.apply({"params": variables["params"], "lora": lora},
                             tokens)[:, :-1]
        logp = jax.nn.log_softmax(logits, axis=-1)
        return -jnp.take_along_axis(logp, tokens[:, 1:, None], -1).mean()

    return mean_nll


def _flat(tree):
    return {program.canonical(p): v for p, v in
            jax.tree_util.tree_flatten_with_path(tree)[0]}


def _reference_logits(arch, key, lora, tokens):
    top = top_weights(arch, key)
    x = top["embedding"][tokens].astype(jnp.float32)
    pos = jnp.broadcast_to(jnp.arange(x.shape[1]), x.shape[:2])
    for l in range(arch.n_layers):
        prefix, index, dense = ref._place(arch, l)
        x = ref.layer_forward(
            arch, ref.layer_weights(arch, key, prefix, index, dense),
            ref.layer_lora(lora, prefix, index), x, pos, dense)
    return head_logits(arch, top, x)


def _compare_with_reference(conf, **overrides):
    cfg = prog.model_config(conf, dtype=jnp.float32, remat=False, **overrides)
    model, variables = _seeded(cfg)
    tokens = jnp.asarray(_tokens())
    arch = ref.Arch.from_config(conf)
    key = weights.root_key(SEED)
    lora0 = ref.init_lora(arch, key)
    assert set(lora0) == set(_flat(variables["lora"]))
    logits = model.apply(variables, tokens)
    np.testing.assert_allclose(
        logits, _reference_logits(arch, key, lora0, tokens), atol=2e-5)
    loss, grads = jax.value_and_grad(_program_loss(model, variables, tokens))(
        variables["lora"])
    ref_loss, ref_grads = ref.make_loss_and_grads(arch, rows_per_block=1)(
        key, lora0, np.asarray(tokens))
    assert abs(float(loss) - ref_loss) < 1e-5
    for name, g in _flat(grads).items():
        assert float(jnp.abs(ref_grads[name]).max()) > 0, name
        np.testing.assert_allclose(g, ref_grads[name], rtol=2e-3, atol=2e-8,
                                   err_msg=name)


def test_latent_attention_block_matches_the_reference_logits_and_lora_gradients():
    """(a) q/k heads of 16 + 8 beside v heads of 16, interleaved RoPE, the
    one rotary key head shared: two layers of latent attention + dense MLP,
    unrolled, against the plain reference — logits and every adapter's
    gradient."""
    conf = dict(CONF, num_hidden_layers=2, first_k_dense_replace=2)
    _compare_with_reference(conf, n_experts=0, first_k_dense=0, scan_layers=False)


def test_dense_then_expert_layers_match_the_reference_logits_and_lora_gradients():
    """The whole program — a leading dense layer outside the scanned stack,
    then scanned expert layers (dropless sigmoid top-2 of 8, selection bias,
    shared expert) — against the plain reference."""
    _compare_with_reference(dict(CONF, num_hidden_layers=3))


def test_interleaved_rope_rotates_adjacent_pairs():
    x = jax.random.normal(jax.random.PRNGKey(0), (1, 5, 2, 8))
    pos = jnp.arange(5)[None]
    out = apply_rope(x, pos, 10000.0, interleave=True)
    np.testing.assert_allclose(out, ref.rope_pairs(x, pos, 10000.0), atol=1e-6)
    # the same rotation as rotate-half on the de-interleaved vector
    perm = np.r_[0:8:2, 1:8:2]
    half = apply_rope(x[..., perm], pos, 10000.0)
    np.testing.assert_allclose(out[..., perm], half, atol=1e-6)


def test_latent_attention_refuses_to_decode():
    cfg = PRESETS["tiny-mla-moe-test"]
    model = LlamaForCausalLM(cfg)
    tokens = jnp.zeros((1, 8), jnp.int32)
    variables = model.init({"params": jax.random.PRNGKey(0)}, tokens)
    with pytest.raises(NotImplementedError, match="latent paged cache"):
        model.apply({"params": variables["params"]}, tokens, decode=True,
                    mutable=("cache",))


def test_param_counts_cover_the_new_layer_kinds():
    cfg = PRESETS["tiny-mla-moe-test"]
    variables = LlamaForCausalLM(cfg).init(
        {"params": jax.random.PRNGKey(0)}, jnp.zeros((1, 8), jnp.int32))
    stored = sum(x.size for x in jax.tree.leaves(variables["params"]))
    assert cfg.param_count() == stored
    idle = ((cfg.n_experts - cfg.moe_top_k) * 3 * cfg.d_model * cfg.moe_d_ff
            * (cfg.n_layers - cfg.first_k_dense))
    assert cfg.param_count() - cfg.active_param_count() == idle


# ---- the expert layer alone ---------------------------------------------------

D, F, E, K = 16, 8, 8, 2
SHARES = ((0, 3), (3, 1), (4, 4))


def _layer(**kw):
    cfg = PRESETS["tiny-mla-moe-test"].replace(
        d_model=D, dtype=jnp.float32, lora=LoRAConfig())
    kw.setdefault("shared", MLP(cfg, d_ff=F, parent=None))
    kw.setdefault("select_bias", True)
    return MoEMLP(d_model=D, d_ff=F, n_experts=E, top_k=K, dispatch="dropless",
                  scoring="sigmoid", routed_scale=2.5,
                  aux_loss=False, dtype=jnp.float32, **kw)


def _loop(params, x, bias):
    """The layer one token at a time, in numpy float64."""
    p = jax.tree.map(lambda a: np.asarray(a, np.float64), params)

    def swiglu(row, k):
        gate = row @ k["gate_proj"]
        return ((gate / (1.0 + np.exp(-gate))) * (row @ k["up_proj"])) @ k["down_proj"]

    out = np.zeros((x.shape[0], D))
    for t, row in enumerate(np.asarray(x, np.float64)):
        s = 1.0 / (1.0 + np.exp(-(row @ p["router"]["kernel"])))
        chosen = np.argsort(-(s + bias), kind="stable")[:K]
        w = s[chosen] / (s[chosen].sum() + 1e-20) * 2.5
        for e, we in zip(chosen, w):
            out[t] += we * swiglu(
                row, {n: p["experts"][n]["kernel"][e] for n in p["experts"]})
        out[t] += swiglu(row, {n: p["shared"][n]["kernel"] for n in p["shared"]})
    return out


@pytest.mark.parametrize("forced", [False, True], ids=["spread", "all_to_two_experts"])
def test_dropless_routing_equals_a_per_token_loop(forced):
    """(c) No pair is dropped at any imbalance (``forced``: a selection bias
    sends EVERY token to the same two experts, so two groups hold all the
    rows); the bias chooses and does not weigh, the chosen scores are
    normalised and scaled by 2.5, the shared expert counts once."""
    layer = _layer()
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 12, D), jnp.float32)
    params = layer.init({"params": jax.random.PRNGKey(2)}, x)["params"]
    bias = np.asarray(jax.random.normal(jax.random.PRNGKey(3), (E,))) * 0.1
    if forced:
        bias[[3, 5]] += 10.0
    params = dict(params, router=dict(params["router"],
                                      bias=jnp.asarray(bias, jnp.float32)))
    out, stats = layer.apply({"params": params}, x, mutable=("moe_stats",))
    stats = stats["moe_stats"]
    assert float(stats["pairs"][0]) == x.shape[0] * x.shape[1] * K
    if forced:
        assert float(stats["load_max_over_mean"][0]) == E / K
    np.testing.assert_allclose(out.reshape(-1, D),
                               _loop(params, x.reshape(-1, D), bias), atol=2e-5)

    # the gradient passes both permutations as gathers: against a difference
    def f(xx):
        return (layer.apply({"params": params}, xx) ** 2).sum()

    probe = jax.random.normal(jax.random.PRNGKey(4), x.shape) * 1e-3
    np.testing.assert_allclose(
        float((jax.grad(f)(x) * probe).sum()),
        float(f(x + probe) - f(x - probe)) / 2, rtol=2e-2)


def test_a_layer_without_the_selection_bias_is_the_layer_with_it_at_zero():
    """What a run that holds the bias at zero builds (no leaf) computes, bit
    for bit, what the layer with a zero bias does: values and gradient."""
    with_bias, without = _layer(), _layer(select_bias=False)
    x = jax.random.normal(jax.random.PRNGKey(8), (2, 12, D), jnp.float32)
    params = with_bias.init({"params": jax.random.PRNGKey(9)}, x)["params"]
    assert not np.asarray(params["router"]["bias"]).any()      # zeros at init
    bare = dict(params, router={"kernel": params["router"]["kernel"]})
    assert "bias" not in without.init({"params": jax.random.PRNGKey(9)}, x)[
        "params"]["router"]
    np.testing.assert_array_equal(with_bias.apply({"params": params}, x),
                                  without.apply({"params": bare}, x))
    grad = [jax.grad(lambda xx, m=m, p=p: (m.apply({"params": p}, xx) ** 2).sum())(x)
            for m, p in ((with_bias, params), (without, bare))]
    np.testing.assert_array_equal(*grad)


def test_shares_of_experts_held_add_up_to_the_uncut_layer():
    """(d) Each share routes over ALL experts and computes its own experts'
    part; the parts of every share plus the shared expert ONCE are the uncut
    layer — the program's, and the plain reference's part by part."""
    full = _layer()
    x = jax.random.normal(jax.random.PRNGKey(5), (1, 24, D), jnp.float32)
    params = full.init({"params": jax.random.PRNGKey(6)}, x)["params"]
    params = dict(params, router=dict(params["router"], bias=0.1 * jax.random.normal(
        jax.random.PRNGKey(7), (E,))))
    whole = full.apply({"params": params}, x)
    shared_only = full.shared.apply({"params": params["shared"]}, x)
    arch = ref.Arch.from_config(dict(CONF, hidden_size=D, moe_intermediate_size=F))
    total, pairs = shared_only, 0.0
    for first, count in SHARES:
        share = _layer(experts_held=(first, count), shared=None)
        kernels = jax.tree.map(lambda a: a[first:first + count], params["experts"])
        part, stats = share.apply(
            {"params": {"router": params["router"], "experts": kernels}}, x,
            mutable=("moe_stats",))
        pairs += float(stats["moe_stats"]["pairs"][0])
        total = total + part
        # the reference, given the same share of the same weights
        w = {"moe/router": params["router"]["kernel"],
             "moe/router/bias": params["router"]["bias"],
             **{f"moe/experts/{n}": kernels[n]["kernel"] for n in kernels}}
        np.testing.assert_allclose(
            part.reshape(-1, D),
            ref.routed_experts(arch._replace(experts_held=(first, count)),
                               w, x.reshape(-1, D)), atol=1e-5)
    assert pairs == 24 * K
    np.testing.assert_allclose(total, whole, atol=1e-5)


def test_trainer_step_reports_the_routing_counters_and_no_auxiliary_loss():
    from finetune_controller_tpu.train.trainer import TrainConfig, Trainer

    cfg = PRESETS["tiny-mla-moe-test"].replace(
        lora=LoRAConfig(rank=4, targets=MLA_TARGETS))
    trainer = Trainer(cfg, TrainConfig(
        mode="lora", total_steps=2, batch_size=2, seq_len=16, warmup_steps=0,
        shard_audit="raise"))
    state = trainer.init_state()
    tokens = _tokens(2, 16)
    state, metrics = trainer.step(
        state, {"tokens": tokens, "loss_mask": np.ones(tokens.shape, np.float32)})
    assert float(metrics["moe_pairs"]) == 2 * 16 * cfg.moe_top_k
    assert float(metrics["moe_load_max_over_mean"]) >= 1.0
    assert "moe_aux" not in metrics and np.isfinite(float(metrics["loss"]))
