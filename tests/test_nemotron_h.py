"""The pattern model (ISSUE 40) at toy size on the CPU: a model that is a
PATTERN of single-mixer layers (``LlamaConfig.layer_pattern``) against the
plain reference (``benchmarks/reference/nemotron_h.py``) on three patterns,
experts without a gate in a latent against their formula, the shares of one
latent expert layer against the uncut layer, a skewed router's later passes
with two-product experts, attention without positions, the counts at the
published keys, and what the model refuses."""

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
from benchmarks.harness.programs import nemotron_h as prog  # noqa: E402
from benchmarks.reference import nemotron_h as ref  # noqa: E402
from finetune_controller_tpu.models import llama, moe  # noqa: E402
from finetune_controller_tpu.models.llama import PRESETS, LlamaForCausalLM  # noqa: E402
from finetune_controller_tpu.models.lora import PATTERN_TARGETS, LoRAConfig  # noqa: E402

CONF = json.loads(
    (ROOT / "tests/benchmarks/fixtures/configs/tiny-nemotron-h.json").read_text())
TINY = PRESETS["tiny-nemotron-h-test"].replace(
    dtype=jnp.float32, lora=LoRAConfig(rank=4, targets=PATTERN_TARGETS))
SEED = 2**31 + 40
#: ``EMEM*``: a scanned pair twice, then attention by itself; ``M*EME``: the
#: same letters in another order, nothing repeats, every layer by itself;
#: ``MMEMEM**``: three stacks (``blocks``, ``blocks_2``, ``blocks_6``)
PATTERNS = ["EMEM*", "M*EME", "MMEMEM**"]


def _conf(pattern):
    return {**CONF, "hybrid_override_pattern": pattern,
            "num_hidden_layers": len(pattern)}


def _tokens(batch=2, seq=20, seed=0):
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


def _variables(cfg=TINY, seq=24):
    model = LlamaForCausalLM(cfg)
    tokens = jnp.asarray(_tokens(2, seq) % cfg.vocab_size)
    variables = model.init({"params": jax.random.PRNGKey(0)}, tokens)
    return model, {c: variables[c] for c in ("params", "lora")}, tokens


# ---- the pattern is data -----------------------------------------------------------


def test_a_pattern_reads_as_runs_of_repeated_units():
    def runs(pattern):
        return TINY.replace(layer_pattern=pattern,
                            n_layers=len(pattern)).pattern_runs()

    assert runs("EMEM*") == (("EM", 2), ("*", 1))
    assert runs("M*EME") == (("M", 1), ("*", 1), ("E", 1), ("M", 1), ("E", 1))
    assert runs("MMEMEM**") == (("M", 2), ("EM", 2), ("*", 2))
    assert runs("EMEMEMEMEM*") == (("EM", 5), ("*", 1))
    assert runs("MMMM") == (("M", 4),)         # the shortest unit that covers it
    published = CONF["published"]["hybrid_override_pattern"]
    assert sum(len(u) * r for u, r in runs(published)) == len(published)
    assert PRESETS["tiny-test"].pattern_runs() == ()
    # the reference places the leaves where the program keeps them
    for pattern in PATTERNS + [published]:
        want, at, stacks = [], 0, 0
        for unit, repeats in runs(pattern):
            if repeats == 1:
                want.append((f"layer_{at}", 0, 0, unit))
            else:
                stack = f"blocks_{at}" if stacks else "blocks"
                want += [(f"{stack}/layer_{j}", r, repeats, k)
                         for r in range(repeats) for j, k in enumerate(unit)]
                stacks += 1
            at += len(unit) * repeats
        assert [tuple(p) for p in ref.places(pattern)] == want
        assert "".join(p.kind for p in ref.places(pattern)) == pattern


@pytest.mark.parametrize("bad", [
    dict(layer_pattern="EMX"), dict(layer_pattern="EM"),
    dict(layer_pattern="EMEM*", attention_kind="mla"),
    dict(layer_pattern="EMEM*", first_k_dense=1),
    dict(layer_pattern="EMEM*", tie_embeddings=True),
    dict(layer_pattern="EMEM*", ssm_n_heads=0),
    dict(layer_pattern="EMEM*", n_experts=0)],
    ids=["letter", "length", "mla", "dense_layer", "tied", "no_mixer", "no_experts"])
def test_a_pattern_the_model_cannot_build_is_refused(bad):
    with pytest.raises(ValueError, match="pattern"):
        TINY.replace(n_layers=5, **bad).pattern_runs()


# ---- the whole model against the reference ---------------------------------------


@pytest.mark.parametrize("pattern", PATTERNS)
def test_loss_and_adapter_gradients_are_the_references(pattern):
    """The program built from the fixture's published keys with ``pattern``,
    seeded weights: its leaves are the ones the reference regenerates, under
    the same names; its loss and every adapter gradient are the reference's."""
    conf = _conf(pattern)
    cfg = prog.model_config(conf)
    assert cfg.layer_pattern == pattern and cfg.rope_theta == 0.0
    model, variables = _seeded(cfg)
    tokens = _tokens()
    arch = ref.Arch.from_config(conf)
    key = weights.root_key(SEED)
    lora = ref.init_lora(arch, key)
    mine = _flat(variables["lora"])
    assert sorted(mine) == sorted(lora)
    for name in lora:
        np.testing.assert_array_equal(mine[name], lora[name])
    # every frozen leaf of the program is one the reference draws
    drawn = {"embed_tokens/embedding", "final_norm/scale", "lm_head/kernel"}
    for place in ref.places(pattern):
        drawn |= {f"{place.prefix}/{n}" for n in arch.other_shapes(place.kind)}
        drawn |= {f"{place.prefix}/{n}/kernel" for n in arch.proj_shapes(place.kind)}
    assert set(_flat(variables["params"])) == drawn

    def mean_nll(lo):
        logits = model.apply({"params": variables["params"], "lora": lo},
                             tokens, mutable=("moe_stats",))[0][:, :-1]
        logp = jax.nn.log_softmax(logits, axis=-1)
        return -jnp.take_along_axis(logp, tokens[:, 1:, None], -1).mean()

    loss, grads = jax.value_and_grad(mean_nll)(variables["lora"])
    want_loss, want = ref.make_loss_and_grads(arch, rows_per_block=2)(
        key, lora, tokens)
    assert float(loss) == pytest.approx(want_loss, rel=2e-5)
    got = _flat(grads)
    scale = max(float(jnp.abs(g).max()) for g in want.values())
    for name in want:
        np.testing.assert_allclose(got[name], want[name], rtol=2e-3,
                                   atol=2e-4 * scale, err_msg=name)
        assert float(jnp.abs(want[name]).max()) > 0, name


def test_the_order_of_the_letters_changes_the_model():
    """The same layers in another order are another model: ``EMEM*`` and
    ``MEME*`` unrolled, each layer handed the leaves of the like layer — and a
    kind's leaves exist for its layers alone."""
    cfg = TINY.replace(scan_layers=False, remat=False)
    model, variables, tokens = _variables(cfg)
    for i, kind in enumerate(cfg.layer_pattern):
        assert sorted(variables["params"][f"layer_{i}"]) == sorted(
            ["norm", llama.LAYER_KINDS[kind]])
    swapped = {c: dict(variables[c]) for c in variables}
    for a, b in ((0, 1), (2, 3)):
        for c in swapped:
            swapped[c][f"layer_{a}"], swapped[c][f"layer_{b}"] = (
                variables[c][f"layer_{b}"], variables[c][f"layer_{a}"])
    other = LlamaForCausalLM(cfg.replace(layer_pattern="MEME*"))
    got = other.apply(swapped, tokens, mutable=("moe_stats",))[0]
    want = model.apply(variables, tokens, mutable=("moe_stats",))[0]
    assert float(jnp.abs(got - want).max()) > 1e-3
    # a layer of a scanned unit holds its own kind's leaves, stacked
    _, scanned, _ = _variables()
    assert sorted(scanned["params"]["blocks"]) == ["layer_0", "layer_1"]
    assert sorted(scanned["params"]["blocks"]["layer_0"]) == ["moe", "norm"]
    assert sorted(scanned["params"]["blocks"]["layer_1"]) == ["mamba", "norm"]
    assert sorted(scanned["params"]["layer_4"]) == ["attn", "norm"]
    assert "mlp" not in str(jax.tree_util.tree_structure(scanned))


@pytest.mark.parametrize("policy", ["full", "none"])
@pytest.mark.parametrize("pattern", ["EMEM*", "MMEMEM**"])
def test_scanned_stacks_compute_the_unrolled_models_gradients(pattern, policy):
    """Every run of the pattern as a scanned stack, each layer of its unit
    under its own remat, gives the unrolled, un-rematerialised model's loss
    and adapter gradients."""
    cfg = TINY.replace(layer_pattern=pattern, n_layers=len(pattern),
                       remat_policy=policy)
    model, variables, tokens = _variables(cfg)

    def loss_and_grads(c, v):
        def loss(lora):
            out = LlamaForCausalLM(c).apply(
                {"params": v["params"], "lora": lora}, tokens,
                mutable=("moe_stats",))[0]
            return (out * jnp.cos(jnp.arange(out.size).reshape(out.shape))).mean()
        return jax.value_and_grad(loss)(v["lora"])

    def unrolled(tree):
        out, at = {k: v for k, v in tree.items() if not k.startswith("blocks")}, 0
        stacks = 0
        for unit, repeats in cfg.pattern_runs():
            if repeats > 1:
                stack = tree[f"blocks_{at}" if stacks else "blocks"]
                stacks += 1
                for r in range(repeats):
                    for j in range(len(unit)):
                        out[f"layer_{at + r * len(unit) + j}"] = jax.tree.map(
                            lambda a: a[r], stack[f"layer_{j}"])
            at += len(unit) * repeats
        return out

    loss, got = loss_and_grads(cfg, variables)
    flat = cfg.replace(scan_layers=False, remat=False)
    want_loss, want = loss_and_grads(
        flat, {c: unrolled(variables[c]) for c in variables})
    np.testing.assert_allclose(loss, want_loss, rtol=1e-5)
    got = unrolled(got)
    assert sorted(got) == sorted(want)
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_allclose(a, b, rtol=5e-4, atol=1e-8)


# ---- the expert layer: no gate, a latent, a held share ------------------------------


def _expert_block(**changes):
    cfg = TINY.replace(n_layers=1, layer_pattern="E", **changes)
    return cfg, llama.Block(cfg, kind="E")


def _expert_variables(cfg, block, tokens=24, seed=5):
    x = jax.random.normal(jax.random.PRNGKey(seed), (1, tokens, cfg.d_model))
    variables = block.init({"params": jax.random.PRNGKey(6)}, x, None, None)
    variables = {c: variables[c] for c in ("params", "lora") if c in variables}
    if "lora" in variables:
        # adapters that do something (flax starts ``lora_b`` at zero)
        variables["lora"] = jax.tree.map(
            lambda a: 0.05 * jax.random.normal(jax.random.PRNGKey(a.size), a.shape),
            variables["lora"])
    return x, variables


def _reference_weights(variables):
    """A block's variables under the reference's names for a layer's leaves."""
    w, lora_l = {}, {}
    for name, leaf in _flat(variables["params"]).items():
        adapted = name.endswith("_proj/kernel") and "/experts/" not in name
        w[name.removesuffix("/kernel") if adapted else name] = leaf
    for name, leaf in _flat(variables["lora"]).items():
        lora_l[name] = leaf
    return w, lora_l


def _arch(cfg):
    return ref.Arch.from_config(CONF)._replace(
        hidden_size=cfg.d_model, n_experts=cfg.n_experts,
        experts_held=cfg.experts_held or (0, cfg.n_experts),
        top_k=cfg.moe_top_k, expert_ff=cfg.moe_d_ff, latent=cfg.moe_latent,
        shared_ff=cfg.n_shared_experts * cfg.moe_d_ff,
        routed_scale=cfg.moe_routed_scale, select_bias=cfg.moe_select_bias,
        rms_eps=cfg.rms_eps, lora_rank=4, lora_alpha=cfg.lora.alpha)


def test_experts_without_a_gate_are_down_of_squared_relu_of_up():
    """Two experts, both chosen by every token: the layer is, element for
    element, ``fc2(sum_e w_e down_e(relu(up_e fc1 x)^2)) + shared(x)`` with
    ``shared = down(relu(up x)^2)`` — and holds no gate matrix anywhere."""
    cfg, block = _expert_block(n_experts=2, moe_top_k=2, lora=LoRAConfig())
    x, variables = _expert_variables(cfg, block)
    p = variables["params"]
    assert sorted(p["moe"]["experts"]) == ["down_proj", "up_proj"]
    assert sorted(p["moe"]["shared"]) == ["down_proj", "up_proj"]
    assert p["moe"]["experts"]["up_proj"]["kernel"].shape == (2, 32, 24)
    assert p["moe"]["experts"]["down_proj"]["kernel"].shape == (2, 24, 32)
    got = block.apply(variables, x, None, None, mutable=("moe_stats",))[0]
    u = np.asarray(llama.RMSNorm(cfg.rms_eps, cfg.dtype).apply(
        {"params": p["norm"]}, x))[0]
    m = jax.tree.map(np.asarray, p["moe"])
    s = 1 / (1 + np.exp(-(u @ m["router"]["kernel"])))
    w = s / s.sum(-1, keepdims=True) * cfg.moe_routed_scale
    r = u @ m["fc1_latent_proj"]["kernel"]
    routed = sum(
        w[:, e, None] * (np.maximum(r @ m["experts"]["up_proj"]["kernel"][e], 0) ** 2
                         @ m["experts"]["down_proj"]["kernel"][e])
        for e in range(2))
    shared = (np.maximum(u @ m["shared"]["up_proj"]["kernel"], 0) ** 2
              @ m["shared"]["down_proj"]["kernel"])
    want = np.asarray(x)[0] + routed @ m["fc2_latent_proj"]["kernel"] + shared
    np.testing.assert_allclose(got[0], want, rtol=2e-5, atol=2e-6)
    # a layer built WITH a gate matrix is not what this configuration builds
    gated = llama.Block(cfg.replace(mlp_act="silu"), kind="E").init(
        {"params": jax.random.PRNGKey(0)}, x, None, None)["params"]["moe"]
    assert sorted(gated["experts"]) == ["down_proj", "gate_proj", "up_proj"]
    assert sorted(gated["shared"]) == ["down_proj", "gate_proj", "up_proj"]


@pytest.mark.parametrize("dispatch", ["dropless", "capacity"])
def test_two_product_experts_run_in_every_dispatch(dispatch):
    """``gated=False`` through the capacity dispatch and the dropless one:
    with room for every pair both are the per-token formula."""
    layer = moe.MoEMLP(d_model=16, d_ff=12, n_experts=4, top_k=2,
                       dispatch=dispatch, capacity_factor=4.0, gated=False,
                       aux_loss=False, dtype=jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(1), (1, 10, 16))
    params = layer.init({"params": jax.random.PRNGKey(2)}, x)["params"]
    got = layer.apply({"params": params}, x, mutable=("moe_stats",))[0][0]
    xs = np.asarray(x)[0]
    probs = np.asarray(jax.nn.softmax(xs @ np.asarray(params["router"]["kernel"])))
    want = np.zeros_like(xs)
    for t in range(10):
        top = np.argsort(-probs[t])[:2]
        for e in top:
            up = np.asarray(params["experts"]["up_proj"]["kernel"][e])
            down = np.asarray(params["experts"]["down_proj"]["kernel"][e])
            want[t] += probs[t, e] / probs[t, top].sum() * (
                np.maximum(xs[t] @ up, 0) ** 2 @ down)
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-6)


def test_the_four_shares_of_a_latent_expert_layer_add_up_to_the_uncut_layer():
    """The share is tied to the model: the four shares ``(0, n/4) ... (3n/4,
    n/4)`` of ONE expert layer, ``fc2`` applied to each PARTIAL sum (it is
    linear), the shared expert counted once, add up to the uncut layer —
    forward and the gradient with respect to the input; and each share is the
    masked reference's on the same share."""
    cfg, block = _expert_block()
    x, variables = _expert_variables(cfg, block, tokens=40)
    n = cfg.n_experts

    def whole(xx):
        return block.apply(variables, xx, None, None, mutable=("moe_stats",))[0]

    def share(i, with_shared):
        c = cfg.replace(experts_held=(i * n // 4, n // 4),
                        n_shared_experts=cfg.n_shared_experts * with_shared)
        v = jax.tree.map(lambda a: a, variables)
        for col in v:
            v[col] = dict(v[col], moe={k: a for k, a in v[col]["moe"].items()
                                       if with_shared or k != "shared"})
        v["params"]["moe"]["experts"] = jax.tree.map(
            lambda a: a[i * n // 4:(i + 1) * n // 4],
            variables["params"]["moe"]["experts"])
        return c, v

    def parts(xx):
        total, pairs = 0.0, 0.0
        for i in range(4):
            c, v = share(i, with_shared=i == 0)
            out, stats = llama.Block(c, kind="E").apply(
                v, xx, None, None, mutable=("moe_stats",))
            total = total + (out - xx)
            pairs = pairs + stats["moe_stats"]["moe"]["pairs"][0]
        return xx + total, pairs

    got, pairs = parts(x)
    assert float(pairs) == 40 * cfg.moe_top_k
    np.testing.assert_allclose(got, whole(x), rtol=1e-5, atol=1e-6)
    weigh = jnp.cos(jnp.arange(x.size).reshape(x.shape))
    np.testing.assert_allclose(
        jax.grad(lambda xx: (parts(xx)[0] * weigh).sum())(x),
        jax.grad(lambda xx: (whole(xx) * weigh).sum())(x), rtol=2e-4, atol=1e-6)
    # a share alone is the reference's masked form on that share
    c, v = share(2, with_shared=True)
    w, lora_l = _reference_weights({k: v[k] for k in v})
    np.testing.assert_allclose(
        llama.Block(c, kind="E").apply(v, x, None, None, mutable=("moe_stats",))[0],
        ref.layer_forward(_arch(c), "E", w, lora_l, x), rtol=1e-5, atol=1e-6)


def test_the_router_reads_the_full_state_and_the_experts_the_latent():
    """Moving ``fc1_latent_proj`` alone leaves the chosen experts unchanged
    (the pairs that reach a share, the fullest expert's load) and moves the
    result; moving the router moves both."""
    cfg, block = _expert_block(experts_held=(0, 4))
    x, variables = _expert_variables(cfg, block, tokens=64)
    variables["params"]["moe"]["experts"] = jax.tree.map(
        lambda a: a[:4], variables["params"]["moe"]["experts"]) \
        if variables["params"]["moe"]["experts"]["up_proj"]["kernel"].shape[0] != 4 \
        else variables["params"]["moe"]["experts"]

    def run(v):
        out, stats = block.apply(v, x, None, None, mutable=("moe_stats",))
        s = stats["moe_stats"]["moe"]
        return out, (float(s["pairs"][0]), float(s["load_max_over_mean"][0]))

    def moved(name):
        v = jax.tree.map(lambda a: a, variables)
        leaf = v["params"]["moe"][name]["kernel"]
        v["params"]["moe"][name]["kernel"] = jax.random.normal(
            jax.random.PRNGKey(11), leaf.shape) * 0.3
        return v

    out, chosen = run(variables)
    out_fc1, chosen_fc1 = run(moved("fc1_latent_proj"))
    out_router, chosen_router = run(moved("router"))
    assert chosen_fc1 == chosen and float(jnp.abs(out_fc1 - out).max()) > 1e-3
    assert chosen_router != chosen
    assert variables["params"]["moe"]["router"]["kernel"].shape == (64, 16)
    assert variables["params"]["moe"]["fc1_latent_proj"]["kernel"].shape == (64, 32)
    assert variables["params"]["moe"]["experts"]["up_proj"]["kernel"].shape == (4, 32, 24)


@pytest.mark.parametrize("count, forced, passes", [
    (1, [0], 2), (3, [0, 1], 2), (1, [], 1)],
    ids=["two_passes", "two_passes_padded_rows", "one_pass_is_enough"])
def test_a_skewed_routers_later_passes_run_two_product_experts(count, forced, passes):
    """``_held_later_passes`` with experts WITHOUT a gate in a latent: a
    router skewed onto the share (a selection bias of 10 on ``forced``) sends
    it more pairs than one pass's rows; the further passes compute them, and
    the layer is the masked reference's on the same share — value, and the
    gradients with respect to the input, the router and an adapter."""
    tokens = 1024
    cfg, block = _expert_block(experts_held=(0, count), moe_select_bias=True)
    bound = moe.held_row_bound(tokens * cfg.moe_top_k, count, cfg.n_experts)
    x, variables = _expert_variables(cfg, block, tokens=tokens)
    bias = jnp.zeros((cfg.n_experts,)).at[jnp.asarray(forced, jnp.int32)].set(10.0)
    variables["params"]["moe"]["router"]["bias"] = bias
    out, stats = block.apply(variables, x, None, None, mutable=("moe_stats",))
    mine = float(stats["moe_stats"]["moe"]["pairs"][0])
    # every token's forced experts, and what the other choices send the share
    assert tokens * len(forced) <= mine < tokens * len(forced) + tokens // 2
    assert -(-mine // bound) == passes
    assert float(moe.moe_counters(stats)["moe_pairs_over_bound"]) == max(
        mine - bound, 0)
    arch = _arch(cfg)

    def plain(xx, router, lora_b):
        v = jax.tree.map(lambda a: a, variables)
        v["params"]["moe"]["router"]["kernel"] = router
        v["lora"]["moe"]["fc1_latent_proj"]["lora_b"] = lora_b
        w, lora_l = _reference_weights(v)
        return ref.layer_forward(arch, "E", w, lora_l, xx)

    def mine_fn(xx, router, lora_b):
        v = jax.tree.map(lambda a: a, variables)
        v["params"]["moe"]["router"]["kernel"] = router
        v["lora"]["moe"]["fc1_latent_proj"]["lora_b"] = lora_b
        return block.apply(v, xx, None, None, mutable=("moe_stats",))[0]

    args = (x, variables["params"]["moe"]["router"]["kernel"],
            variables["lora"]["moe"]["fc1_latent_proj"]["lora_b"])
    np.testing.assert_allclose(out, plain(*args), rtol=2e-5, atol=2e-6)
    weigh = jnp.cos(jnp.arange(x.size).reshape(x.shape))
    got = jax.grad(lambda *a: (mine_fn(*a) * weigh).sum(), argnums=(0, 1, 2))(*args)
    want = jax.grad(lambda *a: (plain(*a) * weigh).sum(), argnums=(0, 1, 2))(*args)
    for a, b in zip(got, want):
        np.testing.assert_allclose(a, b, rtol=2e-3,
                                   atol=2e-5 * float(jnp.abs(b).max()))


# ---- attention without positions, the mixer alone ----------------------------------


def test_attention_without_positions_does_not_see_the_order_of_earlier_tokens():
    """A ``*``-only model of one layer: permuting the earlier tokens leaves
    the last position's output unchanged (causal softmax over a set; a second
    layer would see each earlier position's own, changed, prefix); with a
    rotary embedding it does not."""
    cfg = TINY.replace(n_layers=1, layer_pattern="*")
    model, variables, tokens = _variables(cfg)
    permuted = jnp.concatenate(
        [tokens[:, :-1][:, ::-1], tokens[:, -1:]], axis=1)
    np.testing.assert_allclose(model.apply(variables, permuted)[:, -1],
                               model.apply(variables, tokens)[:, -1],
                               rtol=1e-5, atol=1e-6)
    rotary = LlamaForCausalLM(cfg.replace(rope_theta=10000.0))
    assert float(jnp.abs(rotary.apply(variables, permuted)[:, -1]
                         - rotary.apply(variables, tokens)[:, -1]).max()) > 1e-4


def test_a_mixer_layer_is_the_mixer_alone_under_its_norm():
    from finetune_controller_tpu.models import ssm

    cfg = TINY.replace(n_layers=1, layer_pattern="M")
    block = llama.Block(cfg, kind="M")
    x = jax.random.normal(jax.random.PRNGKey(3), (2, 20, cfg.d_model))
    variables = block.init({"params": jax.random.PRNGKey(4)}, x, None, None)
    assert sorted(variables["params"]) == ["mamba", "norm"]
    u = llama.RMSNorm(cfg.rms_eps, cfg.dtype).apply(
        {"params": variables["params"]["norm"]}, x)
    mixer = ssm.Mamba2Mixer(cfg).apply(
        {c: variables[c]["mamba"] for c in ("params", "lora")}, u)
    np.testing.assert_allclose(block.apply(variables, x, None, None), x + mixer,
                               rtol=1e-6, atol=1e-6)
    # the reference's mixer layer, given the same leaves
    w, lora_l = _reference_weights({c: variables[c] for c in ("params", "lora")})
    np.testing.assert_allclose(
        block.apply(variables, x, None, None),
        ref.layer_forward(_arch(cfg), "M", w, lora_l, x), rtol=2e-4, atol=2e-5)


# ---- counts, counters, rules, refusals -----------------------------------------------


def test_param_counts_know_the_three_kinds_at_the_published_keys():
    _, variables, _ = _variables()
    held = sum(a.size for a in jax.tree.leaves(variables["params"]))
    assert TINY.param_count() == held
    assert TINY.active_param_count() == held - 2 * (16 - 4) * 2 * 32 * 24
    real = json.loads((ROOT / "benchmarks/configs/nemotron-3-super-lora.json"
                       ).read_text())
    published = {**real, **real["published"], "reduced": [],
                 "num_nextn_predict_layers": 0}
    cfg = prog.model_config(published)
    mixer, attention = 109_640_064, 35_655_680
    expert, beside = 2 * 1024 * 2688, 54_530_560
    assert expert == 5_505_024
    assert cfg._mixer_params() + 4096 == mixer
    assert cfg._attention_params() + 4096 == attention
    assert cfg._expert_layer_params(512) + 4096 == beside - 512 + 512 * expert
    assert cfg.param_count() == (
        40 * mixer + 8 * attention + 40 * (beside + 512 * expert)
        + 2 * 131072 * 4096 + 4096 - 40 * 512)
    # with the selection bias as a leaf, the issue's figure to the unit
    assert cfg.replace(moe_select_bias=True).param_count() == (
        40 * mixer + 8 * attention + 40 * 2_873_102_848 + 2 * 131072 * 4096 + 4096)
    assert cfg.active_param_count() == cfg.param_count() - 40 * (512 - 22) * expert
    assert cfg.param_count() == pytest.approx(120.7e9, rel=1e-3)
    assert cfg.active_param_count() == pytest.approx(12.8e9, rel=1e-2)
    # the cut the cell runs: 4.648 B parameters, 9.30 GB of frozen bf16
    cut = prog.model_config(real)
    assert cut.param_count() == 4_648_163_712 - 5 * 512
    assert cut.experts_held == (0, 128) and cut.n_experts == 512


def test_trainer_steps_under_a_mesh_as_on_one_device_and_reports_the_pattern(devices8):
    """The new leaves' partition rules (latent projections over ``fsdp``, the
    experts over ``ep``-less ``fsdp``/``tp``): two steps on a 2 x 2 mesh give
    one device's losses, and ``train-started`` carries the pattern's
    counters."""
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
        return trainer, m, losses

    one, metrics, want = run(MeshSpec(fsdp=1).build(devices8[:1]))
    attrs = one._runtime_attrs()
    assert attrs["layer_pattern"] == "EMEM*"
    assert attrs["layers_by_kind"] == {"*": 1, "E": 2, "M": 2}
    assert (attrs["moe_latent_width"], attrs["moe_experts_held"]) == (32, 8)
    assert (attrs["ssm_layers"], attrs["ssm_chunks_per_row"]) == (2, 3)
    assert attrs["lora_joined_projections"]["of"] == 10
    assert float(metrics["moe_pairs"]) > 0 and "moe_load_max_over_mean" in metrics
    assert float(metrics["moe_pairs_over_bound"]) == 0
    _, _, got = run(MeshSpec(fsdp=2, tp=2).build(devices8[:4]))
    np.testing.assert_allclose(got, want, rtol=2e-5)
    assert got[1] != got[0]
    from jax.sharding import PartitionSpec as P
    assert LLAMA_RULES.spec_for("blocks/layer_0/moe/fc1_latent_proj/kernel") == P(
        "fsdp", None)
    assert LLAMA_RULES.spec_for("blocks/layer_0/moe/fc2_latent_proj/kernel") == P(
        None, "fsdp")


def test_decode_raises():
    model, variables, tokens = _variables()
    with pytest.raises(NotImplementedError, match="decode"):
        model.apply(variables, tokens, decode=True, mutable=["cache"])


def test_pipeline_stage_refuses_a_pattern():
    with pytest.raises(NotImplementedError, match="pattern"):
        llama.make_block_stage_fn(TINY)
    with pytest.raises(NotImplementedError, match="pattern"):
        llama.make_block_stage_fn(TINY.replace(layer_pattern="**", n_layers=2))


@pytest.mark.parametrize("axis", ["sp", "pp"])
@pytest.mark.parametrize("pattern", ["EMEM*", "E*"])
def test_trainer_refuses_a_split_sequence_and_a_pipeline(axis, pattern, devices8):
    from finetune_controller_tpu.parallel.mesh import MeshSpec
    from finetune_controller_tpu.train.trainer import TrainConfig, Trainer

    mesh = MeshSpec(**{axis: 2}).build(devices8[:2])
    with pytest.raises(ValueError, match="sp = pp = 1"):
        Trainer(TINY.replace(layer_pattern=pattern, n_layers=len(pattern)),
                TrainConfig(mode="lora", batch_size=2, seq_len=16,
                            total_steps=2), mesh=mesh)


def test_export_and_import_refuse_a_pattern_before_touching_a_file(tmp_path):
    from finetune_controller_tpu.models.hf_export import export_merged_checkpoint
    from finetune_controller_tpu.models.hf_import import _map_llama_tensors

    with pytest.raises(NotImplementedError, match="pattern"):
        export_merged_checkpoint(TINY, {"params": {}}, tmp_path / "nope")
    assert not (tmp_path / "nope").exists()
    with pytest.raises(NotImplementedError, match="pattern"):
        _map_llama_tensors(iter(()), TINY, jnp.float32)
