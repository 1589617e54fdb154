"""The latent-attention expert model (ISSUE 27) at toy size on the CPU: the
program's blocks against the plain reference (``benchmarks/reference/
mla_moe.py``), dropless routing against a per-token loop, the shares of
``experts_held`` against the uncut layer, and the grouped products that read
a layer's experts in place in the scanned stack (ISSUE 28) against the
per-layer products."""

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
from finetune_controller_tpu.models import moe  # noqa: E402
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


def _primitives(jaxpr, found=None):
    """The names of every primitive in ``jaxpr`` and the jaxprs its equations
    hold (a replayed layer is one ``remat2`` equation)."""
    found = set() if found is None else found
    for eqn in jaxpr.eqns:
        found.add(eqn.primitive.name)
        for inner in jax.core.jaxprs_in_params(eqn.params):
            _primitives(inner, found)
    return found


ROUTES = {"dropless": {"dispatch": "dropless"},
          "held": {"dispatch": "dropless", "experts_held": (2, 4)},
          "capacity": {"dispatch": "capacity"}}


@pytest.mark.parametrize("dispatch", list(ROUTES))
def test_a_replayed_layer_reads_its_routing_and_routes_once(dispatch):
    """Under ``remat_policy`` ``"full"`` the backward pass replays the layer
    from the integers its forward pass left (``moe_routing``: the chosen
    experts, the sort and its inverse or a share's rows, an expert's pairs):
    it holds no ``top_k``, no ``sort`` and no ``scatter`` — under a policy
    that keeps nothing it holds them, so the search can see them — and the
    gradient is the one without remat, bit for bit."""
    from finetune_controller_tpu.models.llama import remat_policy_fn

    layer = MoEMLP(d_model=D, d_ff=F, n_experts=E, top_k=K, scoring="sigmoid",
                   aux_loss=False, dtype=jnp.float32, **ROUTES[dispatch])
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 12, D), jnp.float32)
    params = layer.init({"params": jax.random.PRNGKey(2)}, x)["params"]

    def f(params, x):
        return (layer.apply({"params": params}, x) ** 2).sum()

    def backward(policy):
        vjp = jax.vjp(jax.checkpoint(f, policy=policy), params, x)[1]
        return vjp, _primitives(jax.make_jaxpr(vjp)(jnp.float32(1)).jaxpr)

    vjp, replayed = backward(remat_policy_fn("full"))
    assert not replayed & {"top_k", "sort", "scatter"}, replayed
    assert {"remat2", "gather"} <= replayed
    _, routed_again = backward(jax.checkpoint_policies.nothing_saveable)
    assert {"top_k", "scatter"} <= routed_again
    assert ("sort" in routed_again) == (dispatch != "capacity")
    for got, want in zip(jax.tree.leaves(vjp(jnp.float32(1))),
                         jax.tree.leaves(jax.grad(f, argnums=(0, 1))(params, x))):
        assert float(jnp.abs(want).max()) > 0
        np.testing.assert_array_equal(got, want)


@pytest.mark.parametrize("tokens", [24, 2048], ids=["every_pair_within_the_bound",
                                                    "rows_cut_to_the_bound"])
def test_shares_of_experts_held_add_up_to_the_uncut_layer(tokens):
    """(d) Each share routes over ALL experts and computes its own experts'
    part; the parts of every share plus the shared expert ONCE are the uncut
    layer — the program's, and the plain reference's part by part.  At 2,048
    tokens a share's gathers and products are sized by ``held_row_bound``
    (1,024 rows for one expert of eight, of 4,096 pairs) and still hold every
    pair of its experts."""
    full = _layer()
    assert [moe.held_row_bound(tokens * K, count, E) for _, count in SHARES] == (
        [48, 48, 48] if tokens == 24 else [3072, 1024, 4096])
    x = jax.random.normal(jax.random.PRNGKey(5), (1, tokens, D), jnp.float32)
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
        assert float(stats["moe_stats"]["pairs_over_bound"][0]) == 0
        total = total + part
        # the reference, given the same share of the same weights
        w = {"moe/router": params["router"]["kernel"],
             "moe/router/bias": params["router"]["bias"],
             **{f"moe/experts/{n}": kernels[n]["kernel"] for n in kernels}}
        np.testing.assert_allclose(
            part.reshape(-1, D),
            ref.routed_experts(arch._replace(experts_held=(first, count)),
                               w, x.reshape(-1, D)), atol=1e-5)
    assert pairs == tokens * K
    np.testing.assert_allclose(total, whole, atol=1e-5)


def _routed(tokens, k, e, held, forced=(), chosen=None, seed=3):
    """A held share's pairs as ``MoEMLP._dropless_held`` sorts them: ``(bound,
    order, sizes)``.  ``forced``: experts every token chooses; ``chosen``: the
    experts of the leading tokens, a row each."""
    scores = jax.random.uniform(jax.random.PRNGKey(seed), (tokens, e))
    scores = scores.at[:, jnp.asarray(forced, jnp.int32)].add(2.0)
    top_idx = jax.lax.top_k(scores, k)[1]
    if chosen is not None:
        top_idx = top_idx.at[:len(chosen)].set(jnp.asarray(chosen, jnp.int32))
    first, n_held = held
    bound = moe.held_row_bound(tokens * k, n_held, e)
    order = jnp.argsort((top_idx.reshape(-1) - first) % e, stable=True).astype(jnp.int32)
    order = jnp.concatenate([order, jnp.arange(
        tokens * k, -(-tokens * k // bound) * bound + bound, dtype=jnp.int32)])
    load = jnp.zeros((e,), jnp.int32).at[top_idx.reshape(-1)].add(1)
    return bound, order, jnp.roll(load, -first)[:n_held]


def _a_pass(tokens, k, start=0, **routing):
    """One pass as ``_held_pass`` reads it, in both forms: ``(bound, {form:
    its integers})``."""
    bound, order, sizes = _routed(tokens, k, **routing)
    return bound, {form: moe._pass_rows(order, sizes, start, bound, tokens, k, form)
                   for form in ("rows", "choices")}


#: name -> (``_a_pass`` arguments, what the pass must hold)
HELD_PASSES = {
    # the window/full and 16k cells' ratio: a sixteenth of the experts held
    "t64_k8_a_sixteenth_held": (dict(tokens=64, k=8, e=16, held=(0, 1)), {}),
    # the pattern cell's: top-22, a quarter held
    "t32_k22_a_quarter_held": (dict(tokens=32, k=22, e=32, held=(4, 8)), {}),
    "tokens_with_none_one_and_all_k_held": (
        dict(tokens=64, k=8, e=16, held=(0, 8), chosen=[
            list(range(8, 16)), [0, *range(9, 16)], list(range(8))]),
        {"held_pairs_of_leading_tokens": [0, 1, 8]}),
    # expert 0..3 take every token: 1,536 pairs and more in passes of 512
    # rows, the second of which holds a token's rows of two experts
    "a_later_pass": (dict(tokens=384, k=8, e=64, held=(0, 4), forced=(0, 1, 2, 3),
                          start=512), {"live": 512, "most_rows_a_token": 2}),
    # 1,600 pairs: the fourth pass holds 64
    "the_last_pass_mostly_dead_rows": (
        dict(tokens=400, k=8, e=64, held=(0, 4), forced=(0, 1, 2, 3), start=1536),
        {"live": 64, "most_rows_a_token": 1}),
}


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=["f32", "bf16"])
@pytest.mark.parametrize("case", list(HELD_PASSES))
def test_a_held_pass_sums_its_rows_the_same_in_both_forms(case, dtype):
    """(ISSUE 46) The per-token sums of a held pass — the combine, its two
    gradients, and the gradient of the dispatch's gather — in the ``"rows"``
    form (the pass's rows in token order, summed where they lie) and in the
    ``"choices"`` form (a gather of ``T`` rows a choice) against the plain
    ``(T, k, d)`` expression and its autodiff: the same float32 terms in
    another order, so float32 inputs agree to 1e-6 of the sum's scale and
    bf16 rows as closely (the accumulation is float32 either way).  Dead rows
    hold NaN wherever the program may be handed anything there."""
    kwargs, holds = HELD_PASSES[case]
    bound, forms = _a_pass(**kwargs)
    tokens, k, d = kwargs["tokens"], kwargs["k"], 24
    _, pair_of_row, row_of_pair, live, _ = forms["choices"]
    per_token = (row_of_pair < bound).sum(1)
    assert 0 < int(live.sum()) == int(per_token.sum())
    assert int(live.sum()) < bound or "live" in holds               # dead rows
    assert int(live.sum()) == live.shape[0] or not bool(live[-1])
    if "held_pairs_of_leading_tokens" in holds:
        assert per_token[:3].tolist() == holds["held_pairs_of_leading_tokens"]
    if "live" in holds:
        assert int(live.sum()) == holds["live"]
        assert int(per_token.max()) == holds["most_rows_a_token"]
    rows = jax.random.normal(jax.random.PRNGKey(1), (bound, d)).astype(dtype)
    rows = jnp.where(live[:, None], rows, 0)          # as ``_held_pass`` hands them
    weight = jax.random.uniform(jax.random.PRNGKey(2), (tokens, k)) + 0.1
    probe = jax.random.normal(jax.random.PRNGKey(4), (tokens, d))

    def plain(rows, weight):
        padded = jnp.concatenate([rows, jnp.zeros((1, d), rows.dtype)])
        return jnp.einsum("tk,tkd->td", weight,
                          padded[row_of_pair].astype(jnp.float32))

    want = plain(rows, weight)
    want_rows, want_weight = jax.grad(
        lambda r, w: (plain(r, w) * probe).sum(), (0, 1))(rows, weight)
    scale = float(jnp.abs(want).max())
    for form, (_, pair_of_row, row_of_pair, live, by_token) in forms.items():
        assert (by_token is None) == (form == "choices")

        def combine(rows, weight):
            return moe._held_combine(rows, weight, row_of_pair, pair_of_row, by_token)

        np.testing.assert_allclose(combine(rows, weight), want, rtol=0,
                                   atol=1e-6 * scale, err_msg=form)
        got_rows, got_weight = jax.grad(
            lambda r, w: (combine(r, w) * probe).sum(), (0, 1))(rows, weight)
        np.testing.assert_allclose(
            jnp.where(live[:, None], got_rows, 0).astype(jnp.float32),
            want_rows.astype(jnp.float32), rtol=0,
            atol=(1e-6 if dtype == jnp.float32 else 2 ** -8) * float(
                jnp.abs(want_rows).max()), err_msg=form)
        np.testing.assert_allclose(
            got_weight, want_weight, rtol=0,
            atol=1e-6 * float(jnp.abs(want_weight).max()), err_msg=form)

        # the dispatch's gather and its gradient: a token's rows' cotangents
        # summed, whatever the dead rows' hold
        x = jax.random.normal(jax.random.PRNGKey(5), (tokens, d)).astype(dtype)
        g = jax.random.normal(jax.random.PRNGKey(6), (bound, d)).astype(dtype)
        gathered, vjp = jax.vjp(lambda x: moe._held_rows_of_tokens(
            x, pair_of_row // k, row_of_pair, by_token), x)
        np.testing.assert_array_equal(gathered, x[pair_of_row // k])
        want_x = jnp.zeros((tokens, d), jnp.float32).at[pair_of_row // k].add(
            jnp.where(live[:, None], g, 0).astype(jnp.float32))
        got_x, = vjp(jnp.where(live[:, None], g, jnp.nan))
        assert got_x.dtype == dtype
        np.testing.assert_allclose(
            got_x.astype(jnp.float32), want_x.astype(dtype).astype(jnp.float32),
            rtol=0, atol=(1e-6 if dtype == jnp.float32 else 2 ** -7) * float(
                jnp.abs(want_x).max()), err_msg=form)


def test_the_sum_forms_were_read_under_the_installed_compiler():
    """Which form wins at a ratio of rows to pairs is the compiler's fusions
    as much as arithmetic (``PERF.md`` section 6, PR 46), and no CPU test can
    see them move.  Under another compiler: time both forms at the three
    cells' shapes again, then move ``held_sum_form``'s cutoff or this record."""
    from importlib.metadata import version

    assert moe.SUM_FORMS_READ_UNDER == {
        "jax": version("jax"), "libtpu": version("libtpu")}


def _gathers_of_width(jaxpr, width, found=None):
    """The operand shapes of every ``gather`` of rows ``width`` wide in
    ``jaxpr`` and the jaxprs its equations hold."""
    found = [] if found is None else found
    for eqn in jaxpr.eqns:
        shape = eqn.invars[0].aval.shape if eqn.invars else ()
        if eqn.primitive.name == "gather" and len(shape) == 2 and shape[1] == width:
            found.append(shape)
        for inner in jax.core.jaxprs_in_params(eqn.params):
            _gathers_of_width(inner, width, found)
    return found


@pytest.mark.parametrize("form, gathers", [("rows", 6), ("choices", 1 + 8 + 1 + 8)])
def test_a_held_layers_row_gathers_do_not_grow_with_the_choices(
        monkeypatch, form, gathers):
    """One held layer, forward and backward, at the window/full and 16k cells'
    ratio (top-8, a sixteenth of the experts held: ``held_row_bound`` is the
    tokens): in the rows form SIX gathers of rows ``d`` wide — the dispatch's
    one; the combine's rows into token order and each token's sum out of the
    blocks' results; the cotangent's rows; and the same two for the dispatch's
    gradient — where the layer held 1 + 8 + 1 + 8 + 8 through PR 45
    (``d_weight``'s eight went with either form: one reduction over the pass's
    rows).  (ISSUE 46 asked for at most four, which a form that ends in ONE
    gather gives — the library's transposed grouped product, whose float32
    operand the kernel rounds to bf16: ``CHANGES.md`` PR 46; the form shipped
    is plain ``jnp``: a gather into token order, a one-hot product a block, a
    gather of each token's sum.)"""
    tokens, k, d = 512, 8, 48
    bound, order, sizes = _routed(tokens, k, e=16, held=(0, 1))
    assert bound == tokens and moe.held_sum_form(tokens, k, bound) == "rows"
    monkeypatch.setattr(moe, "held_sum_form", lambda *shapes: form)
    x = jax.random.normal(jax.random.PRNGKey(1), (tokens, d), jnp.float32)
    top_w = jax.random.uniform(jax.random.PRNGKey(2), (tokens, k))
    kernels = tuple(jax.random.normal(jax.random.PRNGKey(i), shape) for i, shape in
                    enumerate([(1, d, 40), (1, d, 40), (1, 40, d)]))
    jaxpr = jax.make_jaxpr(jax.value_and_grad(
        lambda x, w: (moe._held_pass(x, w, kernels, None, order, sizes, 0, bound)
                      ** 2).sum(), (0, 1)))(x, top_w)
    found = _gathers_of_width(jaxpr.jaxpr, d)
    assert len(found) == gathers, found


@pytest.mark.parametrize("cell, tokens, k, held, e, form", [
    ("mimo-v2-flash-lora.train-sft-16k", 16384, 8, 16, 256, "rows"),
    ("glm-5.2-lora.train-sft-16k", 16384, 8, 16, 256, "rows"),
    ("nemotron-3-super-lora.train-sft-8k", 8192, 22, 128, 512, "rows")])
def test_the_form_of_a_held_pass_sum_at_the_cells_shapes(cell, tokens, k, held, e, form):
    """The chooser's answer at the three cells that hold a share: a pass of
    16,384 rows for 131,072 pairs (an eighth) and the pattern cell's pass of
    90,112 rows for 180,224 pairs (a half: the cutoff itself) take the rows
    form — 3.5 against 12.1 ms a sum, 4.6 against 17.4 and 3.1 against 6.0 on
    the chip (``PERF.md`` section 6, PR 46); a pass that holds every pair (a
    toy's, or two chips a layer) stays on the choices form."""
    assert moe.held_sum_form(2048, 2, 4096) == "choices"
    bound = moe.held_row_bound(tokens * k, held, e)
    assert moe.held_sum_form(tokens, k, bound) == form


@pytest.mark.parametrize("count, forced, passes, in_place", [
    (1, [0], 2, False), (3, [0, 1], 2, False), (1, [], 1, False),
    (1, [0], 2, True)], ids=["two_passes", "two_passes_padded_rows",
                             "one_pass_is_enough", "two_passes_read_in_place"])
def test_pairs_beyond_a_shares_row_bound_are_computed_in_further_passes(
        monkeypatch, count, forced, passes, in_place):
    """A router that sends held experts every token passes the rows ONE pass
    is sized for (twice an even share: 1,024 of 4,096 pairs for one expert of
    eight, 3,072 for three): further passes of as many rows compute the pairs
    beyond it — the last one's rows padded past the 4,096 pairs where the
    bound does not divide them — and count them in ``pairs_over_bound``.
    Values and both gradients against the plain reference on the same share:
    nothing is dropped, whatever the router does."""
    tokens = 2048
    share = _layer(experts_held=(0, count), shared=None)
    bound = moe.held_row_bound(tokens * K, count, E)
    x = jax.random.normal(jax.random.PRNGKey(5), (1, tokens, D), jnp.float32)
    params = share.init({"params": jax.random.PRNGKey(6)}, x)["params"]
    bias = jnp.zeros((E,)).at[jnp.asarray(forced, jnp.int32)].set(10.0)
    params = dict(params, router=dict(params["router"], bias=bias))
    where = {}
    if in_place:
        # the share's kernels as layer 1 of a stack of three, read there
        _take_the_in_place_path(monkeypatch)
        where = dict(layer=jnp.int32(1), stacked=tuple(
            jnp.stack([a + 1, a, a - 1]) for a in (
                params["experts"][n]["kernel"]
                for n in ("gate_proj", "up_proj", "down_proj"))))
    out, stats = share.apply({"params": params}, x, mutable=("moe_stats",), **where)
    assert float(stats["moe_stats"]["experts_in_place"][0]) == in_place
    mine = float(stats["moe_stats"]["pairs"][0])
    assert mine == tokens * len(forced) or not forced
    assert -(-mine // bound) == passes
    assert float(moe.moe_counters(stats)["moe_pairs_over_bound"]) == max(
        mine - bound, 0)

    arch = ref.Arch.from_config(dict(CONF, hidden_size=D, moe_intermediate_size=F)
                                )._replace(experts_held=(0, count))

    def plain(xx, router_kernel):
        w = {"moe/router": router_kernel, "moe/router/bias": bias,
             **{f"moe/experts/{n}": params["experts"][n]["kernel"]
                for n in params["experts"]}}
        return ref.routed_experts(arch, w, xx.reshape(-1, D))

    def program_(xx, router_kernel):
        p = dict(params, router=dict(params["router"], kernel=router_kernel))
        return share.apply({"params": p}, xx, **where).reshape(-1, D)

    np.testing.assert_allclose(out.reshape(-1, D),
                               plain(x, params["router"]["kernel"]), atol=1e-5)
    # the cotangent of the rows and of the combine weights (through the
    # router's kernel), with every pass rematerialised on the way back
    probe = jax.random.normal(jax.random.PRNGKey(11), (tokens, D))
    grads = [jax.grad(lambda xx, rk, f=f: (f(xx, rk) * probe).sum(), (0, 1))(
        x, params["router"]["kernel"]) for f in (program_, plain)]
    for got, want in zip(*grads):
        np.testing.assert_allclose(got, want, atol=2e-5 * float(
            jnp.abs(want).max()) + 1e-6)


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


def test_train_started_carries_the_row_tile_where_the_kernel_runs(monkeypatch):
    from finetune_controller_tpu.train.trainer import TrainConfig, Trainer

    cfg = PRESETS["tiny-mla-moe-test"].replace(
        lora=LoRAConfig(rank=4, targets=MLA_TARGETS))
    trainer = Trainer(cfg, TrainConfig(
        mode="lora", total_steps=2, batch_size=4, seq_len=256, grad_accum_steps=2))
    assert "moe_gmm_row_tile" not in trainer._runtime_attrs()      # the CPU
    monkeypatch.setattr(moe, "_pallas_grouped_dot_ok", lambda rows: True)
    # a microbatch of 2 x 256 tokens x top-2 over 8 experts: 128 rows a group
    assert trainer._runtime_attrs()["moe_gmm_row_tile"] == 128


@pytest.mark.parametrize("held, batch, form, share", [
    (None, 4, None, None), ((0, 2), 4, "choices", 1.0), ((0, 1), 16, "rows", 0.25)],
    ids=["all_experts", "a_share_of_few_pairs", "a_share"])
def test_train_started_says_how_a_held_share_sums_its_rows(held, batch, form, share):
    """``moe_held_sum_form`` is ``held_sum_form``'s answer at a microbatch's
    tokens, and ``moe_held_rows_over_pairs`` a pass's rows over the routed
    pairs (0.125 in the window/full and 16k cells, 0.5 in the pattern cell);
    a model that holds every expert says neither."""
    from finetune_controller_tpu.train.trainer import TrainConfig, Trainer

    cfg = PRESETS["tiny-mla-moe-test"].replace(
        lora=LoRAConfig(rank=4, targets=MLA_TARGETS), experts_held=held)
    attrs = Trainer(cfg, TrainConfig(
        mode="lora", total_steps=2, batch_size=batch, seq_len=128,
        grad_accum_steps=2))._runtime_attrs()
    assert attrs.get("moe_held_sum_form") == form
    assert attrs.get("moe_held_rows_over_pairs") == share
    if held:
        tokens = batch // 2 * 128
        assert share == moe.held_row_bound(tokens * 2, held[1], 8) / (tokens * 2)


def test_train_started_says_which_projections_carry_their_adapter():
    """``lora_joined_projections`` asks the rule at each adapter's widths and a
    microbatch's rows; what it says is what the trace does: as many
    ``joined_product`` calls as it counts in the model traced on that shape
    (a scanned stack's block is traced once, and counted once)."""
    from finetune_controller_tpu.train.trainer import TrainConfig, Trainer

    cfg = PRESETS["tiny-mla-moe-test"].replace(
        lora=LoRAConfig(rank=4, targets=MLA_TARGETS))

    def reported_and_traced(seq_len):
        trainer = Trainer(cfg, TrainConfig(
            mode="lora", total_steps=2, batch_size=4, seq_len=seq_len,
            grad_accum_steps=2))
        shapes = jax.eval_shape(trainer.raw_init, jax.random.PRNGKey(0))
        traced = str(jax.make_jaxpr(lambda v, t: trainer.model.apply(
            v, t, mutable=True))(
            trainer._assemble(shapes.frozen, shapes.trainable),
            jax.ShapeDtypeStruct((2, seq_len), jnp.int32)))
        return (trainer._runtime_attrs()["lora_joined_projections"],
                traced.count("joined_product"))

    # 112 rows a microbatch (>= 1.75 * (in + 4) up to 60 columns): the
    # projections of 32 and 48 columns join, those of the model's 64 and the
    # dense layer's 128 do not
    got, traced = reported_and_traced(56)
    assert (got["joined"], got["of"]) == (5, 16) and traced == 5
    assert {p.rsplit("/", 1)[1] for p in got["apart"]} == {
        "q_a_proj", "kv_a_proj_with_mqa", "o_proj", "gate_proj", "up_proj",
        "down_proj"}
    assert "blocks/block/moe/shared/down_proj" not in got["apart"]
    got, traced = reported_and_traced(16)       # 32 rows: too few for any
    assert got["joined"] == traced == 0 and len(got["apart"]) == got["of"] == 16
    # a full fine-tune adapts nothing
    full = Trainer(cfg.replace(lora=LoRAConfig()), TrainConfig(
        mode="full", total_steps=2, batch_size=4, seq_len=64))
    assert "lora_joined_projections" not in full._runtime_attrs()


# ---- the grouped products read a layer's experts in place (ISSUE 28) ---------

#: rows of each of E experts: all the rows in uneven groups, and with experts
#: that no pair chose (the first, one in the middle, the last)
GROUPS = {"uneven": (5, 1, 9, 2, 7, 3, 4, 1), "empty_experts": (0, 6, 0, 11, 8, 0, 7, 0)}


@pytest.mark.parametrize("layer", range(3))
@pytest.mark.parametrize("groups", list(GROUPS), ids=list(GROUPS))
def test_in_place_grouped_product_is_the_per_layer_product(groups, layer):
    """``_grouped_dot(rows, stacked [L, E, k, n], sizes, layer)`` — the merged
    ``L·E`` groups, empty outside the layer's — is ``_grouped_dot(rows,
    stacked[layer], sizes)`` bit for bit, forward and activation gradient
    (``ragged_dot`` takes the same operands the Pallas kernel does)."""
    sizes = jnp.asarray(GROUPS[groups], jnp.int32)
    m = int(sizes.sum())
    stacked = jax.random.normal(jax.random.PRNGKey(11), (3, E, D, F), jnp.float32)
    rows = jax.random.normal(jax.random.PRNGKey(12), (m, D), jnp.float32)
    probe = jax.random.normal(jax.random.PRNGKey(13), (m, F), jnp.float32)

    def both(dot):
        def f(rows):
            out = dot(rows)
            return (out * probe).sum(), out

        (_, out), grad = jax.value_and_grad(f, has_aux=True)(rows)
        return out, grad

    in_place = jax.jit(lambda l: both(
        lambda r: moe._grouped_dot(r, stacked, sizes, l)))(jnp.int32(layer))
    sliced = both(lambda r: moe._grouped_dot(r, stacked[layer], sizes))
    assert float(jnp.abs(sliced[0]).max()) > 0
    np.testing.assert_array_equal(in_place[0], sliced[0])
    np.testing.assert_array_equal(in_place[1], sliced[1])


def _take_the_in_place_path(monkeypatch):
    """What one TPU decides, here: the layer is told the Pallas kernel runs,
    and ``ragged_dot`` stands in for it on the kernel's own operands."""
    from jax.experimental.pallas.ops.tpu.megablox import ops as megablox

    monkeypatch.setattr(moe, "_pallas_grouped_dot_ok", lambda rows: True)
    monkeypatch.setattr(
        megablox, "gmm",
        lambda lhs, rhs, sizes, dtype, tiling: jax.lax.ragged_dot(lhs, rhs, sizes))


def _row_by_row(lhs, rhs, sizes):
    """A grouped product that multiplies each row by its own group's matrix:
    the same bits however many empty groups ``rhs`` holds.  (The CPU's
    ``ragged_dot`` contracts over groups and width together, so its rounding
    follows the group count from some width on; the Pallas kernel's does
    not.)"""
    group = jnp.repeat(jnp.arange(rhs.shape[0]), sizes,
                       total_repeat_length=lhs.shape[0])
    return jnp.einsum("mk,mkn->mn", lhs, rhs[group])


def _expert_model(**overrides):
    """Four layers (one dense, three expert layers), adapters on, the frozen
    base stored in the compute type, every leaf random."""
    cfg = PRESETS["tiny-mla-moe-test"].replace(**{
        "n_layers": 4, "dtype": jnp.float32,
        "lora": LoRAConfig(rank=4, targets=MLA_TARGETS), **overrides})
    model = LlamaForCausalLM(cfg)
    variables = model.init({"params": jax.random.PRNGKey(20)},
                           jnp.zeros((1, 8), jnp.int32))
    variables = {c: variables[c] for c in ("params", "lora") if c in variables}
    leaves, tree = jax.tree.flatten(variables)
    keys = jax.random.split(jax.random.PRNGKey(21), len(leaves))
    return model, jax.tree.unflatten(tree, [
        a + 0.05 * jax.random.normal(k, a.shape, a.dtype)
        if jnp.issubdtype(a.dtype, jnp.floating) else a
        for a, k in zip(leaves, keys)])


def _logits_grads_counters(model, variables, tokens):
    def f(lora):
        logits, sown = model.apply({**variables, "lora": lora}, tokens,
                                   mutable=("moe_stats",))
        return (logits ** 2).mean(), (logits, moe.moe_counters(sown))

    (_, (logits, counters)), grads = jax.value_and_grad(f, has_aux=True)(
        variables.get("lora", {}))
    return logits, grads, counters


@pytest.mark.parametrize("held", [None, (0, 4)], ids=["all_experts", "a_share"])
def test_scanned_expert_model_in_place_is_the_sliced_model(monkeypatch, held):
    """Logits and every LoRA gradient of the scanned stack under full remat
    with the experts — all of them, or the share a layer holds — read in
    place equal the sliced path's exactly."""
    model, variables = _expert_model(experts_held=held)
    tokens = jnp.asarray(_tokens(2, 32))
    monkeypatch.setattr(jax.lax, "ragged_dot", _row_by_row)
    sliced = _logits_grads_counters(model, variables, tokens)
    _take_the_in_place_path(monkeypatch)
    in_place = _logits_grads_counters(model, variables, tokens)
    assert float(sliced[2]["moe_experts_in_place"]) == 0
    assert float(in_place[2]["moe_experts_in_place"]) == 3
    np.testing.assert_array_equal(in_place[0], sliced[0])
    grads = _flat(in_place[1])
    assert grads and all(float(jnp.abs(g).max()) > 0 for g in grads.values())
    for name, g in _flat(sliced[1]).items():
        np.testing.assert_array_equal(grads[name], g, err_msg=name)


#: what keeps a model on the sliced path even where one TPU would run the
#: Pallas kernel -> the configuration that has it
SLICED = {
    "on_the_cpu": {},
    "capacity_dispatch": {"moe_dispatch": "capacity"},
    "trained_experts_no_adapters": {"lora": LoRAConfig()},
    "unrolled_layers": {"scan_layers": False},
    "quantised_experts": {"quantize_base": True, "quant_block": 16},
    "stored_wider_than_computed": {"dtype": jnp.bfloat16},
}


#: ... and what takes the in-place product there
IN_PLACE = {"in_place": {}, "in_place_a_share_of_the_experts": {"experts_held": (0, 4)}}


@pytest.mark.parametrize("why", [*IN_PLACE, *SLICED])
def test_experts_in_place_counts_the_layers_that_took_it(monkeypatch, why):
    """``moe_experts_in_place`` is the scanned expert layers (3) where the
    in-place product is taken and 0 everywhere else."""
    model, variables = _expert_model(**{**IN_PLACE, **SLICED}[why])
    if why != "on_the_cpu":
        _take_the_in_place_path(monkeypatch)
    # the counters are the forward pass's; the gradients through the in-place
    # product are the test above's
    logits, sown = model.apply(variables, jnp.asarray(_tokens(2, 32)),
                               mutable=("moe_stats",))
    counters = moe.moe_counters(sown)
    assert np.isfinite(np.asarray(logits, np.float32)).all()
    assert float(counters["moe_experts_in_place"]) == (3 if why in IN_PLACE else 0)
    assert float(counters["moe_pairs"]) > 0


# ---- the grouped product's tiles follow the groups it is given (ISSUE 35) -----


@pytest.mark.parametrize("rows, groups, tile", [
    (65536, 256, 256),    # the JoyAI cell: 8,192 tokens x 8 over 256 experts
    (16384, 16, 512),     # the 16k cell's held share: 1,024 rows a group
    (65536, 128, 512), (65536, 512, 128),
    (256, 256, 128),      # a decode step's 32 lanes x 8: the smallest tile
    (1536, 3, 512), (768, 2, 256), (640, 2, 128),   # only tiles that divide
])
def test_row_tile_is_the_largest_no_larger_than_a_groups_rows(rows, groups, tile):
    assert moe.gmm_row_tile(rows, groups) == tile
    assert rows % tile == 0


#: (k, n) of every grouped product the two expert configurations run, and
#: of a few-wide-experts model
WIDTHS = [(2048, 768), (768, 2048), (6144, 2048), (2048, 6144),
          (4096, 14336), (14336, 4096), (64, 32)]


@pytest.mark.parametrize("k, n", WIDTHS)
def test_every_tile_the_rule_returns_divides_and_fits_vmem(k, n):
    """Whatever the rows and groups: tiles the kernel's grid can walk (the
    row tile divides the rows; 128-multiples that divide a width, or cover a
    narrow one) inside the VMEM the rule allows itself, under the call's
    16 MB."""
    assert moe._GMM_VMEM_BYTES < 16 * 2 ** 20
    for rows in (128, 256, 4096, 16384, 65536):
        for groups in (1, 8, 16, 256, 1024):
            for itemsize in (2, 4):          # bf16 and float32 experts
                tm, tk, tn = moe._GmmTiling(groups, itemsize)(rows, k, n)
                assert tm == moe.gmm_row_tile(rows, groups)
                assert all(t % 128 == 0 for t in (tm, tk, tn))
                for tile, dim in ((tk, k), (tn, n)):
                    assert dim % tile == 0 or dim < 128
                # (float32 experts at the widest tiles of 1024 are the
                # parent's, over the budget and not this rule's)
                if itemsize == 2 or (tk, tn) != (
                        moe._largest_tile(k), moe._largest_tile(n)):
                    assert (moe._gmm_vmem_bytes(tm, tk, tn, itemsize)
                            <= moe._GMM_VMEM_BYTES)


def _tile_visits(sizes, tile):
    """(row tile, group) pairs with a row in common, counted row by row."""
    group_of_row = np.repeat(np.arange(len(sizes)), sizes)
    return len({(row // tile, g) for row, g in enumerate(group_of_row)})


#: group sizes over 1,024 rows: even, skewed, with empty groups (the first,
#: runs in the middle, the last), aligned to the tile, and with rows behind
#: the last group that no group covers (a held share's pass)
SIZES = {
    "even": (128,) * 8,
    "even_unaligned": (120, 136, 130, 126, 127, 129, 140, 116),
    "skewed": (700, 3, 1, 200, 60, 50, 9, 1),
    "empty_groups": (0, 300, 0, 0, 500, 224, 0, 0),
    "one_group": (0, 0, 1024, 0),
    "rows_no_group_covers": (100, 0, 250, 30),
    "no_rows": (0, 0, 0, 0),
}


@pytest.mark.parametrize("tile", [128, 256, 512])
@pytest.mark.parametrize("sizes", list(SIZES), ids=list(SIZES))
def test_gmm_work_over_need_is_the_tile_visits_counted_by_hand(sizes, tile):
    sizes = np.asarray(SIZES[sizes], np.int32)
    got = float(jax.jit(moe.gmm_work_over_need, static_argnums=1)(
        jnp.asarray(sizes), tile))
    want = _tile_visits(sizes, tile) * tile / max(int(sizes.sum()), 1)
    assert got == pytest.approx(want, rel=1e-6)
    # the issue's reckoning: rows + (non-empty groups - 1) x tile at the most
    if sizes.sum() == 1024:
        assert got <= (1024 + (np.count_nonzero(sizes) - 1) * tile) / 1024


def test_even_load_at_the_cells_shapes_reads_what_the_issue_reckoned():
    """65,536 rows over 256 groups of 256, every group off the tiles' grid by
    a row: 2.99 x the need at 512 rows a tile, 2.0 at 256, 1.5 at 128."""
    sizes = np.full((256,), 256, np.int32)
    sizes[0], sizes[-1] = 255, 257
    for tile, want in ((512, 383 * 512), (256, 511 * 256), (128, 767 * 128)):
        got = float(moe.gmm_work_over_need(jnp.asarray(sizes), tile))
        assert got == pytest.approx(want / 65536, rel=1e-6)


@pytest.mark.parametrize("layer", [None, 1], ids=["plain", "in_place"])
def test_the_tiling_sees_the_layers_own_groups(monkeypatch, layer):
    """Under ``layer=`` the kernel gets ``L·G`` groups; the rule is told
    ``G``, and the tiling is hashable (a static argument of the kernel's
    ``jit``)."""
    from jax.experimental.pallas.ops.tpu.megablox import ops as megablox

    seen = []
    monkeypatch.setattr(moe, "_pallas_grouped_dot_ok", lambda rows: True)
    monkeypatch.setattr(
        megablox, "gmm", lambda lhs, rhs, sizes, dtype, tiling: (
            seen.append((rhs.shape[0], tiling)),
            jax.lax.ragged_dot(lhs, rhs, sizes))[1])
    rows = jnp.ones((1024, 64))
    kernels = jnp.ones((3, 8, 64, 32))
    sizes = jnp.full((8,), 128, jnp.int32)
    if layer is None:
        moe._grouped_dot(rows, kernels[0], sizes)
    else:
        moe._grouped_dot(rows, kernels, sizes, jnp.int32(layer))
    (groups, tiling), = seen
    assert groups == (8 if layer is None else 24)
    want = moe._GmmTiling(8, rows.dtype.itemsize)
    assert tiling == want and hash(tiling) == hash(want)
    assert tiling(1024, 64, 32)[0] == 128


@pytest.mark.parametrize("held", [None, (0, 4)], ids=["all_experts", "a_share"])
def test_gmm_work_over_need_is_a_counter_where_the_kernel_runs(monkeypatch, held):
    """Sown by every dropless layer that runs the Pallas kernel, the worst
    layer's reading among the step's counters; absent where the compiler's
    own product runs (it has no tile)."""
    model, variables = _expert_model(experts_held=held)
    tokens = jnp.asarray(_tokens(2, 32))
    assert "moe_gmm_work_over_need" not in _logits_grads_counters(
        model, variables, tokens)[2]
    _take_the_in_place_path(monkeypatch)
    counters = _logits_grads_counters(model, variables, tokens)[2]
    # whole tiles of 128 rows for a few rows a group: far over the need
    assert 1.0 < float(counters["moe_gmm_work_over_need"]) < float("inf")


@pytest.mark.parametrize("tokens, held, tile", [
    (8192, None, 256), (16384, (0, 16), 512), (32, None, 128)],
    ids=["joyai_4k", "glm_16k_share", "decode_lanes"])
def test_dropless_row_tile_is_static_and_none_without_the_kernel(
        monkeypatch, tokens, held, tile):
    n_held = held[1] if held else 256
    assert moe.dropless_row_tile(tokens * 8, n_held, 256) is None    # the CPU
    monkeypatch.setattr(moe, "_pallas_grouped_dot_ok", lambda rows: True)
    assert moe.dropless_row_tile(tokens * 8, n_held, 256) == tile


#: the variable tree of ``tiny-mla-moe-test`` with rank-4 adapters as the
#: parent of ISSUE 28 had it: the benchmark fills the tree by path,
#: checkpoints and the sharding rules name these paths
TREE = {'lora/blocks/block/attn/kv_a_proj_with_mqa/lora_a': (2, 64, 4),
 'lora/blocks/block/attn/kv_a_proj_with_mqa/lora_b': (2, 4, 40),
 'lora/blocks/block/attn/kv_b_proj/lora_a': (2, 32, 4),
 'lora/blocks/block/attn/kv_b_proj/lora_b': (2, 4, 128),
 'lora/blocks/block/attn/o_proj/lora_a': (2, 64, 4),
 'lora/blocks/block/attn/o_proj/lora_b': (2, 4, 64),
 'lora/blocks/block/attn/q_a_proj/lora_a': (2, 64, 4),
 'lora/blocks/block/attn/q_a_proj/lora_b': (2, 4, 48),
 'lora/blocks/block/attn/q_b_proj/lora_a': (2, 48, 4),
 'lora/blocks/block/attn/q_b_proj/lora_b': (2, 4, 96),
 'lora/blocks/block/moe/shared/down_proj/lora_a': (2, 32, 4),
 'lora/blocks/block/moe/shared/down_proj/lora_b': (2, 4, 64),
 'lora/blocks/block/moe/shared/gate_proj/lora_a': (2, 64, 4),
 'lora/blocks/block/moe/shared/gate_proj/lora_b': (2, 4, 32),
 'lora/blocks/block/moe/shared/up_proj/lora_a': (2, 64, 4),
 'lora/blocks/block/moe/shared/up_proj/lora_b': (2, 4, 32),
 'lora/layer_0/attn/kv_a_proj_with_mqa/lora_a': (64, 4),
 'lora/layer_0/attn/kv_a_proj_with_mqa/lora_b': (4, 40),
 'lora/layer_0/attn/kv_b_proj/lora_a': (32, 4),
 'lora/layer_0/attn/kv_b_proj/lora_b': (4, 128),
 'lora/layer_0/attn/o_proj/lora_a': (64, 4),
 'lora/layer_0/attn/o_proj/lora_b': (4, 64),
 'lora/layer_0/attn/q_a_proj/lora_a': (64, 4),
 'lora/layer_0/attn/q_a_proj/lora_b': (4, 48),
 'lora/layer_0/attn/q_b_proj/lora_a': (48, 4),
 'lora/layer_0/attn/q_b_proj/lora_b': (4, 96),
 'lora/layer_0/mlp/down_proj/lora_a': (128, 4),
 'lora/layer_0/mlp/down_proj/lora_b': (4, 64),
 'lora/layer_0/mlp/gate_proj/lora_a': (64, 4),
 'lora/layer_0/mlp/gate_proj/lora_b': (4, 128),
 'lora/layer_0/mlp/up_proj/lora_a': (64, 4),
 'lora/layer_0/mlp/up_proj/lora_b': (4, 128),
 'params/blocks/block/attn/kv_a_norm/scale': (2, 32),
 'params/blocks/block/attn/kv_a_proj_with_mqa/kernel': (2, 64, 40),
 'params/blocks/block/attn/kv_b_proj/kernel': (2, 32, 128),
 'params/blocks/block/attn/o_proj/kernel': (2, 64, 64),
 'params/blocks/block/attn/q_a_norm/scale': (2, 48),
 'params/blocks/block/attn/q_a_proj/kernel': (2, 64, 48),
 'params/blocks/block/attn/q_b_proj/kernel': (2, 48, 96),
 'params/blocks/block/attn_norm/scale': (2, 64),
 'params/blocks/block/mlp_norm/scale': (2, 64),
 'params/blocks/block/moe/experts/down_proj/kernel': (2, 8, 32, 64),
 'params/blocks/block/moe/experts/gate_proj/kernel': (2, 8, 64, 32),
 'params/blocks/block/moe/experts/up_proj/kernel': (2, 8, 64, 32),
 'params/blocks/block/moe/router/bias': (2, 8),
 'params/blocks/block/moe/router/kernel': (2, 64, 8),
 'params/blocks/block/moe/shared/down_proj/kernel': (2, 32, 64),
 'params/blocks/block/moe/shared/gate_proj/kernel': (2, 64, 32),
 'params/blocks/block/moe/shared/up_proj/kernel': (2, 64, 32),
 'params/embed_tokens/embedding': (256, 64),
 'params/final_norm/scale': (64,),
 'params/layer_0/attn/kv_a_norm/scale': (32,),
 'params/layer_0/attn/kv_a_proj_with_mqa/kernel': (64, 40),
 'params/layer_0/attn/kv_b_proj/kernel': (32, 128),
 'params/layer_0/attn/o_proj/kernel': (64, 64),
 'params/layer_0/attn/q_a_norm/scale': (48,),
 'params/layer_0/attn/q_a_proj/kernel': (64, 48),
 'params/layer_0/attn/q_b_proj/kernel': (48, 96),
 'params/layer_0/attn_norm/scale': (64,),
 'params/layer_0/mlp/down_proj/kernel': (128, 64),
 'params/layer_0/mlp/gate_proj/kernel': (64, 128),
 'params/layer_0/mlp/up_proj/kernel': (64, 128),
 'params/layer_0/mlp_norm/scale': (64,),
 'params/lm_head/kernel': (64, 256)}


def test_the_variable_tree_is_the_one_checkpoints_and_the_benchmark_name():
    cfg = PRESETS["tiny-mla-moe-test"].replace(
        lora=LoRAConfig(rank=4, targets=MLA_TARGETS))
    shapes = jax.eval_shape(lambda: LlamaForCausalLM(cfg).init(
        {"params": jax.random.PRNGKey(0)}, jnp.zeros((1, 8), jnp.int32)))
    tree = {"/".join(str(getattr(k, "key", k)) for k in path): leaf.shape
            for path, leaf in jax.tree_util.tree_flatten_with_path(
                {c: shapes[c] for c in ("params", "lora")})[0]}
    assert tree == TREE
