"""ISSUE 27's benchmark side: the latent-attention expert configuration, its
cell and counts, and a tiny cut fixture of the same program and reference
through the one train driver on the CPU."""

import json
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from benchmarks import run as runner  # noqa: E402
from benchmarks.harness import program, weights  # noqa: E402
from benchmarks.harness.manifest import Manifest  # noqa: E402

MLA = ROOT / "tests/benchmarks/fixtures/BENCHMARK.mla.json"
CELL = "joyai-llm-flash-lora.train-sft-4k"
CONF = Manifest().config("joyai-llm-flash-lora")
COUNTS = Manifest().counts("mla_moe")

#: the source's config.json, by hand (catalog row JoyAI-LLM-Flash)
PUBLISHED = {
    "attention_bias": False, "ep_size": 1, "first_k_dense_replace": 1,
    "head_dim": 64, "hidden_act": "silu", "hidden_size": 2048,
    "intermediate_size": 7168, "kv_lora_rank": 512,
    "max_position_embeddings": 131072, "model_type": "joyai_llm_flash",
    "moe_intermediate_size": 768, "moe_layer_freq": 1, "n_group": 1,
    "n_routed_experts": 256, "n_shared_experts": 1, "norm_topk_prob": True,
    "num_attention_heads": 32, "num_experts_per_tok": 8,
    "num_hidden_layers": 40, "num_key_value_heads": 32,
    "num_nextn_predict_layers": 1, "q_lora_rank": 1536, "qk_head_dim": 192,
    "qk_nope_head_dim": 128, "qk_rope_head_dim": 64, "rms_norm_eps": 1e-06,
    "rope_interleave": True, "rope_scaling": None, "rope_theta": 32000000,
    "routed_scaling_factor": 2.5, "scoring_func": "sigmoid",
    "tie_word_embeddings": False, "topk_group": 1, "topk_method": "noaux_tc",
    "v_head_dim": 128, "vocab_size": 129280,
}


# ---- (f) the manifest with the new entries -----------------------------------


@pytest.mark.parametrize("path", [None, MLA], ids=["BENCHMARK.json", "fixture"])
def test_manifest_with_the_new_entries_has_no_problems(path):
    assert Manifest(path).problems() == []


MISTRAL = ["mistral-7b-qlora.train-sft-2k", "mistral-7b-qlora.train-sft-8k"]
#: per-layer metrics that read no architecture's sizes: every training cell's
NEUTRAL = {"input.wait_share_pct", "trainer.dispatch_ms", "trainer.enqueue_ms",
           "prefetch.producer_busy_pct", "step.forward_share_pct",
           "step.recompute_share_pct", "step.backward_share_pct",
           "step.optimizer_share_pct", "step.unscoped_share_pct",
           "head_loss.time_share_pct", "flash.time_share_pct"}
#: those that count a dense Llama: the Mistral cells' alone
DENSE_LLAMA = {"trainer.mfu_pct", "flash_attention_roofline",
               "proj.time_share_pct", "proj.matmul_roofline",
               "dequant.time_share_pct"}


def test_the_real_manifest_has_its_three_cells_and_no_metric_by_default():
    """What ``test_benchmark_manifest.py`` held of the two-cell manifest
    (``tests/conftest.py::SUPERSEDED``), of the three: the accepted cells
    report what they reported, every per-layer entry lists its cells, and the
    only change to an accepted entry is the new cell's name appended."""
    m = Manifest()
    assert list(m.workloads) == MISTRAL + [CELL]
    for cell in MISTRAL:
        assert m.workload(cell)["driver"] == "train"
        assert m.cell_end_to_end(cell) == ["train_tokens_per_s_chip", "setup_s"]
        assert len(m.cell_per_layer(cell)) == 16
    assert len(m.cell_per_layer(CELL)) == len(NEUTRAL) + len(ADDED)
    for entry in m.raw["per_layer"]:
        want = (MISTRAL + [CELL] if entry["name"] in NEUTRAL
                else MISTRAL if entry["name"] in DENSE_LLAMA else [CELL])
        assert entry["workloads"] == want, entry["name"]
    assert m.end_to_end["train_tokens_per_s_chip"]["workloads"] == MISTRAL + [CELL]
    assert "workloads" not in m.end_to_end["setup_s"]


#: the per-layer entries in the order they were accepted (PRs 23, 24) ...
ACCEPTED = ["input.wait_share_pct", "trainer.dispatch_ms", "trainer.mfu_pct",
            "flash.time_share_pct", "flash_attention_roofline",
            "step.forward_share_pct", "step.recompute_share_pct",
            "step.backward_share_pct", "step.optimizer_share_pct",
            "step.unscoped_share_pct", "proj.time_share_pct",
            "proj.matmul_roofline", "dequant.time_share_pct",
            "head_loss.time_share_pct", "trainer.enqueue_ms",
            "prefetch.producer_busy_pct"]
#: ... and this PR's, appended
ADDED = ["moe.time_share_pct", "moe.shuffle_time_share_pct",
         "moe.experts_roofline", "mla.proj_time_share_pct",
         "mla.flash_attention_roofline", "trainer.mfu_active_pct",
         "mla.proj_matmul_roofline", "blocks.loop_plumbing_share_pct"]


def _cells_of(metric: str) -> list:
    return (MISTRAL + [CELL] if metric in NEUTRAL
            else MISTRAL if metric in DENSE_LLAMA else [CELL])


@pytest.mark.parametrize("metric", ACCEPTED + ADDED)
def test_manifest_registers_and_loads_every_accepted_metric(metric):
    m = Manifest()
    entry, spec = m.per_layer[metric], m.layer_metric(metric)
    assert entry["workloads"] == _cells_of(metric)
    assert entry["moves"] == "train_tokens_per_s_chip"
    assert all(metric in m.cell_per_layer(cell) for cell in entry["workloads"])
    assert callable(m.reducer(spec["reducer"]))
    assert spec["source"] == entry["source"]
    assert (spec["layer"], spec["unit"]) == (entry["layer"], entry["unit"])


def test_the_accepted_entries_stand_first_and_the_new_ones_last():
    names = [m["name"] for m in Manifest().raw["per_layer"]]
    assert names == ACCEPTED + ADDED
    assert [c["name"] for c in Manifest().raw["configs"]] == [
        "mistral-7b-qlora", "joyai-llm-flash-lora"]


def test_configuration_holds_the_published_keys_and_states_its_cut():
    reduced = {"num_hidden_layers": 5, "vocab_size": 16160,
               "num_nextn_predict_layers": 0}
    assert sorted(CONF["reduced"]) == sorted(reduced)
    for key, value in PUBLISHED.items():
        assert CONF[key] == reduced.get(key, value), key
    assert CONF["published"] == {k: PUBLISHED[k] for k in reduced}
    assert CONF["layout"]["chips_sharing_a_layer"] == 8
    assert "pipeline stages" in CONF["layout"]["deployment"]
    assert 8 * CONF["vocab_size"] == PUBLISHED["vocab_size"]


def test_cell_reports_the_neutral_metrics_and_its_own_and_no_dense_llama_count():
    m = Manifest()
    wl = m.workload(CELL)
    assert (wl["batch"], wl["seq"], wl["driver"]) == (2, 4096, "train")
    assert m.cell_end_to_end(CELL) == ["train_tokens_per_s_chip", "setup_s"]
    names = set(m.cell_per_layer(CELL))
    assert names == NEUTRAL | set(ADDED)
    for name in ("moe.experts_roofline", "mla.flash_attention_roofline",
                 "trainer.mfu_active_pct", "mla.proj_matmul_roofline"):
        assert m.layer_metric(name)["args"]["counts"] == "mla_moe"
        assert m.per_layer[name]["workloads"] == [CELL]


def test_program_module_builds_the_published_model():
    cfg = Manifest().program(CONF).model_config(CONF, max_seq_len=4096)
    assert (cfg.attention_kind, cfg.qk_nope_head_dim, cfg.qk_rope_head_dim,
            cfg.v_head_dim) == ("mla", 128, 64, 128)
    assert (cfg.n_experts, cfg.moe_top_k, cfg.moe_d_ff, cfg.n_shared_experts,
            cfg.first_k_dense, cfg.n_layers) == (256, 8, 768, 1, 1, 5)
    assert (cfg.moe_scoring, cfg.moe_dispatch, cfg.moe_routed_scale,
            cfg.router_aux_weight) == ("sigmoid", "dropless", 2.5, 0.0)
    # 10.19 GB of frozen weights in bf16 (ISSUE 27's arithmetic)
    assert cfg.param_count() == pytest.approx(5.095e9, rel=2e-3)


@pytest.mark.parametrize("held,leaf", [("zero", False), ("seeded", True),
                                       (None, True)])
def test_a_run_may_hold_the_selection_bias_at_zero(held, leaf):
    """The cell holds it at zero (every seed then routes evenly and does the
    same work; a seeded 0.1 bell leaves a fifth of the experts empty, a number
    that follows the seed): program AND reference are built without the leaf.
    Seeded, or unsaid as in the tests' fixture, both carry it."""
    from benchmarks.reference import mla_moe as ref

    assert CONF["run"]["selection_bias"] == "zero"
    run = {k: v for k, v in CONF["run"].items() if k != "selection_bias"}
    conf = {**CONF, "run": run if held is None else {**run, "selection_bias": held}}
    cfg = Manifest().program(conf).model_config(conf)
    assert cfg.moe_select_bias is leaf
    assert ref.Arch.from_config(conf).select_bias is leaf
    # not a noaux_tc router: no bias to hold
    other = {**conf, "topk_method": "greedy"}
    assert Manifest().program(other).model_config(other).moe_select_bias is False


def test_program_module_refuses_another_selection_bias():
    conf = {**CONF, "run": {**CONF["run"], "selection_bias": "flat"}}
    with pytest.raises(ValueError, match="selection_bias"):
        Manifest().program(conf).model_config(conf)


@pytest.mark.parametrize("key,value", [
    ("norm_topk_prob", False), ("n_group", 2), ("topk_group", 2),
    ("moe_layer_freq", 2), ("rope_scaling", {"type": "yarn", "factor": 4.0}),
    ("scoring_func", "tanh")])
def test_program_module_refuses_what_it_does_not_compute(key, value):
    """One way to weigh the chosen experts (normalised, then scaled), no
    group limit, an expert layer at every layer, plain RoPE: a configuration
    that asks for anything else is refused, not run as something it is not."""
    with pytest.raises(ValueError):
        Manifest().program(CONF).model_config({**CONF, key: value})


def test_reference_refuses_unnormalised_weights_too():
    from benchmarks.reference import mla_moe as ref

    with pytest.raises(ValueError):
        ref.Arch.from_config({**CONF, "norm_topk_prob": False})


def test_the_superseded_pins_are_three_and_each_has_its_replacement_here():
    """``tests/conftest.py`` skips a pin only beside the test that holds what
    it held: the table names exactly the three pins of the two-cell manifest,
    each defined in its file, each replaced by a test of this module."""
    import conftest

    assert len(conftest.SUPERSEDED) == 3
    here = "tests/benchmarks/test_benchmark_mla_moe.py::"
    for pin, (_, held_by) in conftest.SUPERSEDED.items():
        path, name = pin.split("::")
        assert path in ("tests/benchmarks/test_benchmark_manifest.py",
                        "tests/benchmarks/test_benchmark_scopes.py")
        assert f"def {name}(" in (ROOT / path).read_text()
        assert held_by.startswith(here)
        assert callable(globals()[held_by.removeprefix(here)])


# ---- the two reducers' readings on a made-up step ------------------------------


def _made_up_run():
    import types

    from benchmarks.harness import counts, scopes as S

    def op(seconds, *names):
        return S.Op(seconds, frozenset(names), "forward")

    body = ("LlamaForCausalLM", "while", "body")
    run = types.SimpleNamespace(
        trace=object(), traced=(0.0, 2.0), conf=CONF, manifest=Manifest(),
        notes={"traced_steps": 2, "batch": 2, "seq": 4096},
        peaks=counts.peaks_for("TPU v5 lite"))
    run._step_ops = [[
        op(0.25, *body),                                   # a slicing copy
        op(0.05, *body, "closed_call"),                    # stacking a residual
        op(0.5, *body, "closed_call", "blocks", "block", "moe", "experts"),
        op(0.1, *body, "blocks", "block", "attn", "q_b_proj", "base_matmul"),
        op(0.1, *body, "blocks", "block", "attn", "o_proj", "lora_delta"),
        op(0.3, "LlamaForCausalLM", "layer_0", "attn", "kv_b_proj"),
        op(0.2, "LlamaForCausalLM", "lm_head", "base_matmul"),
    ]]
    return run


def _reduce(run, metric):
    m = Manifest()
    spec = m.layer_metric(metric)
    return m.reducer(spec["reducer"])(run, **spec["args"])


def test_loop_plumbing_is_the_loop_bodys_time_outside_every_layer_module():
    run = _made_up_run()
    assert _reduce(run, "blocks.loop_plumbing_share_pct") == pytest.approx(
        100 * (0.25 + 0.05) / 2.0)
    run._step_ops = [[o for o in run._step_ops[0] if "blocks" in o.names]]
    assert _reduce(run, "blocks.loop_plumbing_share_pct") == 0.0   # not None
    run._step_ops, run.trace = [[]], None
    assert _reduce(run, "blocks.loop_plumbing_share_pct") is None


def test_projection_roofline_counts_five_projections_of_every_layer(capsys):
    need = 2 * 8192 * 5 * (4 * 26_345_472 + 6 * 16 * 28_736) / 197e12
    assert _reduce(_made_up_run(), "mla.proj_matmul_roofline") == pytest.approx(
        100 * need / (0.1 + 0.1 + 0.3))
    assert "needs" in capsys.readouterr().out


# ---- (g) the counts, against numbers worked by hand ---------------------------


def test_counts_of_a_layer_by_hand():
    mla = 2048 * 1536 + 1536 * 6144 + 2048 * 576 + 512 * 8192 + 4096 * 2048
    assert mla == 26_345_472 == COUNTS.mla_proj_params(CONF)
    expert = 3 * 2048 * 768
    assert expert == 4_718_592 == COUNTS.expert_params(CONF)
    # attention + router + shared expert + eight routed experts: 69.3 M
    assert COUNTS.expert_layer_active_params(CONF) == mla + 2048 * 256 + 9 * expert \
        == 69_337_088
    assert COUNTS.dense_layer_active_params(CONF) == mla + 3 * 2048 * 7168 \
        == 70_385_664
    assert COUNTS.head_params(CONF) == 2048 * 16160 == 33_095_680
    assert COUNTS.frozen_active_params(CONF) == 4 * 69_337_088 + 70_385_664 + 33_095_680
    attn_lora = 16 * (3584 + 7680 + 2624 + 8704 + 6144)
    assert COUNTS.lora_params(CONF) == (
        5 * attn_lora + 16 * 3 * (2048 + 7168) + 4 * 16 * 3 * (2048 + 768)) == 3_281_920


def test_flops_of_a_token_by_hand():
    attn = 3 * 4096 * 32 * (192 + 128) * 5          # causal, forward + 2 x backward
    want = 4 * 380_829_696 + 6 * 3_281_920 + attn
    assert COUNTS.lora_train_flops_per_token(CONF, 4096) == want
    assert want == pytest.approx(2.17e9, rel=5e-3)
    assert COUNTS.expert_matmul_flops_per_token(CONF) == 4 * 4 * 8 * 4_718_592
    assert COUNTS.mla_proj_flops_per_token(CONF) == 5 * (
        4 * 26_345_472 + 6 * 16 * 28_736)


@pytest.mark.parametrize("kind,qk,v", [("fwd", 1, 1), ("bwd_dq", 2, 1),
                                        ("bwd_dkv", 2, 2)])
def test_flash_call_counts_uneven_heads(kind, qk, v):
    """A product over the q/k head size is S^2 * H * 192 over the causal
    half, one over the v head size S^2 * H * 128."""
    unit = 2 * 4096 * 4096 * 32
    assert COUNTS.flash_call_flops(CONF, 2, 4096, kind) == unit * (qk * 192 + v * 128)
    rows = 2 * 4096 * 32 * 2
    reads_qk, reads_v = {"fwd": (2, 2), "bwd_dq": (3, 2), "bwd_dkv": (3, 3)}[kind]
    assert COUNTS.flash_call_bytes(CONF, 2, 4096, kind) == rows * (
        reads_qk * 192 + reads_v * 128)


# ---- (e) the tiny cut fixture through the one train driver --------------------


def test_fill_has_a_rule_for_every_leaf_of_the_new_tree():
    import jax
    import jax.numpy as jnp

    from finetune_controller_tpu.models.llama import LlamaForCausalLM

    conf = Manifest(MLA).config("tiny-mla-moe")
    model = LlamaForCausalLM(Manifest(MLA).program(conf).model_config(conf))
    shapes = jax.eval_shape(
        lambda: model.init({"params": jax.random.PRNGKey(0)},
                           jnp.zeros((1, 8), jnp.int32)))
    shapes = {k: shapes[k] for k in ("params", "lora")}
    names = {program.canonical(p) for p, _ in
             jax.tree_util.tree_flatten_with_path(shapes)[0]}
    assert {"blocks/moe/router/kernel", "blocks/moe/router/bias",
            "blocks/moe/experts/gate_proj/kernel",
            "blocks/moe/shared/down_proj/lora_b", "blocks/attn/q_a_norm/scale",
            "blocks/attn/kv_a_proj_with_mqa/kernel",
            "layer_0/mlp/down_proj/kernel", "layer_0/attn/kv_a_norm/scale",
            "layer_0/attn/q_b_proj/lora_a"} <= names
    filled = program.fill(shapes, weights.root_key(2**31 + 5), 64)   # no raise
    experts = filled["params"]["blocks"]["block"]["moe"]["experts"]
    assert experts["gate_proj"]["kernel"].shape == (4, 8, 64, 32)
    # a residual writer is drawn at an eighth, also among the experts
    ratio = float(jnp.std(experts["down_proj"]["kernel"].astype(jnp.float32))
                  / jnp.std(experts["up_proj"]["kernel"].astype(jnp.float32)))
    assert ratio == pytest.approx(0.125 * (64 / 32) ** 0.5, rel=0.05)


def test_the_cut_cell_runs_through_the_train_driver_and_is_correct(capsys):
    line = runner.main(
        ["--workload", "tiny-mla-moe.train-tiny", "--seed", str(2**31 + 27),
         "--seconds", "0.5", "--trace", "0"], manifest_path=MLA, allow_cpu=True)
    printed = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert printed["correct"] is True and line["failed"] == 0
    assert set(printed["compared"]) >= {
        "loss_step1_gap", "loss_step2_gap", "first_grad_norm_gap",
        "param_change_norm_gap", "no_compile_in_window"}
    assert printed["metrics"]["train_tokens_per_s_chip"]["value"] > 0


@pytest.mark.parametrize("seed", [2**31 + 27, 5])
def test_control_in_lower_precision_fails_a_limit_of_the_cut_cell(seed):
    """The reference put in the program's place, computed in scaled float8
    (``q`` on both operands of every product, the experts' and the router's
    among them), comes out NOT correct; given a share of the experts it
    computes another result, which is not correct either."""
    from benchmarks.harness import compare, data
    from benchmarks.harness.drivers.train import judge
    from benchmarks.reference import model as ref_model

    m = Manifest(MLA)
    conf, wl = m.config("tiny-mla-moe"), m.workload("tiny-mla-moe.train-tiny")
    reference_numbers = m.reference(conf).reference_numbers
    gen = data.increment_batches(wl["batch"], wl["seq"], conf["vocab_size"], seed)
    tokens = [next(gen)["tokens"] for _ in range(wl["reference_steps"])]
    ref = reference_numbers(conf, wl, seed, tokens)
    for other in (
            reference_numbers(conf, wl, seed, tokens, q=ref_model.to_fp8,
                              precision="default"),
            reference_numbers(conf, wl, seed, tokens, experts_held=(0, 4))):
        cmp = compare.Comparison()
        judge(cmp, wl["limits"], other, ref)
        assert not cmp.correct
    sound = compare.Comparison()
    judge(sound, wl["limits"], ref, ref)
    assert sound.correct
