"""ISSUE 36's benchmark side: the hybrid state-space configuration, its cell,
counts and per-layer metrics, a tiny cut fixture of the same program and
reference through the one train driver on the CPU — and what four tests of
``test_benchmark_mla_dsa_moe.py`` held of the four-cell manifest, of the five
(``tests/conftest.py::SUPERSEDED``)."""

import json
import sys
import types
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from benchmarks import run as runner  # noqa: E402
from benchmarks.harness import counts, program, scopes as S, trace as T  # noqa: E402
from benchmarks.harness.manifest import Manifest  # noqa: E402

FIXTURE = ROOT / "tests/benchmarks/fixtures/BENCHMARK.falcon-h1.json"
CELL = "falcon-h1-34b-lora.train-sft-8k"
GLM = "glm-5.2-lora.train-sft-16k"
JOYAI = "joyai-llm-flash-lora.train-sft-4k"
MISTRAL = ["mistral-7b-qlora.train-sft-2k", "mistral-7b-qlora.train-sft-8k"]
CONF = Manifest().config("falcon-h1-34b-lora")
COUNTS = Manifest().counts("falcon_h1")


def _catalog_row() -> dict:
    """The source's config.json as the catalog beside the model-configs guide
    holds it; None where this machine has no catalog."""
    path = Path("/opt/skills/guides/model-configs/architectures.jsonl")
    if not path.exists():
        return None
    rows = [json.loads(line) for line in path.read_text().splitlines() if line]
    return next(r for r in rows if r["name"] == "Falcon-H1-34B-Instruct")


#: the widths and the mechanism's numbers, by hand, none of them cut
PUBLISHED_WIDTHS = {
    "hidden_size": 5120, "intermediate_size": 21504, "head_dim": 128,
    "num_attention_heads": 20, "num_key_value_heads": 4,
    "mamba_d_ssm": 4096, "mamba_n_heads": 32, "mamba_d_head": 128,
    "mamba_d_state": 256, "mamba_n_groups": 2, "mamba_d_conv": 4,
    "mamba_chunk_size": 128, "mamba_expand": 2, "mamba_rms_norm": True,
    "mamba_norm_before_gate": False, "mamba_conv_bias": True,
    "mamba_proj_bias": False, "mlp_expansion_factor": 8,
    "embedding_multiplier": 5.656854249492381, "lm_head_multiplier": 0.0078125,
    "attention_in_multiplier": 1, "attention_out_multiplier": 0.0375,
    "key_multiplier": 0.011048543456039804, "ssm_in_multiplier": 0.25,
    "ssm_out_multiplier": 0.08838834764831845,
    "ssm_multipliers": [0.3535533905932738, 0.25, 0.1767766952966369, 0.5,
                        0.3535533905932738],
    "mlp_multipliers": [0.1767766952966369, 0.011160714285714284],
    "rope_theta": 100000000000, "rope_scaling": None, "rms_norm_eps": 1e-05,
    "model_type": "falcon_h1", "max_position_embeddings": 262144,
    "tie_word_embeddings": False,
}


# ---- the manifest of five cells ---------------------------------------------------


@pytest.mark.parametrize("path", [None, FIXTURE], ids=["BENCHMARK.json", "fixture"])
def test_manifest_with_the_new_entries_has_no_problems(path):
    assert Manifest(path).problems() == []


#: per-layer metrics that read no architecture's sizes: every training cell's
NEUTRAL = {"input.wait_share_pct", "trainer.dispatch_ms", "trainer.enqueue_ms",
           "prefetch.producer_busy_pct", "step.forward_share_pct",
           "step.recompute_share_pct", "step.backward_share_pct",
           "step.optimizer_share_pct", "step.unscoped_share_pct",
           "head_loss.time_share_pct", "flash.time_share_pct"}
#: those that count a dense Llama with int4 projections: the Mistral cells' alone ...
MISTRAL_ALONE = {"trainer.mfu_pct", "proj.time_share_pct", "proj.matmul_roofline",
                 "dequant.time_share_pct"}
#: ... and the dense flash kernels' roofline, counted from ``num_attention_heads``,
#: ``num_key_value_heads`` and ``head_dim``, which the new file states: its too
DENSE_FLASH = {"flash_attention_roofline"}
#: PR 27's that both expert configurations report ...
BOTH_EXPERT = {"moe.time_share_pct", "moe.shuffle_time_share_pct",
               "mla.proj_time_share_pct", "mla.proj_matmul_roofline"}
#: ... and the scanned loop's own work, which the new cell's loop has too
SCANNED = {"blocks.loop_plumbing_share_pct"}
JOYAI_ALONE = {"moe.experts_roofline", "mla.flash_attention_roofline",
               "trainer.mfu_active_pct"}
GLM_ALONE = {"dsa.indexer_time_share_pct", "dsa.topk_time_share_pct",
             "dsa.index_scores_roofline", "dsa.sparse_attention_roofline",
             "moe.held_experts_roofline", "trainer.mfu_selected_pct"}
#: the per-layer entries in the order they were accepted (PRs 23, 24, 27, 32) ...
ACCEPTED = ["input.wait_share_pct", "trainer.dispatch_ms", "trainer.mfu_pct",
            "flash.time_share_pct", "flash_attention_roofline",
            "step.forward_share_pct", "step.recompute_share_pct",
            "step.backward_share_pct", "step.optimizer_share_pct",
            "step.unscoped_share_pct", "proj.time_share_pct",
            "proj.matmul_roofline", "dequant.time_share_pct",
            "head_loss.time_share_pct", "trainer.enqueue_ms",
            "prefetch.producer_busy_pct", "moe.time_share_pct",
            "moe.shuffle_time_share_pct", "moe.experts_roofline",
            "mla.proj_time_share_pct", "mla.flash_attention_roofline",
            "trainer.mfu_active_pct", "mla.proj_matmul_roofline",
            "blocks.loop_plumbing_share_pct", "dsa.indexer_time_share_pct",
            "dsa.topk_time_share_pct", "dsa.index_scores_roofline",
            "dsa.sparse_attention_roofline", "moe.held_experts_roofline",
            "trainer.mfu_selected_pct"]
#: ... and this PR's, appended
ADDED = ["ssm.time_share_pct", "ssm.scan_time_share_pct", "ssm.scan_roofline",
         "trainer.mfu_hybrid_pct"]


def _cells_of(metric: str) -> list:
    if metric in NEUTRAL:
        return MISTRAL + [JOYAI, GLM, CELL]
    if metric in MISTRAL_ALONE:
        return MISTRAL
    if metric in DENSE_FLASH:
        return MISTRAL + [CELL]
    if metric in BOTH_EXPERT:
        return [JOYAI, GLM]
    if metric in SCANNED:
        return [JOYAI, GLM, CELL]
    if metric in JOYAI_ALONE:
        return [JOYAI]
    return [GLM] if metric in GLM_ALONE else [CELL]


def test_the_real_manifest_has_its_five_cells_and_no_metric_by_default():
    """What ``test_the_real_manifest_has_its_four_cells_and_no_metric_by_
    default`` held, of the five: the accepted cells report what they
    reported, every per-layer entry lists its cells, and the only change to
    an accepted entry is the new cell's name appended."""
    m = Manifest()
    assert list(m.workloads) == MISTRAL + [JOYAI, GLM, CELL]
    for cell in MISTRAL:
        assert m.workload(cell)["driver"] == "train"
        assert m.cell_end_to_end(cell) == ["train_tokens_per_s_chip", "setup_s"]
        assert len(m.cell_per_layer(cell)) == 16
    assert len(m.cell_per_layer(JOYAI)) == len(NEUTRAL) + 8
    assert len(m.cell_per_layer(GLM)) == len(NEUTRAL) + 5 + len(GLM_ALONE)
    assert set(m.cell_per_layer(CELL)) == NEUTRAL | DENSE_FLASH | SCANNED | set(ADDED)
    for entry in m.raw["per_layer"]:
        assert entry["workloads"] == _cells_of(entry["name"]), entry["name"]
    assert m.end_to_end["train_tokens_per_s_chip"]["workloads"] == (
        MISTRAL + [JOYAI, GLM, CELL])
    assert "workloads" not in m.end_to_end["setup_s"]
    assert m.raw["run_seconds"] == 45 and all(
        w["chips"] == 1 for w in m.raw["workloads"])


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
        "mistral-7b-qlora", "joyai-llm-flash-lora", "glm-5.2-lora",
        "falcon-h1-34b-lora"]


def test_the_new_cell_is_the_one_the_issue_names():
    m = Manifest()
    wl = m.workload(CELL)
    assert (wl["batch"], wl["seq"], wl["driver"], wl["config"]) == (
        1, 8192, "train", "falcon-h1-34b-lora")
    assert (wl["lr"], wl["clip_norm"], wl["prefetch"], wl["first_steps"],
            wl["reference_steps"], wl["reference_rows"], wl["trace_steps"]) == (
        0.002, 1.0, 2, 3, 2, 1, 2)
    assert m.cell_end_to_end(CELL) == ["train_tokens_per_s_chip", "setup_s"]
    assert not set(m.cell_per_layer(CELL)) & (
        MISTRAL_ALONE | BOTH_EXPERT | JOYAI_ALONE | GLM_ALONE)
    for name in ("ssm.scan_roofline", "trainer.mfu_hybrid_pct"):
        assert m.layer_metric(name)["args"]["counts"] == "falcon_h1"
    for name in ADDED:
        assert m.per_layer[name]["workloads"] == [CELL]
        assert m.per_layer[name]["layer"] == (
            "trainer train/trainer.py" if name.startswith("trainer")
            else "state-space mixer models/ssm.py")
    assert m.per_layer["ssm.scan_time_share_pct"]["better"] == "lower"
    assert set(wl["limits"]) == {"loss_gap", "first_grad_norm_gap",
                                 "param_change_norm_gap"}
    entry = m.workloads[CELL]
    assert (entry["chips"], entry["traffic"]) == (1, "train-sft-8k")


def test_the_superseded_pins_are_twelve_and_each_has_its_replacement():
    """``tests/conftest.py`` skips a pin only beside the test that holds what
    it held: three pins of the two-cell manifest, five of the three-cell one
    and four of the four-cell one (held here), each defined in its file, each
    replacement defined in its own."""
    import conftest

    assert len(conftest.SUPERSEDED) == 12
    here = "tests/benchmarks/test_benchmark_falcon_h1.py::"
    held_here = 0
    for pin, (_, held_by) in conftest.SUPERSEDED.items():
        path, name = pin.split("::")
        assert f"def {name}(" in (ROOT / path).read_text()
        by_path, by_name = held_by.split("::")
        assert f"def {by_name}(" in (ROOT / by_path).read_text()
        if held_by.startswith(here):
            assert path == "tests/benchmarks/test_benchmark_mla_dsa_moe.py"
            assert callable(globals()[by_name])
            held_here += 1
    assert held_here == 4


# ---- the configuration ----------------------------------------------------------


def test_configuration_holds_the_published_keys_and_states_its_cut():
    assert sorted(CONF["reduced"]) == ["num_hidden_layers", "vocab_size"]
    assert (CONF["num_hidden_layers"], CONF["vocab_size"]) == (9, 32640)
    assert CONF["published"] == {"num_hidden_layers": 72, "vocab_size": 261120}
    for key, value in PUBLISHED_WIDTHS.items():
        assert CONF[key] == value, key
    assert 8 * CONF["vocab_size"] == CONF["published"]["vocab_size"]
    assert 8 * CONF["num_hidden_layers"] == CONF["published"]["num_hidden_layers"]
    assert CONF["layout"]["chips_sharing_a_layer"] == 8
    assert "pipeline stages" in CONF["layout"]["deployment"]
    assert "14,847,517,696" in CONF["layout"]["deployment"]
    for note in ("rope", "step_size", "gated_norm", "block", "segments",
                 "leaf_names", "weights", "lora_targets", "adapters"):
        assert CONF["assumed"][note], note
    for leaf in ("A_log/bias", "dt_bias/bias", "D/scale", "conv1d/kernel",
                 "conv1d/bias", "norm/scale"):
        assert leaf in CONF["assumed"]["leaf_names"], leaf
    run = CONF["run"]
    assert (run["program"], run["reference"], run["max_seq_len"],
            run["attention_impl"], run["remat_policy"], run["quantize_base"],
            run["frozen_dtype"], run["compute_dtype"], run["lora_rank"],
            run["lora_alpha"], run["mesh"]) == (
        "falcon_h1", "falcon_h1", 8192, "auto", "full", False, "bfloat16",
        "bfloat16", 16, 16.0, {"fsdp": 1})
    assert run["lora_targets"] == ["q_proj", "k_proj", "v_proj", "o_proj",
                                   "in_proj", "out_proj", "gate_proj",
                                   "up_proj", "down_proj"]


def test_configuration_keeps_every_key_of_the_catalog_row_outside_its_cut():
    row = _catalog_row()
    if row is None:
        pytest.skip("no catalog beside the model-configs guide here")
    assert CONF["source"] == row["source_url"]
    for key, value in row["config"].items():
        if key in CONF["reduced"]:
            assert CONF["published"][key] == value, key
        else:
            assert CONF[key] == value, key


def test_program_module_builds_the_published_model_at_its_cut():
    cfg = Manifest().program(CONF).model_config(CONF, max_seq_len=8192)
    assert (cfg.attention_kind, cfg.d_model, cfg.n_heads, cfg.n_kv_heads,
            cfg.head_dim, cfg.d_ff, cfg.rope_theta, cfg.n_layers,
            cfg.vocab_size, cfg.tie_embeddings) == (
        "gqa", 5120, 20, 4, 128, 21504, 1e11, 9, 32640, False)
    assert (cfg.ssm_d_inner, cfg.ssm_n_heads, cfg.ssm_head_dim, cfg.ssm_d_state,
            cfg.ssm_n_groups, cfg.ssm_d_conv, cfg.ssm_chunk) == (
        4096, 32, 128, 256, 2, 4, 128)
    assert (cfg.embedding_multiplier, cfg.lm_head_multiplier,
            cfg.attention_in_multiplier, cfg.attention_out_multiplier,
            cfg.key_multiplier, cfg.ssm_in_multiplier, cfg.ssm_out_multiplier) == (
        5.656854249492381, 0.0078125, 1.0, 0.0375, 0.011048543456039804, 0.25,
        0.08838834764831845)
    assert cfg.ssm_multipliers == tuple(CONF["ssm_multipliers"])
    assert cfg.mlp_multipliers == tuple(CONF["mlp_multipliers"])
    assert (cfg.remat_policy, cfg.attention_impl, cfg.lora.rank) == ("full", "auto", 16)
    # 8.41 GB of frozen weights in bf16 (ISSUE 36's arithmetic): 4.205 B
    assert cfg.param_count() == pytest.approx(4.205e9, rel=2e-4)


@pytest.mark.parametrize("key,value", [
    ("mamba_norm_before_gate", True), ("mamba_rms_norm", False),
    ("mamba_conv_bias", False), ("mamba_proj_bias", True),
    ("attention_bias", True), ("mlp_bias", True), ("projectors_bias", True),
    ("rope_scaling", {"rope_type": "yarn", "factor": 4}),
    ("attn_layer_indices", [0, 4]), ("hidden_act", "gelu"),
    ("model_type", "mamba2"), ("mamba_d_ssm", 4000), ("mamba_n_groups", 3),
    ("ssm_multipliers", [1.0, 1.0]), ("mlp_multipliers", [1.0])])
def test_program_module_refuses_what_it_does_not_compute(key, value):
    """Gate first and then the grouped norm, a biased convolution and no other
    bias, every block both mixers, plain RoPE: a configuration that asks for
    anything else is refused, not run as something it is not."""
    with pytest.raises(ValueError):
        Manifest().program(CONF).model_config({**CONF, key: value})


def test_reference_refuses_another_order_of_gate_and_norm():
    from benchmarks.reference import falcon_h1 as ref

    arch = ref.Arch.from_config(CONF)
    assert (arch.ssm_heads, arch.ssm_state, arch.conv_channels) == (32, 256, 5120)
    assert arch.proj_shapes()["mamba/in_proj"] == (5120, 9248)
    with pytest.raises(ValueError):
        ref.Arch.from_config({**CONF, "mamba_norm_before_gate": True})
    with pytest.raises(ValueError):
        ref.Arch.from_config({**CONF, "mamba_d_ssm": 4000})


# ---- the counts, against numbers worked by hand (ISSUE 36's Motivation) ----------


def test_counts_of_a_layer_by_hand():
    attention = 2 * 5120 * 2560 + 2 * 5120 * 512
    mixer = 5120 * 9248 + 4096 * 5120
    mlp = 3 * 5120 * 21504
    assert (attention, mixer, mlp) == (31_457_280, 68_321_280, 330_301_440)
    assert COUNTS.proj_shapes(CONF)["in_proj"] == (5120, 4096 + 5120 + 32)
    assert COUNTS.mixer_proj_params(CONF) == mixer
    assert COUNTS.layer_matmul_params(CONF) == attention + mixer + mlp == 430_080_000
    head = 5120 * 32640
    assert COUNTS.frozen_matmul_params(CONF) == 9 * 430_080_000 + head == 4_037_836_800
    per_layer = 16 * ((5120 + 2560) * 2 + (5120 + 512) * 2 + (5120 + 9248)
                      + (4096 + 5120) + 3 * (5120 + 21504))
    assert per_layer == 2_081_280
    assert COUNTS.lora_params(CONF) == 9 * per_layer == 18_731_520
    # the mixer's projections are 16 % of a layer's matmul weights, the MLP 77 %
    assert mixer / 430_080_000 == pytest.approx(0.159, abs=1e-3)
    assert mlp / 430_080_000 == pytest.approx(0.768, abs=1e-3)


def test_scan_counts_by_hand():
    q, n, p, h, g = 128, 256, 128, 32, 2
    layer = 2 * q * n * g + 2 * q * p * h + 4 * n * p * h
    assert layer == 5_373_952 == COUNTS.scan_flops_per_token_layer(CONF)
    assert COUNTS.scan_flops_per_token(CONF) == 3 * 9 * layer
    row = 4096 + 512 + 512 + 32 + 4096            # x, B, C, delta read; y written
    assert COUNTS.scan_bytes_per_token(CONF) == 3 * 9 * row * 2
    # at the chip's peaks the FLOPs bound it, not the bytes
    peaks = counts.peaks_for("TPU v5 lite")
    seconds, bound = counts.roofline_seconds(
        COUNTS.scan_flops_per_token(CONF), COUNTS.scan_bytes_per_token(CONF), peaks)
    assert bound == "compute"
    assert seconds == pytest.approx(3 * 9 * layer / 197e12)


def test_flops_of_a_token_by_hand():
    attn = 3 * (4 * 8192 * 8192 * 20 * 128 / 2) * 9 / 8192
    assert attn / 27 == pytest.approx(41.94e6, rel=1e-3)     # 42 MFLOP a layer forward
    want = 4 * 4_037_836_800 + 6 * 18_731_520 + attn + 27 * 5_373_952
    assert COUNTS.lora_train_flops_per_token(CONF, 8192) == pytest.approx(want)
    assert want == pytest.approx(17.54e9, rel=1e-3)
    # the dense flash kernels' count reads this file's 20 / 4 heads of 128
    assert counts.flash_call_flops(CONF, 1, 8192, "fwd") == 2 * (
        2 * 8192 * 8192 * 20 * 128 / 2)
    assert counts.flash_call_bytes(CONF, 1, 8192, "fwd") == (
        2 * 8192 * 20 * 128 * 2 + 2 * 8192 * 4 * 128 * 2)


# ---- every new metric on a made-up step -------------------------------------------


def _made_up_run():
    def op(seconds, *names):
        return S.Op(seconds, frozenset(names), "forward")

    block = ("LlamaForCausalLM", "while", "body", "blocks", "block")
    run = types.SimpleNamespace(
        traced=(0.0, 4.0), conf=CONF, manifest=Manifest(),
        notes={"traced_steps": 2, "batch": 1, "seq": 8192},
        end_to_end={"train_tokens_per_s_chip": 5000.0},
        peaks=counts.peaks_for("TPU v5 lite"))
    run._step_ops = [[
        op(0.30, *block, "mamba", "in_proj", "base_matmul"),
        op(0.05, *block, "mamba", "ssm_conv"),
        op(0.25, *block, "mamba", "ssd_scan"),
        op(0.15, *block, "mamba", "ssd_scan", "while", "body"),
        op(0.03, *block, "mamba", "ssm_gate_norm"),
        op(0.12, *block, "mamba", "out_proj", "lora_delta"),
        op(1.0, *block, "mlp", "up_proj", "base_matmul"),
        op(0.2, *block, "attn", "q_proj", "base_matmul"),
    ]]
    run.trace = T.Trace(devices={0: []}, modules={}, host=[])
    return run


def _reduce(run, metric):
    m = Manifest()
    spec = m.layer_metric(metric)
    return m.reducer(spec["reducer"])(run, **spec["args"])


def test_every_new_metric_reduces_a_made_up_step(capsys):
    run = _made_up_run()
    assert _reduce(run, "ssm.time_share_pct") == pytest.approx(
        100 * (0.30 + 0.05 + 0.25 + 0.15 + 0.03 + 0.12) / 4.0)
    assert _reduce(run, "ssm.scan_time_share_pct") == pytest.approx(
        100 * (0.25 + 0.15) / 4.0)
    tokens = 2 * 8192
    assert _reduce(run, "ssm.scan_roofline") == pytest.approx(
        100 * (27 * 5_373_952) * tokens / 197e12 / 0.40)
    assert "bound by compute" in capsys.readouterr().out
    assert _reduce(run, "trainer.mfu_hybrid_pct") == pytest.approx(
        100 * COUNTS.lora_train_flops_per_token(CONF, 8192) * 5000.0 / 197e12)
    # on a program without the names (the parent's): nothing, and no raise
    run._step_ops = [[o for o in run._step_ops[0] if "mamba" not in o.names]]
    for metric in ("ssm.time_share_pct", "ssm.scan_time_share_pct",
                   "ssm.scan_roofline"):
        assert _reduce(run, metric) is None
    del run.end_to_end["train_tokens_per_s_chip"]
    assert _reduce(run, "trainer.mfu_hybrid_pct") is None


def test_a_scope_the_bytes_bound_reads_its_bytes(monkeypatch):
    """``scope_bound_roofline`` takes the LARGER of the two least times: with
    the recurrence's FLOPs made small, its bytes over the HBM peak."""
    run = _made_up_run()
    module = run.manifest.counts("falcon_h1")
    run.manifest = types.SimpleNamespace(counts=lambda name: types.SimpleNamespace(
        scan_flops_per_token=lambda conf: 1.0,
        scan_bytes_per_token=module.scan_bytes_per_token))
    reduce = Manifest().reducer("scope_bound_roofline")
    got = reduce(run, ["ssd_scan"], "scan_flops_per_token",
                 "scan_bytes_per_token", "falcon_h1")
    assert got == pytest.approx(
        100 * (27 * 9248 * 2) * 2 * 8192 / 819e9 / 0.40)


# ---- the tiny cut fixture through the one train driver ----------------------------


def test_fill_has_a_rule_for_every_leaf_of_the_new_tree():
    import jax
    import jax.numpy as jnp

    from benchmarks.harness import weights
    from finetune_controller_tpu.models.llama import LlamaForCausalLM

    conf = Manifest(FIXTURE).config("tiny-falcon-h1")
    model = LlamaForCausalLM(Manifest(FIXTURE).program(conf).model_config(conf))
    shapes = jax.eval_shape(
        lambda: model.init({"params": jax.random.PRNGKey(0)},
                           jnp.zeros((1, 8), jnp.int32)))
    shapes = {k: shapes[k] for k in ("params", "lora")}
    names = {program.canonical(p) for p, _ in
             jax.tree_util.tree_flatten_with_path(shapes)[0]}
    assert {n for n in names if "/mamba/" in n and "lora" not in n} == {
        f"blocks/mamba/{n}" for n in (
            "in_proj/kernel", "out_proj/kernel", "conv1d/kernel", "conv1d/bias",
            "A_log/bias", "dt_bias/bias", "D/scale", "norm/scale")}
    assert {n for n in names if "/mamba/" in n and "lora" in n} == {
        f"blocks/mamba/{p}/{leaf}" for p in ("in_proj", "out_proj")
        for leaf in ("lora_a", "lora_b")}
    # the reference regenerates exactly these leaves, under exactly these names
    from benchmarks.reference import falcon_h1 as ref

    arch = ref.Arch.from_config(conf)
    assert {f"blocks/{n}" for n in arch.vector_shapes()} | {
        f"blocks/{n}/kernel" for n in arch.proj_shapes()} | {
        "embed_tokens/embedding", "final_norm/scale", "lm_head/kernel"} == {
        n for n in names if "lora" not in n}
    filled = program.fill(shapes, weights.root_key(2**31 + 5), 64)   # no raise
    mamba = filled["params"]["blocks"]["block"]["mamba"]
    assert mamba["conv1d"]["kernel"].shape == (4, 4, 96)
    assert mamba["in_proj"]["kernel"].shape == (4, 64, 164)
    # the harness's rules: A = -exp(0.1 bell), D = 1 + 0.1 bell
    assert float(abs(mamba["A_log"]["bias"]).max()) < 0.35
    assert float(abs(mamba["D"]["scale"] - 1).max()) < 0.35


def test_the_cut_cell_runs_through_the_train_driver_and_is_correct(capsys):
    line = runner.main(
        ["--workload", "tiny-falcon-h1.train-tiny", "--seed", str(2**31 + 36),
         "--seconds", "0.5", "--trace", "0"], manifest_path=FIXTURE, allow_cpu=True)
    out = capsys.readouterr().out
    printed = json.loads(out.strip().splitlines()[-1])
    assert printed["correct"] is True and line["failed"] == 0
    assert set(printed["compared"]) >= {
        "loss_step1_gap", "loss_step2_gap", "first_grad_norm_gap",
        "param_change_norm_gap", "no_compile_in_window", "losses_finite"}
    assert printed["metrics"]["train_tokens_per_s_chip"]["value"] > 0


def _reference_and_tokens(seed):
    from benchmarks.harness import data

    m = Manifest(FIXTURE)
    conf, wl = m.config("tiny-falcon-h1"), m.workload("tiny-falcon-h1.train-tiny")
    gen = data.increment_batches(wl["batch"], wl["seq"], conf["vocab_size"], seed)
    tokens = [next(gen)["tokens"] for _ in range(wl["reference_steps"])]
    return conf, wl, tokens, m.reference(conf).reference_numbers


@pytest.mark.parametrize("seed", [2**31 + 36, 5])
def test_control_in_lower_precision_fails_a_limit_of_the_cut_cell(seed):
    """The reference put in the program's place, computed in scaled float8
    (``q`` on both operands of every product, the recurrence's among them),
    comes out NOT correct; the sound reference against itself is."""
    from benchmarks.harness import compare
    from benchmarks.harness.drivers.train import judge
    from benchmarks.reference import model as ref_model

    conf, wl, tokens, reference_numbers = _reference_and_tokens(seed)
    ref = reference_numbers(conf, wl, seed, tokens)
    control = reference_numbers(conf, wl, seed, tokens, q=ref_model.to_fp8,
                                precision="default")
    cmp = compare.Comparison()
    judge(cmp, wl["limits"], control, ref)
    assert not cmp.correct
    sound = compare.Comparison()
    judge(sound, wl["limits"], ref, ref)
    assert sound.correct


@pytest.mark.parametrize("key", [
    "embedding_multiplier", "lm_head_multiplier", "attention_in_multiplier",
    "attention_out_multiplier", "key_multiplier", "ssm_in_multiplier",
    "ssm_out_multiplier", "ssm_multipliers", "mlp_multipliers"])
def test_the_reference_reads_every_multiplier(key):
    """A configuration with one multiplier changed is another model to the
    reference too: its first loss or its first gradient moves."""
    from benchmarks.harness import compare
    from benchmarks.harness.drivers.train import judge

    conf, wl, tokens, reference_numbers = _reference_and_tokens(7)
    one = dict(wl, reference_steps=1)
    ref = reference_numbers(conf, one, 7, tokens)
    value = conf[key]
    moved = [v * 3 for v in value] if isinstance(value, list) else value * 3
    other = reference_numbers({**conf, key: moved}, one, 7, tokens)
    cmp = compare.Comparison()
    judge(cmp, {k: 1e-6 for k in wl["limits"]}, other, ref)
    assert not cmp.correct
