"""ISSUE 40's benchmark side: the pattern configuration, its cell, counts and
per-layer metrics, two tiny cut fixtures of the same program and reference
(the pattern ``EMEM*`` and the same letters in another order) through the one
train driver on the CPU — and what two tests of ``test_benchmark_falcon_h1.py``
and four of ``test_benchmark_startup.py`` held of the five-cell manifest, of
the six (``tests/conftest.py::SUPERSEDED``)."""

import json
import sys
import types
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

import test_benchmark_falcon_h1 as hybrid  # noqa: E402
import test_benchmark_startup as startup  # noqa: E402

from benchmarks import run as runner  # noqa: E402
from benchmarks.harness import counts, program, scopes as S, trace as T  # noqa: E402
from benchmarks.harness.manifest import Manifest  # noqa: E402

FIXTURE = ROOT / "tests/benchmarks/fixtures/BENCHMARK.nemotron-h.json"
CELL = "nemotron-3-super-lora.train-sft-8k"
CONFIG = "nemotron-3-super-lora"
HYBRID, GLM, JOYAI, MISTRAL = hybrid.CELL, hybrid.GLM, hybrid.JOYAI, hybrid.MISTRAL
CELLS = MISTRAL + [JOYAI, GLM, HYBRID, CELL]
CONF = Manifest().config(CONFIG)
COUNTS = Manifest().counts("nemotron_h")
PATTERN88 = ("MEMEMEM*EMEMEMEM*EMEMEMEM*EMEMEMEMEM*EMEMEMEMEM*EMEMEMEMEM*"
             "EMEMEMEMEM*EMEMEMEM*EMEMEMEME")


def _catalog_row() -> dict:
    path = Path("/opt/skills/guides/model-configs/architectures.jsonl")
    if not path.exists():
        return None
    rows = [json.loads(line) for line in path.read_text().splitlines() if line]
    return next(r for r in rows
                if r["name"] == "NVIDIA-Nemotron-3-Super-120B-A12B-BF16")


#: the widths and the mechanisms' numbers, by hand, none of them cut
PUBLISHED_WIDTHS = {
    "hidden_size": 4096, "head_dim": 128, "num_attention_heads": 32,
    "num_key_value_heads": 2, "intermediate_size": 2688,
    "mamba_num_heads": 128, "mamba_head_dim": 64, "ssm_state_size": 128,
    "n_groups": 8, "conv_kernel": 4, "chunk_size": 128, "expand": 2,
    "moe_intermediate_size": 2688, "moe_latent_size": 1024,
    "moe_shared_expert_intermediate_size": 5376, "n_shared_experts": 1,
    "num_experts_per_tok": 22, "routed_scaling_factor": 5, "n_group": 1,
    "topk_group": 1, "norm_topk_prob": True, "mlp_hidden_act": "relu2",
    "mamba_hidden_act": "silu", "use_conv_bias": True, "use_bias": False,
    "layer_norm_epsilon": 1e-05, "norm_eps": 1e-05, "rms_norm_eps": 1e-05,
    "rope_theta": 10000, "partial_rotary_factor": 1,
    "model_type": "nemotron_h", "max_position_embeddings": 262144,
    "tie_word_embeddings": False, "mtp_hybrid_override_pattern": "*E",
    "time_step_min": 0.001, "time_step_max": 0.1, "time_step_floor": 0.0001,
}


# ---- the manifest of six cells ----------------------------------------------------


@pytest.mark.parametrize("path", [None, FIXTURE], ids=["BENCHMARK.json", "fixture"])
def test_manifest_with_the_new_entries_has_no_problems(path):
    assert Manifest(path).problems() == []


#: the accepted entries to which ISSUE 40 appends its cell: the neutral ones,
#: the dense flash roofline (counted from the file's 32 / 2 heads of 128), the
#: loop's plumbing, the mixer's two shares and the expert layer's two ...
APPENDED = (hybrid.NEUTRAL | hybrid.DENSE_FLASH | hybrid.SCANNED
            | {"ssm.time_share_pct", "ssm.scan_time_share_pct",
               "moe.time_share_pct", "moe.shuffle_time_share_pct"}
            | set(startup.ADDED))
#: ... the accepted entries in the order they were accepted (PRs 23-38) ...
ACCEPTED = hybrid.ACCEPTED + hybrid.ADDED + startup.ADDED
#: ... and this PR's, appended, each with the new cell alone
ADDED = ["trainer.mfu_hybrid_moe_pct", "ssm.scan_roofline_h64",
         "moe.latent_experts_roofline"]


def _cells_of(metric: str) -> list:
    if metric in ADDED:
        return [CELL]
    was = (startup.CELLS if metric in startup.ADDED else hybrid._cells_of(metric))
    return was + [CELL] if metric in APPENDED else was


def test_the_real_manifest_has_its_six_cells_and_no_metric_by_default():
    """What ``test_the_real_manifest_has_its_five_cells_and_five_more_metrics_
    in_each`` held, of the six: the accepted cells report what they reported,
    every per-layer entry lists its cells, and the only change to an accepted
    entry is the new cell's name appended."""
    m = Manifest()
    assert list(m.workloads) == CELLS
    for cell in MISTRAL:
        assert m.workload(cell)["driver"] == "train"
        assert m.cell_end_to_end(cell) == ["train_tokens_per_s_chip", "setup_s"]
        assert len(m.cell_per_layer(cell)) == 16 + 5
    assert len(m.cell_per_layer(JOYAI)) == len(hybrid.NEUTRAL) + 8 + 5
    assert len(m.cell_per_layer(GLM)) == (
        len(hybrid.NEUTRAL) + 5 + len(hybrid.GLM_ALONE) + 5)
    assert set(m.cell_per_layer(HYBRID)) == (
        hybrid.NEUTRAL | hybrid.DENSE_FLASH | hybrid.SCANNED
        | set(hybrid.ADDED) | set(startup.ADDED))
    assert set(m.cell_per_layer(CELL)) == APPENDED | set(ADDED)
    for entry in m.raw["per_layer"]:
        assert entry["workloads"] == _cells_of(entry["name"]), entry["name"]
        assert entry["moves"] == ("setup_s" if entry["name"] in startup.ADDED
                                  else "train_tokens_per_s_chip")
    assert m.end_to_end["train_tokens_per_s_chip"]["workloads"] == CELLS
    assert "workloads" not in m.end_to_end["setup_s"]
    assert m.raw["run_seconds"] == 45 and all(
        w["chips"] == 1 for w in m.raw["workloads"])


@pytest.mark.parametrize("metric", ACCEPTED + ADDED)
def test_manifest_registers_and_loads_every_accepted_metric(metric):
    m = Manifest()
    entry, spec = m.per_layer[metric], m.layer_metric(metric)
    assert entry["workloads"] == _cells_of(metric)
    assert entry["moves"] == spec["moves"] == (
        "setup_s" if metric in startup.ADDED else "train_tokens_per_s_chip")
    assert all(metric in m.cell_per_layer(cell) for cell in entry["workloads"])
    assert callable(m.reducer(spec["reducer"]))
    assert spec["source"] == entry["source"]
    assert (spec["layer"], spec["unit"]) == (entry["layer"], entry["unit"])


@pytest.mark.parametrize("metric", startup.ADDED)
def test_manifest_registers_and_loads_every_start_up_metric(metric):
    """What the test of that name in ``test_benchmark_startup.py`` held, with
    the sixth cell among the cells."""
    m = Manifest()
    entry, spec = m.per_layer[metric], m.layer_metric(metric)
    assert entry["moves"] == spec["moves"] == "setup_s"
    assert entry["workloads"] == CELLS
    assert all(metric in m.cell_per_layer(cell) for cell in CELLS)
    assert (entry["unit"], entry["better"], entry["layer"]) == (
        "s", "lower", startup.LAYER)
    assert entry["source"] == spec["source"] == (
        "program_counter" if metric == "setup.import_s" else "program_span")
    assert spec["reducer"] == "program_startup_stat"
    assert "counts" not in spec["args"]


def test_the_accepted_entries_stand_first_and_the_new_ones_last():
    names = [m["name"] for m in Manifest().raw["per_layer"]]
    assert names == ACCEPTED + ADDED
    assert [c["name"] for c in Manifest().raw["configs"]] == [
        "mistral-7b-qlora", "joyai-llm-flash-lora", "glm-5.2-lora",
        "falcon-h1-34b-lora", CONFIG]


def test_the_hybrid_cell_is_still_the_one_issue_36_named():
    """What ``test_the_new_cell_is_the_one_the_issue_names`` held of the
    hybrid cell; its mixer's two shares list the pattern cell after it."""
    m = Manifest()
    wl = m.workload(HYBRID)
    assert (wl["batch"], wl["seq"], wl["driver"], wl["config"]) == (
        1, 8192, "train", "falcon-h1-34b-lora")
    assert (wl["lr"], wl["clip_norm"], wl["prefetch"], wl["first_steps"],
            wl["reference_steps"], wl["reference_rows"], wl["trace_steps"]) == (
        0.002, 1.0, 2, 3, 2, 1, 2)
    assert m.cell_end_to_end(HYBRID) == ["train_tokens_per_s_chip", "setup_s"]
    assert not set(m.cell_per_layer(HYBRID)) & (
        hybrid.MISTRAL_ALONE | hybrid.BOTH_EXPERT | hybrid.JOYAI_ALONE
        | hybrid.GLM_ALONE | set(ADDED))
    for name in ("ssm.scan_roofline", "trainer.mfu_hybrid_pct"):
        assert m.layer_metric(name)["args"]["counts"] == "falcon_h1"
        assert m.per_layer[name]["workloads"] == [HYBRID]
    for name in ("ssm.time_share_pct", "ssm.scan_time_share_pct"):
        assert m.per_layer[name]["workloads"] == [HYBRID, CELL]
    for name in hybrid.ADDED:
        assert m.per_layer[name]["layer"] == (
            "trainer train/trainer.py" if name.startswith("trainer")
            else "state-space mixer models/ssm.py")
    assert m.per_layer["ssm.scan_time_share_pct"]["better"] == "lower"
    assert set(wl["limits"]) == {"loss_gap", "first_grad_norm_gap",
                                 "param_change_norm_gap"}
    entry = m.workloads[HYBRID]
    assert (entry["chips"], entry["traffic"]) == (1, "train-sft-8k")


def test_the_new_cell_is_the_one_the_issue_names():
    m = Manifest()
    wl = m.workload(CELL)
    assert (wl["batch"], wl["seq"], wl["driver"], wl["config"]) == (
        1, 8192, "train", CONFIG)
    assert (wl["lr"], wl["clip_norm"], wl["prefetch"], wl["first_steps"],
            wl["reference_steps"], wl["reference_rows"], wl["trace_steps"]) == (
        0.002, 1.0, 2, 3, 2, 1, 2)
    assert m.cell_end_to_end(CELL) == ["train_tokens_per_s_chip", "setup_s"]
    # no metric whose counts would read a dense Llama, latent attention, a
    # selection or the other configurations' experts and recurrence
    assert not set(m.cell_per_layer(CELL)) & (
        hybrid.MISTRAL_ALONE | hybrid.JOYAI_ALONE | hybrid.GLM_ALONE
        | {"mla.proj_time_share_pct", "mla.proj_matmul_roofline",
           "ssm.scan_roofline", "trainer.mfu_hybrid_pct"})
    for name in ADDED:
        assert m.layer_metric(name)["args"]["counts"] == "nemotron_h"
        assert m.per_layer[name]["workloads"] == [CELL]
        assert (m.per_layer[name]["unit"], m.per_layer[name]["better"]) == (
            "%", "higher")
    assert [m.per_layer[name]["layer"] for name in ADDED] == [
        "trainer train/trainer.py", "state-space mixer models/ssm.py",
        "expert layer models/moe.py"]
    assert [m.layer_metric(name)["reducer"] for name in ADDED] == [
        "mfu", "scope_bound_roofline", "scope_roofline"]
    assert m.layer_metric("ssm.scan_roofline_h64")["args"]["scopes"] == ["ssd_scan"]
    assert m.layer_metric("moe.latent_experts_roofline")["args"]["scopes"] == [
        "experts"]
    assert set(wl["limits"]) == {"loss_gap", "first_grad_norm_gap",
                                 "param_change_norm_gap"}
    entry = m.workloads[CELL]
    assert (entry["chips"], entry["traffic"], entry["config"]) == (
        1, "train-sft-8k", CONFIG)
    assert "quarter" in entry["why"] and len(entry["why"]) <= 200


def test_the_cells_limits_stand_between_their_two_readings():
    """The one rule of ``PERF.md`` section 4: 3 x the sound seeds' largest,
    under the scaled-float8 control's smallest; the readings are in the
    cell's ``.limits.json``."""
    m = Manifest()
    limits = m.workload(CELL)["limits"]
    with open(ROOT / f"benchmarks/workloads/{CELL}.limits.json") as f:
        read = json.load(f)
    assert read["cell"] == CELL and read["device"]["kind"] == "TPU v5 lite"
    assert read["sound_seeds"] >= 7 and read["control_seeds"] >= 3
    assert len(read["sound"]) == read["sound_seeds"]
    for name, limit in limits.items():
        summary = read["summary"][name]
        assert summary["limit"] == limit
        assert summary["sound_largest"] == max(r[name] for r in read["sound"])
        assert 3.0 * summary["sound_largest"] <= limit <= 3.1 * summary["sound_largest"]
    # the gradient's limit stands between its two readings, and by it the
    # control comes out NOT correct on every seed; scaled float8 hardly moves
    # the other two against the sound runs' own spread (3 x the reading alone)
    grad = read["summary"]["first_grad_norm_gap"]
    assert grad["sound_largest"] < grad["limit"] < grad["control_smallest"]
    for row in read["control"]:
        assert row["first_grad_norm_gap"] > limits["first_grad_norm_gap"], row["seed"]
    assert limits["param_change_norm_gap"] < 0.05 < 1.0


def test_the_superseded_pins_are_twenty_two_and_each_has_its_replacement():
    """``tests/conftest.py`` skips a pin only beside the test that holds what
    it held: the sixteen of the manifests of two to five cells, and six of the
    five-cell manifest's 39 entries (held here)."""
    import conftest

    assert len(conftest.SUPERSEDED) == 22
    here = "tests/benchmarks/test_benchmark_nemotron_h.py::"
    held_here = 0
    for pin, (_, held_by) in conftest.SUPERSEDED.items():
        path, name = pin.split("::")
        assert f"def {name}(" in (ROOT / path).read_text()
        by_path, by_name = held_by.split("::")
        assert f"def {by_name}(" in (ROOT / by_path).read_text()
        if held_by.startswith(here):
            assert path in ("tests/benchmarks/test_benchmark_falcon_h1.py",
                            "tests/benchmarks/test_benchmark_startup.py")
            assert callable(globals()[by_name])
            held_here += 1
    assert held_here == 6


# ---- the configuration ----------------------------------------------------------


def test_configuration_holds_the_published_keys_and_states_its_cut():
    assert CONF["reduced"] == ["num_hidden_layers", "hybrid_override_pattern",
                               "n_routed_experts", "vocab_size",
                               "num_nextn_predict_layers"]
    assert (CONF["num_hidden_layers"], CONF["hybrid_override_pattern"],
            CONF["n_routed_experts"], CONF["vocab_size"],
            CONF["num_nextn_predict_layers"]) == (11, "EMEMEMEMEM*", 128, 32768, 0)
    assert CONF["published"] == {
        "num_hidden_layers": 88, "hybrid_override_pattern": PATTERN88,
        "n_routed_experts": 512, "vocab_size": 131072,
        "num_nextn_predict_layers": 1}
    # published layers 26-36: one period, the model's own 40 : 40 : 8
    assert PATTERN88[26:37] == CONF["hybrid_override_pattern"]
    assert [PATTERN88.count(k) for k in "ME*"] == [40, 40, 8]
    for key, value in PUBLISHED_WIDTHS.items():
        assert CONF[key] == value, key
    assert 4 * CONF["vocab_size"] == CONF["published"]["vocab_size"]
    assert 4 * CONF["n_routed_experts"] == CONF["published"]["n_routed_experts"]
    assert 8 * CONF["num_hidden_layers"] == CONF["published"]["num_hidden_layers"]
    layout = CONF["layout"]
    assert (layout["chips_sharing_a_layer"], layout["leading_dense_layers"]) == (4, 0)
    assert "pipeline stages" in layout["deployment"]
    assert "14,835,670,016" in layout["deployment"]
    for note in ("rms_norm_eps", "no_rotary", "step_size", "expert_layer",
                 "gated_norm", "layer", "held_share", "selection_bias", "not_run",
                 "segments", "leaf_names", "weights", "lora_targets", "adapters"):
        assert CONF["assumed"][note], note
    for leaf in ("fc1_latent_proj/kernel", "fc2_latent_proj/kernel",
                 "experts/up_proj/kernel", "experts/down_proj/kernel",
                 "router/kernel", "A_log/bias", "dt_bias/bias", "D/scale",
                 "conv1d/kernel", "conv1d/bias", "norm/scale"):
        assert leaf in CONF["assumed"]["leaf_names"], leaf
    run = CONF["run"]
    assert (run["program"], run["reference"], run["max_seq_len"],
            run["attention_impl"], run["remat_policy"], run["quantize_base"],
            run["frozen_dtype"], run["compute_dtype"], run["lora_rank"],
            run["lora_alpha"], run["selection_bias"], run["mesh"]) == (
        "nemotron_h", "nemotron_h", 8192, "auto", "full", False, "bfloat16",
        "bfloat16", 16, 16.0, "zero", {"fsdp": 1})
    assert run["lora_targets"] == [
        "q_proj", "k_proj", "v_proj", "o_proj", "in_proj", "out_proj",
        "fc1_latent_proj", "fc2_latent_proj", "up_proj", "down_proj"]


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
            cfg.head_dim, cfg.rope_theta, cfg.n_layers, cfg.layer_pattern,
            cfg.vocab_size, cfg.tie_embeddings, cfg.mlp_act) == (
        "gqa", 4096, 32, 2, 128, 0.0, 11, "EMEMEMEMEM*", 32768, False, "relu2")
    assert cfg.pattern_runs() == (("EM", 5), ("*", 1))
    assert (cfg.ssm_d_inner, cfg.ssm_n_heads, cfg.ssm_head_dim, cfg.ssm_d_state,
            cfg.ssm_n_groups, cfg.ssm_d_conv, cfg.ssm_chunk) == (
        8192, 128, 64, 128, 8, 4, 128)
    assert (cfg.n_experts, cfg.experts_held, cfg.moe_top_k, cfg.moe_d_ff,
            cfg.moe_latent, cfg.n_shared_experts, cfg.moe_scoring,
            cfg.moe_dispatch, cfg.moe_select_bias, cfg.moe_routed_scale,
            cfg.router_aux_weight) == (
        512, (0, 128), 22, 2688, 1024, 2, "sigmoid", "dropless", False, 5.0, 0.0)
    # no multiplier anywhere: nothing is traced for one
    assert (cfg.embedding_multiplier, cfg.ssm_in_multiplier,
            cfg.ssm_out_multiplier, cfg.ssm_multipliers) == (1.0, 1.0, 1.0, (1.0,) * 5)
    assert (cfg.remat_policy, cfg.attention_impl, cfg.lora.rank) == ("full", "auto", 16)
    # 9.30 GB of frozen weights in bf16 (ISSUE 40's arithmetic): 4.648 B
    assert cfg.param_count() == pytest.approx(4.648e9, rel=2e-4)
    from finetune_controller_tpu.models.moe import held_row_bound
    assert held_row_bound(8192 * 22, 128, 512) == 90112


@pytest.mark.parametrize("key,value", [
    ("model_type", "mamba2"), ("mlp_hidden_act", "silu"),
    ("mamba_hidden_act", "gelu"), ("attention_bias", True), ("mlp_bias", True),
    ("mamba_proj_bias", True), ("use_bias", True), ("use_conv_bias", False),
    ("n_group", 4), ("topk_group", 2), ("norm_topk_prob", False),
    ("tie_word_embeddings", True), ("residual_in_fp32", True),
    ("sliding_window", 4096), ("num_nextn_predict_layers", 1),
    ("hybrid_override_pattern", "EMEMEMEMEM-"),
    ("hybrid_override_pattern", "EMEM*"), ("expand", 4), ("n_groups", 3),
    ("moe_shared_expert_intermediate_size", 5000), ("rms_norm_eps", 1e-6)])
def test_program_module_refuses_what_it_does_not_compute(key, value):
    """Squared-ReLU experts without a gate under normalised top-k weights and
    no group limit, a biased convolution and no other bias, an untied head,
    the letters M E * alone (one a layer), no prediction layer: a
    configuration that asks for anything else is refused, not run as something
    it is not."""
    with pytest.raises(ValueError):
        Manifest().program(CONF).model_config({**CONF, key: value})
    bad_run = {**CONF, "run": {**CONF["run"], "selection_bias": "learned"}}
    with pytest.raises(ValueError):
        Manifest().program(CONF).model_config(bad_run)


def test_reference_reads_the_same_sizes_and_refuses_gated_experts():
    from benchmarks.reference import nemotron_h as ref

    arch = ref.Arch.from_config(CONF)
    assert (arch.ssm_heads, arch.ssm_state, arch.conv_channels, arch.ssm_inner) == (
        128, 128, 10240, 8192)
    assert arch.proj_shapes("M")["mamba/in_proj"] == (4096, 18560)
    assert arch.proj_shapes("E")["moe/shared/up_proj"] == (4096, 5376)
    assert arch.other_shapes("E")["moe/experts/up_proj/kernel"] == (128, 1024, 2688)
    assert arch.other_shapes("E")["moe/router/kernel"] == (4096, 512)
    assert (arch.n_experts, arch.experts_held, arch.top_k, arch.select_bias) == (
        512, (0, 128), 22, False)
    assert [p.prefix for p in ref.places(arch.pattern)][-3:] == [
        "blocks/layer_0", "blocks/layer_1", "layer_10"]
    for key, value in (("mlp_hidden_act", "silu"), ("norm_topk_prob", False),
                       ("n_group", 2), ("hybrid_override_pattern", "EMEMEMEMEM-")):
        with pytest.raises(ValueError):
            ref.Arch.from_config({**CONF, key: value})


# ---- the counts, against numbers worked by hand (ISSUE 40's Motivation) ----------


def test_counts_of_the_three_kinds_by_hand():
    mixer = 4096 * 18560 + 8192 * 4096
    attention = 2 * 4096 * 4096 + 2 * 4096 * 256
    expert = 2 * 1024 * 2688
    expert_layer = (4096 * 512 + 2 * 4096 * 1024 + 2 * 4096 * 5376
                    + 22 * (128 / 512) * expert)
    assert (mixer, attention, expert, expert_layer) == (
        109_576_192, 35_651_584, 5_505_024, 84_803_584.0)
    assert COUNTS.layer_active_params(CONF, "M") == mixer
    assert COUNTS.layer_active_params(CONF, "*") == attention
    assert COUNTS.layer_active_params(CONF, "E") == expert_layer
    assert [COUNTS.layers(CONF, k) for k in "ME*"] == [5, 5, 1]
    head = 4096 * 32768
    assert COUNTS.frozen_active_params(CONF) == (
        5 * mixer + 5 * expert_layer + attention + head) == 1_141_768_192
    per_kind = {"*": 16 * (2 * (4096 + 4096) + 2 * (4096 + 256)),
                "M": 16 * ((4096 + 18560) + (8192 + 4096)),
                "E": 16 * (2 * (4096 + 1024) + 2 * (4096 + 5376))}
    assert per_kind == {"*": 401_408, "M": 559_104, "E": 466_944}
    assert COUNTS.lora_params(CONF) == 401_408 + 5 * 559_104 + 5 * 466_944 == 5_531_648
    assert COUNTS.held_share(CONF) == 0.25 and COUNTS.routed_width(CONF) == 512
    assert COUNTS.held_expert_flops_per_token(CONF) == 4 * 5 * 22 * 0.25 * expert
    # by weights a token touches: mixers 48 %, expert layers 37 %, attention 3, head 12
    total = COUNTS.frozen_active_params(CONF)
    assert [round(100 * part / total) for part in (
        5 * mixer, 5 * expert_layer, attention, head)] == [48, 37, 3, 12]


def test_scan_counts_by_hand():
    q, n, p, h, g = 128, 128, 64, 128, 8
    layer = 2 * q * n * g + 2 * q * p * h + 4 * n * p * h
    assert layer == 6_553_600 == COUNTS.scan_flops_per_token_layer(CONF)
    assert COUNTS.scan_flops_per_token(CONF) == 3 * 5 * layer
    row = 8192 + 1024 + 1024 + 128 + 8192          # x, B, C, delta read; y written
    assert COUNTS.scan_bytes_per_token(CONF) == 3 * 5 * row * 2
    # at this head shape the BYTES bound it (35 FLOPs a byte against the
    # chip's 240): the accepted hybrid cell's recurrence is bound by compute
    peaks = counts.peaks_for("TPU v5 lite")
    seconds, bound = counts.roofline_seconds(
        COUNTS.scan_flops_per_token(CONF), COUNTS.scan_bytes_per_token(CONF), peaks)
    assert bound == "memory"
    assert seconds == pytest.approx(3 * 5 * row * 2 / 819e9)


def test_flops_of_a_token_by_hand():
    attn = 3 * (4 * 8192 * 8192 * 32 * 128 / 2) * 1 / 8192
    want = 4 * 1_141_768_192 + 6 * 5_531_648 + attn + 15 * 6_553_600
    assert COUNTS.lora_train_flops_per_token(CONF, 8192) == pytest.approx(want)
    assert want == pytest.approx(4.90e9, rel=1e-3)
    # the dense flash kernels' count reads this file's 32 / 2 heads of 128
    assert counts.flash_call_flops(CONF, 1, 8192, "fwd") == 2 * (
        2 * 8192 * 8192 * 32 * 128 / 2)
    assert counts.flash_call_bytes(CONF, 1, 8192, "fwd") == (
        2 * 8192 * 32 * 128 * 2 + 2 * 8192 * 2 * 128 * 2)


# ---- every new metric on a made-up step -------------------------------------------


def _made_up_run():
    def op(seconds, *names):
        return S.Op(seconds, frozenset(names), "forward")

    unit = ("LlamaForCausalLM", "while", "body", "blocks")
    run = types.SimpleNamespace(
        traced=(0.0, 2.0), conf=CONF, manifest=Manifest(),
        notes={"traced_steps": 2, "batch": 1, "seq": 8192},
        end_to_end={"train_tokens_per_s_chip": 9000.0},
        peaks=counts.peaks_for("TPU v5 lite"))
    run._step_ops = [[
        op(0.20, *unit, "layer_1", "mamba", "in_proj", "base_matmul"),
        op(0.25, *unit, "layer_1", "mamba", "ssd_scan"),
        op(0.05, *unit, "layer_1", "mamba", "ssd_scan", "while", "body"),
        op(0.10, *unit, "layer_0", "moe", "experts"),
        op(0.15, *unit, "layer_0", "moe", "experts", "gmm"),
        op(0.04, *unit, "layer_0", "moe", "moe_route"),
        op(0.03, *unit, "layer_0", "moe", "fc1_latent_proj", "base_matmul"),
        op(0.08, *unit, "layer_0", "moe", "shared", "up_proj", "base_matmul"),
        op(0.05, "LlamaForCausalLM", "layer_10", "attn", "q_proj", "base_matmul"),
    ]]
    run.trace = T.Trace(devices={0: []}, modules={}, host=[])
    return run


def _reduce(run, metric):
    m = Manifest()
    spec = m.layer_metric(metric)
    return m.reducer(spec["reducer"])(run, **spec["args"])


def test_every_new_metric_reduces_a_made_up_step(capsys):
    run = _made_up_run()
    tokens = 2 * 8192
    assert _reduce(run, "ssm.scan_roofline_h64") == pytest.approx(
        100 * (15 * 18560 * 2) * tokens / 819e9 / 0.30)
    assert "bound by memory" in capsys.readouterr().out
    assert _reduce(run, "moe.latent_experts_roofline") == pytest.approx(
        100 * 4 * 5 * 22 * 0.25 * 5_505_024 * tokens / 197e12 / 0.25)
    assert _reduce(run, "trainer.mfu_hybrid_moe_pct") == pytest.approx(
        100 * COUNTS.lora_train_flops_per_token(CONF, 8192) * 9000.0 / 197e12)
    # the accepted shares the cell is appended to read the same names
    assert _reduce(run, "ssm.time_share_pct") == pytest.approx(100 * 0.50 / 2.0)
    assert _reduce(run, "ssm.scan_time_share_pct") == pytest.approx(100 * 0.30 / 2.0)
    assert _reduce(run, "moe.time_share_pct") == pytest.approx(100 * 0.40 / 2.0)
    assert _reduce(run, "moe.shuffle_time_share_pct") == pytest.approx(100 * 0.04 / 2.0)
    # on a program without the names (the parent's): nothing, and no raise
    run._step_ops = [[o for o in run._step_ops[0]
                      if not {"mamba", "moe"} & o.names]]
    for metric in ("ssm.scan_roofline_h64", "moe.latent_experts_roofline"):
        assert _reduce(run, metric) is None
    del run.end_to_end["train_tokens_per_s_chip"]
    assert _reduce(run, "trainer.mfu_hybrid_moe_pct") is None


def test_the_trace_table_tool_names_the_three_kinds_and_their_scopes():
    import importlib

    from benchmarks.tools import trace_table

    scopes, projections = trace_table.SCOPES, trace_table.PROJECTIONS
    try:
        tool = importlib.import_module("benchmarks.tools.trace_table_nemotron_h")
        assert tool.trace_table.PROJECTIONS == ("mamba", "moe", "attn", "lm_head")
        listed = tool.trace_table.SCOPES
        for scope in ("ssd_scan", "ssm_conv", "ssm_gate_norm", "experts",
                      "moe_route", "moe_dispatch", "moe_combine"):
            assert listed.index(scope) < listed.index("base_matmul")
        assert listed.index("mamba") > listed.index("lora_delta")
        assert set(scopes) < set(listed)
    finally:
        trace_table.SCOPES, trace_table.PROJECTIONS = scopes, projections


# ---- the tiny cut fixtures through the one train driver ----------------------------


def test_fill_has_a_rule_for_every_leaf_of_the_new_tree():
    import jax
    import jax.numpy as jnp

    from benchmarks.harness import weights
    from finetune_controller_tpu.models.llama import LlamaForCausalLM

    conf = Manifest(FIXTURE).config("tiny-nemotron-h")
    model = LlamaForCausalLM(Manifest(FIXTURE).program(conf).model_config(conf))
    shapes = jax.eval_shape(
        lambda: model.init({"params": jax.random.PRNGKey(0)},
                           jnp.zeros((1, 8), jnp.int32)))
    shapes = {k: shapes[k] for k in ("params", "lora")}
    names = {program.canonical(p) for p, _ in
             jax.tree_util.tree_flatten_with_path(shapes)[0]}
    frozen = {n for n in names if "lora" not in n}
    assert {n for n in frozen if n.startswith("blocks/layer_0/")} == {
        f"blocks/layer_0/{n}" for n in (
            "norm/scale", "moe/router/kernel", "moe/fc1_latent_proj/kernel",
            "moe/fc2_latent_proj/kernel", "moe/experts/up_proj/kernel",
            "moe/experts/down_proj/kernel", "moe/shared/up_proj/kernel",
            "moe/shared/down_proj/kernel")}
    assert {n for n in frozen if n.startswith("blocks/layer_1/")} == {
        f"blocks/layer_1/{n}" for n in (
            "norm/scale", "mamba/in_proj/kernel", "mamba/out_proj/kernel",
            "mamba/conv1d/kernel", "mamba/conv1d/bias", "mamba/A_log/bias",
            "mamba/dt_bias/bias", "mamba/D/scale", "mamba/norm/scale")}
    assert {n for n in frozen if n.startswith("layer_4/")} == {
        f"layer_4/{n}" for n in ("norm/scale", "attn/q_proj/kernel",
                                 "attn/k_proj/kernel", "attn/v_proj/kernel",
                                 "attn/o_proj/kernel")}
    assert all(weights.is_stacked(n) == n.startswith("blocks/") for n in names)
    filled = program.fill(shapes, weights.root_key(2**31 + 5), 64)   # no raise
    moe = filled["params"]["blocks"]["layer_0"]["moe"]
    assert moe["experts"]["up_proj"]["kernel"].shape == (2, 8, 32, 24)
    assert moe["router"]["kernel"].shape == (2, 64, 32)
    assert "bias" not in moe["router"]          # selection_bias: zero
    # the harness's rules: unit-variance router logits, a down projection
    # drawn 8 times smaller than an up projection of the same fan-in
    assert float(moe["router"]["kernel"].std()) == pytest.approx(64 ** -0.5, rel=0.1)
    assert float(moe["experts"]["down_proj"]["kernel"].std()) == pytest.approx(
        0.125 * 24 ** -0.5, rel=0.1)
    assert float(moe["fc2_latent_proj"]["kernel"].std()) == pytest.approx(
        32 ** -0.5, rel=0.1)


@pytest.mark.parametrize("cell", ["tiny-nemotron-h.train-tiny",
                                  "tiny-nemotron-h-order.train-tiny"],
                         ids=["EMEM*", "M*EME"])
def test_the_cut_cell_runs_through_the_train_driver_and_is_correct(cell, capsys):
    """The whole model's losses, first clipped gradient and two AdamW steps
    are the reference's, on the pattern and on the same letters in another
    order (where every layer is unrolled and no stack exists)."""
    line = runner.main(
        ["--workload", cell, "--seed", str(2**31 + 40), "--seconds", "0.5",
         "--trace", "0"], manifest_path=FIXTURE, allow_cpu=True)
    out = capsys.readouterr().out
    printed = json.loads(out.strip().splitlines()[-1])
    assert printed["correct"] is True and line["failed"] == 0
    assert set(printed["compared"]) >= {
        "loss_step1_gap", "loss_step2_gap", "first_grad_norm_gap",
        "param_change_norm_gap", "no_compile_in_window", "losses_finite"}
    assert printed["metrics"]["train_tokens_per_s_chip"]["value"] > 0


def _reference_and_tokens(seed, config="tiny-nemotron-h"):
    from benchmarks.harness import data

    m = Manifest(FIXTURE)
    conf, wl = m.config(config), m.workload(f"{config}.train-tiny")
    gen = data.increment_batches(wl["batch"], wl["seq"], conf["vocab_size"], seed)
    tokens = [next(gen)["tokens"] for _ in range(wl["reference_steps"])]
    return conf, wl, tokens, m.reference(conf).reference_numbers


def test_the_order_of_the_letters_changes_the_references_loss():
    conf, wl, tokens, reference_numbers = _reference_and_tokens(9)
    other, _, _, _ = _reference_and_tokens(9, "tiny-nemotron-h-order")
    one = dict(wl, reference_steps=1)
    a = reference_numbers(conf, one, 9, tokens)
    b = reference_numbers(other, one, 9, tokens)
    assert abs(a["losses"][0] - b["losses"][0]) > 1e-4
    # the pattern's stack holds two repeats of each adapter, the other order none
    assert any(n.endswith("[1]") for n in a["grad_norms"])
    assert not any("[" in n for n in b["grad_norms"])


@pytest.mark.parametrize("seed", [2**31 + 40, 5])
def test_control_in_lower_precision_fails_a_limit_of_the_cut_cell(seed):
    """The reference put in the program's place, computed in scaled float8
    (``q`` on both operands of every product, the recurrence's and the
    experts' among them), comes out NOT correct; the sound reference against
    itself is."""
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


@pytest.mark.parametrize("key,value", [
    ("routed_scaling_factor", 2.5), ("num_experts_per_tok", 3),
    ("layer_norm_epsilon", 0.1)])
def test_the_reference_reads_the_keys_that_shape_a_layer(key, value):
    """A configuration with one of them changed is another model to the
    reference too: its first loss or its first gradient moves."""
    from benchmarks.harness import compare
    from benchmarks.harness.drivers.train import judge

    conf, wl, tokens, reference_numbers = _reference_and_tokens(7)
    one = dict(wl, reference_steps=1)
    ref = reference_numbers(conf, one, 7, tokens)
    other = reference_numbers({**conf, key: value}, one, 7, tokens)
    cmp = compare.Comparison()
    judge(cmp, {k: 1e-6 for k in wl["limits"]}, other, ref)
    assert not cmp.correct
