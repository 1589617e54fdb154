"""The seam between ``models/`` and ``train/trainer.py`` (ISSUE 47): what a
model says of itself at ``train-started`` is, key for key and value for value,
what the trainer built before the model said it — and the trainer reads no
family field, names no sown collection and no module of a model."""

import ast
import json
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))

from finetune_controller_tpu.models.llama import PRESETS  # noqa: E402
from finetune_controller_tpu.models.lora import LoRAConfig  # noqa: E402
from finetune_controller_tpu.models.multimodal import MM_PRESETS  # noqa: E402
from finetune_controller_tpu.train.trainer import TrainConfig, Trainer  # noqa: E402


def _indexer_model():
    """``tests/test_dsa.py::_config``'s model: an indexer picks 8 of 32 keys,
    ``[full, shared, shared, full, shared]`` behind a leading dense layer."""
    from benchmarks.harness.programs import mla_dsa_moe as prog

    conf = json.loads(
        (ROOT / "tests/benchmarks/fixtures/configs/tiny-dsa-moe.json").read_text())
    return prog.model_config(conf, dtype=jnp.float32, remat=False, index_topk=8)


MODELS = {
    "tiny-test": lambda: PRESETS["tiny-test"],
    "tiny-moe-test": lambda: PRESETS["tiny-moe-test"],
    "tiny-mla-moe-test": lambda: PRESETS["tiny-mla-moe-test"],
    "tiny-falcon-h1-test": lambda: PRESETS["tiny-falcon-h1-test"],
    "tiny-nemotron-h-test": lambda: PRESETS["tiny-nemotron-h-test"],
    "tiny-mimo-v2-test": lambda: PRESETS["tiny-mimo-v2-test"],
    "tiny-minicpm-sala-test": lambda: PRESETS["tiny-minicpm-sala-test"],
    "tiny-dsa-moe": _indexer_model,
    "tiny-mm-test": lambda: MM_PRESETS["tiny-mm-test"],
}


def train_started(name: str, as_chip: bool, monkeypatch) -> dict:
    """The model's part of ``train-started`` for a microbatch of 2 x 128
    tokens.  ``as_chip``: what the same trainer says where its kernels run —
    the backend answers ``tpu`` and the step's attention is the flash kernels
    — so that the counters of the TPU's branches are held too."""
    cfg = MODELS[name]().replace(lora=LoRAConfig(rank=4))
    trainer = Trainer(cfg, TrainConfig(
        mode="lora", total_steps=2, batch_size=4, seq_len=128,
        grad_accum_steps=2))
    if as_chip:
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
        trainer.attention_impl = "pallas"
    attrs = trainer._runtime_attrs()
    # the host's own: the device report and the bytes its devices hold
    for key in ("platform", "kind", "count", "device_state_bytes"):
        attrs.pop(key)
    return attrs


#: recorded at the parent (commit f624c98) by this file's own ``train_started``
RECORDED = {
    ('tiny-dsa-moe', False):
        {'attention_impl': 'xla',
         'dsa_full_layers': 2,
         'dsa_shared_layers': 3,
         'lora_joined_projections': {'apart': [], 'joined': 8, 'of': 8},
         'mesh': {},
         'moe_held_rows_over_pairs': 1.0,
         'moe_held_sum_form': 'choices'},
    ('tiny-dsa-moe', True):
        {'attention_impl': 'pallas',
         'dsa_full_layers': 2,
         'dsa_shared_layers': 3,
         'flash_causal_work_over_need': 2.0,
         'lora_joined_projections': {'apart': [], 'joined': 8, 'of': 8},
         'mesh': {},
         'moe_gmm_row_tile': 128,
         'moe_held_rows_over_pairs': 1.0,
         'moe_held_sum_form': 'choices'},
    ('tiny-falcon-h1-test', False):
        {'attention_impl': 'xla',
         'lora_joined_projections': {'apart': [], 'joined': 7, 'of': 7},
         'mesh': {},
         'ssm_chunks_per_row': 16,
         'ssm_layers': 2,
         'ssm_scan_heads_per_block': 0,
         'ssm_scan_impl': 'xla',
         'ssm_state_bytes_per_row': 2048},
    ('tiny-falcon-h1-test', True):
        {'attention_impl': 'pallas',
         'flash_causal_work_over_need': 2.0,
         'lora_joined_projections': {'apart': [], 'joined': 7, 'of': 7},
         'mesh': {},
         'ssm_chunks_per_row': 16,
         'ssm_layers': 2,
         'ssm_scan_heads_per_block': 0,
         'ssm_scan_impl': 'xla',
         'ssm_state_bytes_per_row': 2048},
    ('tiny-mimo-v2-test', False):
        {'attention_impl': 'xla',
         'attention_layers_by_kind': {'F': 2, 'W': 5},
         'attention_pattern': 'FWWWWFW',
         'attention_sink_layers': 5,
         'attention_window': 4,
         'lora_joined_projections': {'apart': [], 'joined': 19, 'of': 19},
         'mesh': {},
         'moe_experts_held': 16},
    ('tiny-mimo-v2-test', True):
        {'attention_impl': 'pallas',
         'attention_layers_by_kind': {'F': 2, 'W': 5},
         'attention_pattern': 'FWWWWFW',
         'attention_sink_layers': 5,
         'attention_window': 4,
         'flash_causal_work_over_need': 2.0,
         'flash_window_work_over_need': 32.3794466403162,
         'lora_joined_projections': {'apart': [], 'joined': 19, 'of': 19},
         'mesh': {},
         'moe_experts_held': 16,
         'moe_gmm_row_tile': 128},
    ('tiny-minicpm-sala-test', False):
        {'attention_impl': 'xla',
         'layer_pattern': 'SLLLLLLS',
         'layers_by_kind': {'L': 6, 'S': 2},
         'lightning_chunks_per_row': 16,
         'lightning_heads_per_block': 0,
         'lightning_scan_impl': 'xla',
         'lora_joined_projections': {'apart': [], 'joined': 21, 'of': 21},
         'mesh': {},
         'sparse_block': 8,
         'sparse_blocks_kept': 6,
         'sparse_dense_len': 32,
         'sparse_kv_groups': 2,
         'sparse_window': 16},
    ('tiny-minicpm-sala-test', True):
        {'attention_impl': 'pallas',
         'flash_causal_work_over_need': 2.0,
         'layer_pattern': 'SLLLLLLS',
         'layers_by_kind': {'L': 6, 'S': 2},
         'lightning_chunks_per_row': 16,
         'lightning_heads_per_block': 0,
         'lightning_scan_impl': 'xla',
         'lora_joined_projections': {'apart': [], 'joined': 21, 'of': 21},
         'mesh': {},
         'sparse_block': 8,
         'sparse_blocks_kept': 6,
         'sparse_dense_len': 32,
         'sparse_kv_groups': 2,
         'sparse_window': 16},
    ('tiny-mla-moe-test', False):
        {'attention_impl': 'xla',
         'lora_joined_projections': {'apart': [], 'joined': 8, 'of': 8},
         'mesh': {}},
    ('tiny-mla-moe-test', True):
        {'attention_impl': 'pallas',
         'flash_causal_work_over_need': 2.0,
         'lora_joined_projections': {'apart': [], 'joined': 8, 'of': 8},
         'mesh': {},
         'moe_gmm_row_tile': 128},
    ('tiny-mm-test', False):
        {'attention_impl': 'xla', 'mesh': {}},
    ('tiny-mm-test', True):
        {'attention_impl': 'pallas',
         'flash_causal_work_over_need': 2.0,
         'mesh': {}},
    ('tiny-moe-test', False):
        {'attention_impl': 'xla',
         'lora_joined_projections': {'apart': [], 'joined': 4, 'of': 4},
         'mesh': {}},
    ('tiny-moe-test', True):
        {'attention_impl': 'pallas',
         'flash_causal_work_over_need': 2.0,
         'lora_joined_projections': {'apart': [], 'joined': 4, 'of': 4},
         'mesh': {}},
    ('tiny-nemotron-h-test', False):
        {'attention_impl': 'xla',
         'layer_pattern': 'EMEM*',
         'layers_by_kind': {'*': 1, 'E': 2, 'M': 2},
         'lora_joined_projections': {'apart': [], 'joined': 6, 'of': 6},
         'mesh': {},
         'moe_experts_held': 16,
         'moe_latent_width': 32,
         'ssm_chunks_per_row': 16,
         'ssm_layers': 2,
         'ssm_scan_heads_per_block': 0,
         'ssm_scan_impl': 'xla',
         'ssm_state_bytes_per_row': 4096},
    ('tiny-nemotron-h-test', True):
        {'attention_impl': 'pallas',
         'flash_causal_work_over_need': 2.0,
         'layer_pattern': 'EMEM*',
         'layers_by_kind': {'*': 1, 'E': 2, 'M': 2},
         'lora_joined_projections': {'apart': [], 'joined': 6, 'of': 6},
         'mesh': {},
         'moe_experts_held': 16,
         'moe_gmm_row_tile': 128,
         'moe_latent_width': 32,
         'ssm_chunks_per_row': 16,
         'ssm_layers': 2,
         'ssm_scan_heads_per_block': 0,
         'ssm_scan_impl': 'xla',
         'ssm_state_bytes_per_row': 4096},
    ('tiny-test', False):
        {'attention_impl': 'xla',
         'lora_joined_projections': {'apart': [], 'joined': 7, 'of': 7},
         'mesh': {}},
    ('tiny-test', True):
        {'attention_impl': 'pallas',
         'flash_causal_work_over_need': 2.0,
         'lora_joined_projections': {'apart': [], 'joined': 7, 'of': 7},
         'mesh': {}},
}


@pytest.mark.parametrize("as_chip", [False, True], ids=["here", "as_chip"])
@pytest.mark.parametrize("name", sorted(MODELS))
def test_train_started_is_the_parents_literal(name, as_chip, monkeypatch):
    assert train_started(name, as_chip, monkeypatch) == RECORDED[name, as_chip]


#: what a family adds to ``LlamaConfig``: by name, and by the prefix its later
#: fields will carry
FAMILY_FIELDS = {"n_experts", "layer_pattern", "experts_held", "indexer_kinds",
                 "sliding_window", "head_widths", "router_aux_weight",
                 "first_k_dense"}
FAMILY_PREFIXES = ("index_", "ssm_", "window_", "moe_", "sparse_", "lightning_")
#: the sown collections and a module of a model, by their names
MODEL_NAMES = {"moe_aux", "moe_stats", "dsa_stats", "sparse_stats", "sink"}


def test_the_trainer_reads_no_family_field_and_names_no_part_of_a_model():
    """``train/trainer.py`` asks the model (``sown``, ``sown_readings``,
    ``keeps_dtype``, ``refuse_mesh``, ``run_description``): no attribute
    access to a family field of ``LlamaConfig`` and no string that names a
    sown collection or a module — the next family that reaches into the
    trainer fails here, not in a review."""
    path = ROOT / "finetune_controller_tpu/train/trainer.py"
    tree = ast.parse(path.read_text())
    fields = {f for f in PRESETS["tiny-test"].__dataclass_fields__
              if f in FAMILY_FIELDS or f.startswith(FAMILY_PREFIXES)}
    assert {"moe_top_k", "ssm_chunk", "index_topk", "window_sink"} <= fields
    # a method some other object has (the phase timer's ``window_row``) is
    # not a field: the prefixes hold what is read, the names what is called too
    called = {id(node.func) for node in ast.walk(tree)
              if isinstance(node, ast.Call)}
    read = sorted({(node.attr, node.lineno) for node in ast.walk(tree)
                   if isinstance(node, ast.Attribute)
                   and (node.attr in FAMILY_FIELDS | fields
                        or (node.attr.startswith(FAMILY_PREFIXES)
                            and id(node) not in called))})
    assert not read, f"trainer.py reads a family's fields: {read}"
    named = sorted({(node.value, node.lineno) for node in ast.walk(tree)
                    if isinstance(node, ast.Constant)
                    and isinstance(node.value, str)
                    and set(node.value.replace('"', " ").split()) & MODEL_NAMES})
    assert not named, f"trainer.py names a part of a model: {named}"
