"""The program names its own work in a profiler trace (ISSUE 24,
docs/observability.md §Names in a profile).

* the device's work: ``jax.named_scope`` s and flax module names in the
  compiled train step's metadata — in the forward pass, under remat and in
  the backward pass — and ``name=`` on the four Pallas calls;
* the host's work: ``obs.annotate`` spans in the host planes of an open
  ``jax.profiler`` session, and in nothing else.
"""

from __future__ import annotations

import glob
import re
import subprocess
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from finetune_controller_tpu.models.llama import LlamaConfig
from finetune_controller_tpu.models.lora import LoRAConfig
from finetune_controller_tpu.train.trainer import TrainConfig, Trainer

BATCH, SEQ = 2, 16


def tiny_trainer(grad_accum_steps: int = 1, **train_kw) -> Trainer:
    cfg = LlamaConfig(
        vocab_size=64, d_model=32, n_layers=2, n_heads=2, n_kv_heads=1,
        d_ff=64, max_seq_len=SEQ, attention_impl="xla", remat_policy="full",
        quantize_base=True, quant_block=16, lora=LoRAConfig(rank=2, alpha=4.0),
        dtype=jnp.bfloat16)
    return Trainer(cfg, TrainConfig(
        mode="lora", batch_size=BATCH, seq_len=SEQ, learning_rate=1e-3,
        warmup_steps=0, schedule="constant", total_steps=10,
        grad_accum_steps=grad_accum_steps, trace=False, **train_kw))


# ---- the device's work: the compiled step's metadata ---------------------------

def _step_op_names():
    """``op_name`` of every instruction of the tiny train step compiled for
    the CPU, with two microbatches so that ``grad_accum`` is there.  The
    persistent cache is off: its key leaves metadata out, so a hit could hand
    back a program compiled before the names were there."""
    tr = tiny_trainer(grad_accum_steps=2)
    shapes = jax.eval_shape(tr.raw_init, jax.random.PRNGKey(0))
    batch = {"tokens": jax.ShapeDtypeStruct((BATCH, SEQ), jnp.int32),
             "loss_mask": jax.ShapeDtypeStruct((BATCH, SEQ), jnp.float32)}
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    try:
        with tr.mesh:
            text = jax.jit(tr._train_step).lower(shapes, batch).compile().as_text()
    finally:
        jax.config.update("jax_enable_compilation_cache", was)
    return sorted(set(re.findall(r'op_name="([^"]*)"', text)))


@pytest.fixture(scope="module")
def op_names():
    """The step as the rule leaves it: a microbatch of 16 rows is too few for
    any projection to take the joined form (``models/lora.py``)."""
    from finetune_controller_tpu.models import lora

    names = _step_op_names()
    assert not any("joined_product" in n or "custom_vjp" in n for n in names)
    assert lora.joins_base_product(16, 32, 2) is False
    return names


@pytest.fixture(scope="module")
def op_names_joined():
    """The same step with every adapted projection in the joined form."""
    from finetune_controller_tpu.models import lora

    rule = lora.joins_base_product
    lora.joins_base_product = lambda *a, **k: True
    try:
        return _step_op_names()
    finally:
        lora.joins_base_product = rule


#: one iteration of the scanned stack.  The stack is built in a method the
#: model calls on itself (``_scanned_blocks``), and flax names such a call
#: in the stack; a program served from a compile cache written before the
#: method existed has the stack without it (the cache's key leaves names out)
LAYER = r"(?:LlamaForCausalLM\._scanned_blocks/)?while/body/closed_call/"
FORWARD = r"/jvp\(LlamaForCausalLM\)/" + LAYER
RECOMPUTE = (r"/transpose\(jvp\(LlamaForCausalLM\)\)/" + LAYER
             + r"checkpoint/rematted_computation/")
BACKWARD = r"/transpose\(jvp\(LlamaForCausalLM\)\)/" + LAYER + "checkpoint/"
PASSES = {"forward": FORWARD, "recompute": RECOMPUTE, "backward": BACKWARD}
PROJECTIONS = ["attn/q_proj", "attn/k_proj", "attn/v_proj", "attn/o_proj",
               "mlp/gate_proj", "mlp/up_proj", "mlp/down_proj"]


LAYER_SCOPES = [*[f"{p}/base_matmul" for p in PROJECTIONS],
                *[f"{p}/lora_delta" for p in PROJECTIONS],
                "attn/rope", "attn_norm", "mlp_norm"]
#: the block's last base matmul feeds nothing the backward pass needs: the
#: compiler drops its replay (so does the chip's: PERF.md section 5)
NOT_REPLAYED = {("mlp/down_proj/base_matmul", "recompute")}


@pytest.mark.parametrize("scope,which", [
    (s, w) for s in LAYER_SCOPES for w in ("forward", "recompute", "backward")
    if (s, w) not in NOT_REPLAYED])
def test_layer_scope_is_in_the_step_metadata_in_each_pass(op_names, scope, which):
    rx = re.compile(PASSES[which] + "blocks/block/" + scope + "/")
    assert any(rx.search(n) for n in op_names), (scope, which)


@pytest.mark.parametrize("scope,which", [
    (f"{p}/{s}", w) for p in PROJECTIONS for s in ("base_matmul", "lora_delta")
    for w in ("forward", "recompute", "backward")
    if (f"{p}/{s}", w) not in NOT_REPLAYED])
def test_joined_projection_keeps_both_scopes_in_each_pass(
        op_names_joined, scope, which):
    """The joined product sits under ``base_matmul`` and what only the
    adapter needs under ``lora_delta``, in the hand-written backward rule
    too: the metrics that read the two names find them in every pass."""
    rx = re.compile(PASSES[which] + "blocks/block/" + scope + "/")
    assert any(rx.search(n) for n in op_names_joined), (scope, which)


@pytest.mark.parametrize("which", ["forward", "recompute"])
@pytest.mark.parametrize("proj", PROJECTIONS)
def test_dequant_scope_runs_forward_and_under_remat_only(op_names, proj, which):
    """The frozen base has no gradient: dequantisation is replayed under
    remat, never transposed."""
    rx = re.compile(PASSES[which] + f"blocks/block/{proj}/dequant_int4/")
    assert any(rx.search(n) for n in op_names), (proj, which)
    assert not any(re.search(BACKWARD + f"blocks/block/{proj}/dequant_int4/", n)
                   for n in op_names)


#: the head is built in a method the model calls on itself (``_head``, as
#: ``_scanned_blocks`` above): flax names the call in the stack, and a program
#: served from a compile cache written before the method existed lacks it
HEAD = r"(?:LlamaForCausalLM\._head/)?"


@pytest.mark.parametrize("pattern", [
    r"^jit\(_train_step\)/grad_accum/while/body/closed_call/jvp\(loss\)/",
    r"^jit\(_train_step\)/grad_accum/while/body/closed_call/transpose\(jvp\(loss\)\)/",
    r"^jit\(_train_step\)/grad_accum/while/body/closed_call/jvp\(LlamaForCausalLM\)/" + HEAD + "final_norm/",
    r"^jit\(_train_step\)/grad_accum/while/body/closed_call/jvp\(LlamaForCausalLM\)/" + HEAD + "lm_head/base_matmul/",
    r"^jit\(_train_step\)/grad_accum/while/body/closed_call/transpose\(jvp\(LlamaForCausalLM\)\)/" + HEAD + "lm_head/base_matmul/",
    r"^jit\(_train_step\)/grad_accum/while/body/closed_call/jvp\(LlamaForCausalLM\)/embed_tokens/",
    r"^jit\(_train_step\)/optimizer/",
    r"^jit\(_train_step\)/optimizer/jit\(clip\)/",
], ids=["loss-forward", "loss-backward", "final_norm", "lm_head-forward",
        "lm_head-backward", "embed_tokens", "optimizer", "optimizer-clip"])
def test_step_level_scope_is_in_the_step_metadata(op_names, pattern):
    assert any(re.search(pattern, n) for n in op_names)


def test_every_pass_of_the_model_sits_inside_grad_accum(op_names):
    model = [n for n in op_names if "LlamaForCausalLM" in n and n.startswith("jit(")]
    assert model and all(
        n.startswith("jit(_train_step)/grad_accum/while") for n in model)


# ---- the device's work: kernel names ---------------------------------------------

def _names_in(jaxpr) -> set[str]:
    return set(re.findall(r"\bname=(\w+)", str(jaxpr)))


def test_jaxpr_shows_the_three_flash_kernel_names():
    from finetune_controller_tpu.ops.pallas.flash_attention import flash_attention

    q = jnp.zeros((1, 128, 4, 64), jnp.bfloat16)
    kv = jnp.zeros((1, 128, 2, 64), jnp.bfloat16)

    def loss(q, k, v):
        return flash_attention(q, k, v, interpret=True).astype(jnp.float32).sum()

    assert "flash_fwd" in _names_in(jax.make_jaxpr(loss)(q, kv, kv))
    names = _names_in(jax.make_jaxpr(jax.grad(loss, argnums=(0, 1, 2)))(q, kv, kv))
    assert {"flash_fwd", "flash_bwd_dq", "flash_bwd_dkv"} <= names


def test_jaxpr_shows_the_paged_kernel_name():
    from finetune_controller_tpu.ops.pallas.paged_attention import paged_attention

    pages, page, hkv, d = 4, 16, 2, 128
    q = jnp.zeros((2, 1, 4, d), jnp.bfloat16)
    pool = jnp.zeros((pages, page, hkv, d), jnp.bfloat16)
    table = jnp.zeros((2, 2), jnp.int32)
    idx = jnp.array([3, 17], jnp.int32)
    jaxpr = jax.make_jaxpr(
        lambda *a: paged_attention(*a, interpret=True))(q, pool, pool, table, idx)
    assert "paged_decode" in _names_in(jaxpr)


# ---- the host's work: annotations ----------------------------------------------------

def _host_spans(trace_dir) -> dict[str, list]:
    from jax.profiler import ProfileData

    (path,) = glob.glob(f"{trace_dir}/plugins/profile/*/*.xplane.pb")
    out: dict[str, list] = {}
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    out.setdefault(e.name, []).append(
                        (e.start_ns, e.start_ns + e.duration_ns, dict(e.stats)))
    return out


@pytest.fixture(scope="module")
def session(tmp_path_factory):
    """Two tiny steps through the prefetch thread inside a profiler session,
    one step and some annotations outside it."""
    from finetune_controller_tpu.data.prefetch import prefetch_batches
    from finetune_controller_tpu.obs import annotate

    tr = tiny_trainer()
    rng = np.random.default_rng(0)

    def batches():
        while True:
            yield {"tokens": rng.integers(0, 64, (BATCH, SEQ), dtype=np.int32),
                   "loss_mask": np.ones((BATCH, SEQ), np.float32)}

    feed = prefetch_batches(batches(), depth=2, transfer=tr.shard_batch)
    state = tr.init_state()
    state, _ = tr.step(state, next(feed))     # compiles; outside the session
    jax.block_until_ready(state)
    with annotate("outside.before"):
        pass
    trace_dir = tmp_path_factory.mktemp("profile")
    jax.profiler.start_trace(str(trace_dir))
    try:
        for _ in range(2):
            state, _ = tr.step(state, next(feed))
        jax.block_until_ready(state)
        with annotate("inside.marker", n=7):
            time.sleep(0.002)
    finally:
        jax.profiler.stop_trace()
    with annotate("outside.after"):
        pass
    feed.close()
    return _host_spans(trace_dir)


def test_session_holds_one_step_span_per_step_numbered_on_the_host(session):
    steps = session["trainer.step"]
    assert len(steps) == 2
    # the state's own counter was never read: step() numbers its calls
    assert [int(s[2]["step_num"]) for s in steps] == [2, 3]
    assert all(int(s[2]["_r"]) == 1 for s in steps)     # a step marker


@pytest.mark.parametrize("child", ["trainer.shard_batch", "trainer.enqueue"])
def test_step_span_contains_its_children(session, child):
    steps, kids = session["trainer.step"], session[child]
    assert len(kids) == 2
    for (s0, s1, _), (k0, k1, _) in zip(steps, kids):
        assert s0 <= k0 and k1 <= s1 and k1 - k0 < s1 - s0


@pytest.mark.parametrize("name", ["prefetch.build", "prefetch.transfer",
                                  "prefetch.take", "inside.marker"])
def test_span_lies_inside_the_sessions_window(session, name):
    lo = min(s for spans in session.values() for s, _, _ in spans)
    hi = max(e for spans in session.values() for _, e, _ in spans)
    assert session[name]
    assert all(lo <= s <= e <= hi for s, e, _ in session[name])


def test_take_carries_the_queue_depth_it_found(session):
    assert all(0 <= int(stats["depth"]) <= 2
               for _, _, stats in session["prefetch.take"])
    assert int(session["inside.marker"][0][2]["n"]) == 7


def test_annotate_outside_a_session_leaves_nothing(session):
    assert "outside.before" not in session and "outside.after" not in session


def test_span_recorder_spans_show_in_the_profile_too(tmp_path):
    """``fit``'s init / restore / checkpoint / eval spans go through the
    recorder: each opens an annotation of the same name, whether or not the
    JSONL log is enabled (a profile window is armed independently of it)."""
    from finetune_controller_tpu.obs import SpanRecorder

    rec = SpanRecorder(str(tmp_path / "artifacts"), "", enabled=False)
    jax.profiler.start_trace(str(tmp_path / "profile"))
    try:
        with rec.span("checkpoint", step=4):
            time.sleep(0.001)
        restore = rec.start("restore")
        rec.finish(restore)
    finally:
        jax.profiler.stop_trace()
    spans = _host_spans(tmp_path / "profile")
    assert len(spans["checkpoint"]) == 1 and len(spans["restore"]) == 1
    assert not (tmp_path / "artifacts").exists()      # disabled: no JSONL
    assert rec._open == {}


def test_finish_and_record_share_one_write_path(tmp_path):
    from finetune_controller_tpu.obs import SpanRecorder, parse_span_lines

    rec = SpanRecorder(str(tmp_path), "t" * 32)
    with rec.span("a"):
        pass
    rec.record("b", start_ns=1, end_ns=2)
    lines = parse_span_lines(open(rec.path).read())
    assert [s["name"] for s in lines] == ["a", "b"]
    rec.dir = str(tmp_path / "file")          # a file where the dir should be
    rec.path = str(tmp_path / "file" / "x.jsonl")
    (tmp_path / "file").write_text("")
    with rec.span("c"):
        pass
    rec.record("d", start_ns=1, end_ns=2)
    assert rec.write_failures == 2


# ---- who imports JAX ---------------------------------------------------------------

@pytest.mark.parametrize("module", [
    "finetune_controller_tpu.obs",
    "finetune_controller_tpu.obs.trace",
    "finetune_controller_tpu.data.prefetch",
    "finetune_controller_tpu.controller.server",
])
def test_import_pulls_in_no_jax(module):
    code = (f"import sys, {module}; "
            "bad = [m for m in sys.modules if m == 'jax' or m.startswith('jax.')]; "
            "sys.exit(1 if bad else 0)")
    r = subprocess.run([sys.executable, "-c", code], capture_output=True, text=True)
    assert r.returncode == 0, r.stderr[-2000:]


def test_annotate_without_jax_is_a_null_context():
    code = (
        "import sys\n"
        "sys.modules['jax'] = None; sys.modules['jax.profiler'] = None\n"
        "from finetune_controller_tpu.obs import annotate, SpanRecorder\n"
        "with annotate('x', depth=1) as a:\n"
        "    assert a is None\n"
        "rec = SpanRecorder('/nonexistent', '', enabled=False)\n"
        "with rec.span('checkpoint'):\n"
        "    pass\n"
    )
    r = subprocess.run([sys.executable, "-c", code], capture_output=True, text=True)
    assert r.returncode == 0, r.stderr[-2000:]
