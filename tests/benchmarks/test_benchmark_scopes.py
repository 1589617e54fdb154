"""The readers of the program's own names (ISSUE 24): ``xspace`` (the wire
format), ``scopes`` (name stacks, passes, host spans) and the three reducers,
on a small recorded ``XSpace`` (``fixtures/trace_scopes.txt``) against values
worked by hand.

Chip 0, ms after the trace's origin; the train step's program runs 0-100, a
decode program 120-140.  Leaf operations: q_proj base_matmul (forward) 0-20;
a fusion built around up_proj's dequant_int4 under remat 20-30; down_proj
lora_delta (backward) 30-55; a ``while`` 0-60 spanning them; loss (forward)
60-65; lm_head (backward) 65-70; optimizer 70-72; a copy with no name stack
72-75; a multiply in the step's own frame 75-76; flash_bwd_dq 76-100; and,
inside the DECODE program, the same q_proj fusion 120-140.  Window 140 ms.
Host: ``trainer.enqueue`` 2-4, 50-54 and (outside the window) 200-209;
``prefetch.build`` 10-20 and 130-150 (cut at 140), ``prefetch.transfer``
20-24, on a second line of the same name; ``prefetch.take`` with depth 2.
"""

import re
import sys
import types
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from benchmarks.harness import counts, scope_counts, scopes as S, trace as T, xspace  # noqa: E402
from benchmarks.harness.manifest import Manifest  # noqa: E402

FIXTURE = Path(__file__).parent / "fixtures" / "trace_scopes.txt"
REAL = Manifest()
CELL = "mistral-7b-qlora.train-sft-2k"
CELLS = [CELL, "mistral-7b-qlora.train-sft-8k"]
CUT = Manifest(ROOT / "tests/benchmarks/fixtures/BENCHMARK.cut.json")
TINY_CONF = ROOT / "tests/benchmarks/fixtures/configs/tiny-qlora.json"
NEW = ["step.forward_share_pct", "step.recompute_share_pct",
       "step.backward_share_pct", "step.optimizer_share_pct",
       "step.unscoped_share_pct", "proj.time_share_pct", "proj.matmul_roofline",
       "dequant.time_share_pct", "head_loss.time_share_pct",
       "trainer.enqueue_ms", "prefetch.producer_busy_pct"]
W = 140.0   # ms


def make_run(text: str, with_device: bool = True):
    from jax.profiler import ProfileData

    import json

    tr = T.from_profile_data(ProfileData.from_text_proto(text))
    run = types.SimpleNamespace(
        trace=tr if with_device else None,
        traced=T.window(tr) if with_device else (0.0, 0.0),
        conf=json.loads(TINY_CONF.read_text()),
        notes={"traced_steps": 2, "batch": 4, "seq": 32},
        peaks=counts.peaks_for("TPU v5 lite"), scratch=None)
    run._xspace_planes = xspace.from_text_proto(text)
    return run


@pytest.fixture()
def run():
    return make_run(FIXTURE.read_text())


def reduce(run, metric: str):
    spec = REAL.layer_metric(metric)
    return REAL.reducer(spec["reducer"])(run, **spec.get("args", {}))


# ---- the manifest ----------------------------------------------------------

@pytest.mark.parametrize("metric", NEW)
def test_manifest_registers_and_loads_every_new_metric(metric):
    entry, spec = REAL.per_layer[metric], REAL.layer_metric(metric)
    assert entry["workloads"] == CELLS
    assert entry["moves"] == "train_tokens_per_s_chip"
    assert all(metric in REAL.cell_per_layer(cell) for cell in CELLS)
    assert callable(REAL.reducer(spec["reducer"]))
    assert spec["source"] == entry["source"]
    assert REAL.problems() == []


def test_the_accepted_entries_stand_first_and_unchanged():
    names = [m["name"] for m in REAL.raw["per_layer"]]
    assert names[:5] == ["input.wait_share_pct", "trainer.dispatch_ms",
                         "trainer.mfu_pct", "flash.time_share_pct",
                         "flash_attention_roofline"]
    assert names[5:] == NEW


# ---- the wire format ---------------------------------------------------------

def test_xspace_reads_metadata_stats_event_stats_and_joins_lines(run):
    planes = {p.name: p for p in run._xspace_planes}
    ops = planes["/device:TPU:0"].lines["XLA Ops"]
    assert len(ops) == 11
    q = ops[1]
    assert q.name.startswith("%fusion.847 = bf16[8,2048,4096]")
    assert q.stats["tf_op"].endswith("q_proj/base_matmul/dot_general:")
    assert q.stats["flops"] == 1234
    assert (q.start, q.end) == pytest.approx((0.001, 0.021))
    assert "tf_op" not in ops[7].stats           # the copy: no name stack
    host = planes["/host:CPU"].lines["python3"]  # two lines of one name
    assert len(host) == 9
    take = next(e for e in host if e.name == "prefetch.take")
    assert take.stats == {"depth": 2}


def test_xspace_clock_is_the_one_the_harness_reports(run):
    lo, hi = run.traced
    ops = [e for p in run._xspace_planes if p.name == "/device:TPU:0"
           for e in p.lines["XLA Ops"] if not T.is_container(e.name)]
    assert min(e.start for e in ops) == pytest.approx(lo)
    assert max(e.end for e in ops) == pytest.approx(hi)
    assert hi - lo == pytest.approx(W * 1e-3)


# ---- name stacks ---------------------------------------------------------------

M = "LlamaForCausalLM"
LOOP = "while/body/closed_call"


@pytest.mark.parametrize("tf_op,names,which", [
    (f"jit(_train_step)/jvp({M})/{LOOP}/blocks/block/attn/q_proj/base_matmul/dot_general:",
     {"blocks", "attn", "q_proj", "base_matmul"}, "forward"),
    (f"jit(_train_step)/transpose(jvp({M}))/{LOOP}/checkpoint/rematted_computation/blocks/block/mlp/up_proj/dequant_int4/jit(_where)/select_n:",
     {"up_proj", "dequant_int4", "rematted_computation", "_where"}, "recompute"),
    (f"jit(_train_step)/transpose(jvp({M}))/{LOOP}/checkpoint/blocks/block/attn/flash_bwd_dkv/pallas_call:",
     {"attn", "flash_bwd_dkv"}, "backward"),
    (f"jit(_train_step)/transpose(jvp({M}))/lm_head/base_matmul/dot_general:",
     {"lm_head", "base_matmul"}, "backward"),
    ("jit(_train_step)/jvp(loss)/jit(log_softmax)/reduce_max:", {"loss"}, "forward"),
    ("jit(_train_step)/transpose(jvp(loss))/jit(take_along_axis)/mul:", {"loss"}, "backward"),
    ("jit(_train_step)/optimizer/jit(clip)/mul:", {"optimizer", "clip"}, "optimizer"),
    (f"jit(_train_step)/grad_accum/{LOOP}/jvp({M})/{LOOP}/blocks/block/attn/rope/mul:",
     {"grad_accum", "rope"}, "forward"),
    ("checkpoint/blocks/block/attn/reduce_sum", {"blocks"}, "backward"),
    ("jit(_train_step)/blocks/block/attn/rope/cos:", {"rope"}, None),
    ("jit(_train_step)/mul:", set(), None),
    ("", set(), None),
])
def test_name_stack_gives_scopes_and_pass(tf_op, names, which):
    got, transforms = S.stack(tf_op)
    assert names <= got
    assert "_train_step" not in got and "dot_general" not in got
    assert S.step_pass(got, transforms) == which


# ---- the reducers on the fixture ---------------------------------------------

@pytest.mark.parametrize("metric,want", [
    ("step.forward_share_pct", 100 * (20 + 5) / W),
    ("step.recompute_share_pct", 100 * 10 / W),
    ("step.backward_share_pct", 100 * (25 + 5 + 24) / W),
    ("step.optimizer_share_pct", 100 * 2 / W),
    ("step.unscoped_share_pct", 100 * (3 + 1) / W),
    # the decode program's q_proj fusion (20 ms) is not the step's
    ("proj.time_share_pct", 100 * (20 + 10 + 25) / W),
    # the fusion's own metadata names dequant_int4: all of it is charged there
    ("dequant.time_share_pct", 100 * 10 / W),
    ("head_loss.time_share_pct", 100 * (5 + 5) / W),
    ("trainer.enqueue_ms", 3.0),
    ("prefetch.producer_busy_pct", 100 * (10 + 4 + 10) / W),
])
def test_metric_on_the_fixture(run, metric, want):
    assert reduce(run, metric) == pytest.approx(want)


def test_pass_shares_and_unscoped_sum_to_the_steps_leaf_time(run):
    total = sum(reduce(run, f"step.{p}_share_pct")
                for p in ("forward", "recompute", "backward", "optimizer", "unscoped"))
    assert total == pytest.approx(100 * 95 / W)      # 5 ms idle inside the step
    assert S.seconds(S.step_ops(run)) == pytest.approx(0.095)


def test_roofline_counts_the_need_not_the_recompute(run, capsys):
    conf = run.conf
    per_token = (4 * conf["num_hidden_layers"] * counts.layer_matmul_params(conf)
                 + 6 * counts.lora_params(conf))
    assert scope_counts.proj_matmul_flops_per_token(conf) == per_token
    need = per_token * 2 * 4 * 32 / 197e12
    assert reduce(run, "proj.matmul_roofline") == pytest.approx(100 * need / 0.055)
    assert "needs" in capsys.readouterr().out


# ---- counts found by name (ISSUE 26): another architecture's by a new file -----

def counted_run():
    """The fixture trace as a run of the tests' cut configuration, whose
    counts are the module ``counting/tiny_counts.py`` under ITS paths."""
    run = make_run(FIXTURE.read_text())
    run.conf, run.manifest = CUT.config("tiny-cut"), CUT
    run.end_to_end = {"train_tokens_per_s_chip": 1e6}
    return run


def test_mfu_takes_its_count_from_the_named_module():
    run = counted_run()
    mfu = REAL.reducer("mfu")
    got = mfu(run, count="train_flops_per_token", rate="train_tokens_per_s_chip",
              counts="tiny_counts")
    assert got == pytest.approx(100 * (1000 * 64 + 32) * 1e6 / 197e12)
    spec = CUT.layer_metric("tiny.mfu_pct")
    assert CUT.reducer(spec["reducer"])(run, **spec["args"]) == pytest.approx(got)
    # left out, it reads what it read before: harness/counts.py
    run.conf = make_run(FIXTURE.read_text()).conf
    want = counts.lora_train_flops_per_token(run.conf, 32)
    assert mfu(run, count="lora_train_flops_per_token",
               rate="train_tokens_per_s_chip") == pytest.approx(
        100 * want * 1e6 / 197e12)


def test_scope_roofline_takes_its_count_from_the_named_module(capsys):
    run = counted_run()
    got = REAL.reducer("scope_roofline")(
        run, scopes=["q_proj", "up_proj", "down_proj"],
        count="proj_flops_per_token", counts="tiny_counts")
    need = 500 * 64 * 2 * 4 * 32 / 197e12
    assert got == pytest.approx(100 * need / 0.055)
    with pytest.raises(AttributeError):     # not a function of scope_counts.py
        REAL.reducer("scope_roofline")(run, scopes=["q_proj"],
                                       count="proj_flops_per_token")


def test_flash_roofline_takes_flops_and_bytes_from_the_named_module(capsys):
    # q/k heads of 24 and v heads of 16: the one dQ call (24 ms) of 4
    # sequences of 32 needs 2 score products and 1 value product
    run = counted_run()
    kernels = [k for k in REAL.layer_metric("flash_attention_roofline")["args"]["kernels"]]
    got = REAL.reducer("flash_roofline")(run, kernels=kernels, counts="tiny_counts")
    flops = (2 * 24 + 16) * 32 * 32 * 4 * 4
    nbytes = 2.0 * 4 * 32 * 4 * (2 * 24 + 2 * 16)
    need = max(flops / 197e12, nbytes / 819e9)
    assert got == pytest.approx(100 * need / 0.024)
    assert "memory" in capsys.readouterr().out      # so small it is bytes-bound
    # left out: harness/counts.py, one head size for q, k and v
    run.conf = make_run(FIXTURE.read_text()).conf
    want = max(counts.flash_call_flops(run.conf, 4, 32, "bwd_dq") / 197e12,
               counts.flash_call_bytes(run.conf, 4, 32, "bwd_dq") / 819e9)
    assert REAL.reducer("flash_roofline")(run, kernels=kernels) == pytest.approx(
        100 * want / 0.024)


def test_a_share_over_105_percent_prints_no_result():
    # the backward lora_delta fusion stretched over the whole window: the
    # step's operations then sum to (140 + 70) / 140
    text = FIXTURE.read_text().replace(
        "metadata_id: 4 offset_ps: 30000000000 duration_ps: 25000000000",
        "metadata_id: 4 offset_ps: 0 duration_ps: 140000000000")
    run = make_run(text)
    run.traced = (0.001, 0.141)
    share = REAL.reducer("scope_time_share")
    with pytest.raises(SystemExit, match="counted twice"):
        share(run)
    assert share(run, step_pass="forward") == pytest.approx(100 * 25 / W)
    busy = REAL.reducer("program_span_stat")
    text = FIXTURE.read_text().replace(
        "metadata_id: 7 offset_ps: 20000000000 duration_ps: 4000000000",
        "metadata_id: 7 offset_ps: 0 duration_ps: 140000000000")
    with pytest.raises(SystemExit, match="counted twice"):
        busy(make_run(text), spans=["prefetch.build", "prefetch.transfer"],
             stat="share")


def test_a_program_without_the_names_reads_nothing_and_does_not_raise():
    """The parent commit's trace: module names and transformations, none of
    this PR's scopes or spans; and a trace with no name stack at all."""
    text = FIXTURE.read_text()
    parent = re.sub(r"/(dequant_int4|base_matmul|lora_delta|optimizer)/", "/", text)
    parent = parent.replace("jvp(loss)", "jvp()").replace("trainer.enqueue", "x.y") \
        .replace("prefetch.build", "x.b").replace("prefetch.transfer", "x.t")
    run = make_run(parent)
    assert reduce(run, "dequant.time_share_pct") is None
    assert reduce(run, "step.optimizer_share_pct") is None
    assert reduce(run, "trainer.enqueue_ms") is None
    assert reduce(run, "prefetch.producer_busy_pct") is None
    assert reduce(run, "step.unscoped_share_pct") == pytest.approx(100 * (3 + 1 + 2) / W)
    assert reduce(run, "proj.time_share_pct") == pytest.approx(100 * 55 / W)
    bare = make_run(re.sub(r' stats \{ metadata_id: 1 str_value: "[^"]*" \}', "", text))
    assert reduce(bare, "step.forward_share_pct") is None
    assert reduce(bare, "step.unscoped_share_pct") == pytest.approx(100 * 95 / W)
    assert reduce(bare, "proj.matmul_roofline") is None


def test_without_a_device_trace_only_the_host_spans_read():
    run = make_run(FIXTURE.read_text(), with_device=False)
    for metric in NEW[:9]:
        assert reduce(run, metric) is None, metric
    # no device operation, no window: every span counts, a share has no base
    assert reduce(run, "trainer.enqueue_ms") == pytest.approx(4.0)
    assert reduce(run, "prefetch.producer_busy_pct") is None


def test_no_trace_file_reads_nothing(tmp_path):
    run = types.SimpleNamespace(trace=None, traced=(0.0, 0.0), scratch=tmp_path)
    assert xspace.run_planes(run) is None
    assert S.host_spans(run, ["trainer.enqueue"]) == []
    assert reduce(run, "trainer.enqueue_ms") is None


# ---- end to end off the chip ---------------------------------------------------

def test_traced_tiny_run_reads_the_programs_spans_and_leaves_device_metrics_out(capsys):
    """The CPU backend writes no device plane: the span of ``Trainer.step``'s
    enqueue is read from the host planes, the device's shares are left out of
    the line, nothing raises."""
    import json

    from benchmarks import run as runner

    manifest = ROOT / "tests/benchmarks/fixtures/BENCHMARK.scopes.json"
    assert Manifest(manifest).problems() == []
    runner.main(["--workload", "tiny-qlora.train-tiny", "--seed", str(2**31 + 24),
                 "--seconds", "0.5", "--trace", "1"],
                manifest_path=manifest, allow_cpu=True)
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert line["correct"] is True
    assert set(line["metrics"]) == {"trainer.enqueue_ms"}
    assert 0 < line["metrics"]["trainer.enqueue_ms"]["value"] < 5000
