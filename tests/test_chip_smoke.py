"""chip_smoke.py off the chip: control flow, exit codes, and the last line.

The full run needs a TPU (and is what the driver runs on one).  Here: the
``--tiny`` rehearsal drives the same phases on the CPU once, a failed phase
ends the run non-zero with no result line, the result line has exactly the
shape the contract names and is never printed for anything but TPU children,
and the things the smoke leans on hold without a chip — a TPU flavor pins its
trainer to the TPU (which then fails here), and the API server starts no JAX
backend of its own.
"""

from __future__ import annotations

import asyncio
import importlib.util
import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parents[1]
SMOKE = REPO / "chip_smoke.py"

PHASES = ["grouped-parity", "ssd-parity", "window-parity", "latent-share-parity", "lora-parity", "server", "submit", "train", "promote",
          "serve-load", "serve-generate", "shutdown", "paged-parity",
          "compile-cache", "total"]


def _env(**overrides) -> dict:
    env = {k: v for k, v in os.environ.items()
           if k not in ("XLA_FLAGS", "FTC_DEVICE_CONFIG_FILE")}
    env["JAX_PLATFORMS"] = "cpu"
    env.update(overrides)
    return env


def _run(args, env, cwd=None, timeout=600):
    return subprocess.run(
        [sys.executable, str(SMOKE), *args], env=env, cwd=cwd or str(REPO),
        capture_output=True, text=True, timeout=timeout,
    )


def _result_lines(stdout: str) -> list[dict]:
    """Every stdout line that parses as a JSON object."""
    out = []
    for line in stdout.splitlines():
        if line.startswith("{"):
            out.append(json.loads(line))
    return out


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke_mod", SMOKE)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture
def off_jax(monkeypatch):
    """main() refuses to pass in a process that imported jax; pytest's has.
    Hide the module for the duration of an in-process main() call."""
    monkeypatch.delitem(sys.modules, "jax")


# ---------------------------------------------------------------------------
# subprocess runs
# ---------------------------------------------------------------------------


def test_tiny_rehearsal_runs_every_phase_and_is_not_a_chip_pass():
    out = _run(["--tiny"], _env())
    assert out.returncode == 0, out.stderr[-3000:]
    lines = out.stdout.strip().splitlines()
    phases = [l.split(":")[0][len("phase "):] for l in lines
              if l.startswith("phase ")]
    assert phases == PHASES
    detail = {l.split(":")[0][len("phase "):]: json.loads(l[l.index("{"):])
              for l in lines if l.startswith("phase ")}
    losses = detail["train"]["losses"]
    assert len(losses) == 24 and losses[-1] < losses[0]
    assert detail["train"]["device"]["platform"] == "cpu"
    assert detail["train"]["attention_impl"] == "xla"
    assert detail["serve-load"]["paged_attention"]["decode"] == "gather"
    assert detail["serve-load"]["warm_start_s"] > 0
    gen = detail["serve-generate"]
    assert gen["requests"] == 24 and gen["tokens_generated"] == 24 * 16
    assert gen["identical_alone_and_together"] is True
    assert gen["prefix_hits"] == 16  # waves two and three found their prompts
    # on the CPU a prefix hit changes nothing, bit for bit
    assert gen["prompts_moved_by_prefix_reuse"] == []
    assert detail["shutdown"]["survivors"] == []
    assert detail["paged-parity"]["worst_max_err"] <= \
        detail["paged-parity"]["tolerance"]
    # the compiler's own grouped product against itself here: every kind of
    # group sizes ran, and nothing was compiled for a chip
    grouped = detail["grouped-parity"]
    assert grouped["compiled"] is False
    assert grouped["worst_err"] <= grouped["tolerance"]
    assert {key.split(":")[1] for key in grouped["work_over_need"]} == {
        "even", "skewed", "empty_groups", "rows_no_group_covers"}
    # the chunked scan against the token-by-token recurrence, float32 here:
    # five and a half chunks, value and gradients
    ssd = detail["ssd-parity"]
    assert ssd["compiled"] is False
    assert ssd["worst_err"] <= ssd["tolerance"]
    assert ssd["errs_by_shape"]["44x4x8x2x6x8"]["chunks"] == 6
    # off the chip the chooser keeps the plain form, and says so
    assert ssd["ssm_scan_impl"] == "xla"
    assert ssd["errs_by_shape"]["44x4x8x2x6x8"]["heads_per_block"] == 0
    # the flash kernels under a window and beside a sink, interpreted here,
    # against the XLA form: values and the gradients of q, k, v and the sink
    window = detail["window-parity"]
    assert window["compiled"] is False
    assert window["worst_err"] <= window["tolerance"]
    assert set(window["errs_by_shape"]) == {"40x4x2x24x16x5x1", "40x4x1x24x16x0x0"}
    assert set(window["errs_by_shape"]["40x4x2x24x16x5x1"]) == {
        "value", "dq", "dk", "dv", "dsink"}
    assert set(window["errs_by_shape"]["40x4x1x24x16x0x0"]) == {
        "value", "dq", "dk", "dv"}
    assert list(window["flash_window_work_over_need"]) == ["40x4x2x24x16x5x1"]
    # a held share of a latent expert layer (its grouped path, two products
    # an expert) against the masked plain form: value and the input's
    # gradient, and the same pairs on both sides
    latent = detail["latent-share-parity"]
    assert latent["compiled"] is False
    assert latent["worst_err"] <= latent["tolerance"]
    (share,) = latent["errs_by_shape"].values()
    assert set(latent["errs_by_shape"]) == {"64x32x16x24x16x4x4"}
    assert 0 < share["pairs"] <= share["row_bound"] == 64 * 4
    # the joined LoRA product against the layer's old expression, bf16 over
    # an int4 base: value and gradients
    joined = detail["lora-parity"]
    assert joined["compiled"] is False
    assert joined["worst_err"] <= joined["tolerance"]
    assert set(joined["errs_by_shape"]) == {"48x64x96x4"}
    # the last line is a rehearsal record: no "ok" anywhere on stdout
    last = json.loads(lines[-1])
    assert last == {"rehearsal": "tiny", "passed": True,
                    "device": {"platform": "cpu", "kind": "cpu", "count": 1}}
    assert all("ok" not in rec for rec in _result_lines(out.stdout))


def test_a_failed_phase_exits_nonzero_and_prints_no_result(tmp_path):
    """Injected through the environment an operator controls: a device
    catalog without the flavor the smoke submits on makes the submit phase
    fail (HTTP 400).  The run ends there."""
    catalog = tmp_path / "catalog.json"
    catalog.write_text(json.dumps({
        "flavors": [{"name": "elsewhere", "generation": "cpu", "hosts": 1,
                     "chips_per_host": 1, "runtime": "cpu"}],
        "quotas": [], "default_flavor": "elsewhere",
    }))
    out = _run(["--tiny"], _env(FTC_DEVICE_CONFIG_FILE=str(catalog)))
    assert out.returncode == 1
    # a failed run leaves its work directory (server log, sandboxes) behind
    (kept,) = [l.split(": ", 1)[1] for l in out.stderr.splitlines()
               if l.startswith("work directory kept: ")]
    assert (Path(kept) / "server.log").exists()
    shutil.rmtree(kept)
    assert "FAILED: POST /jobs -> HTTP 400" in out.stderr
    assert "unknown device 'cpu-test'" in out.stderr
    phases = [l for l in out.stdout.splitlines() if l.startswith("phase ")]
    assert [p.split(":")[0] for p in phases] == [
        "phase grouped-parity", "phase ssd-parity", "phase window-parity",
        "phase latent-share-parity", "phase lora-parity", "phase server"]
    assert _result_lines(out.stdout) == []
    # and nothing it started is left behind
    leftovers = subprocess.run(
        ["pgrep", "-f", "finetune_controller_tpu.controller.server"],
        capture_output=True, text=True).stdout.split()
    mine = [p for p in leftovers
            if str(tmp_path) in Path(f"/proc/{p}/environ").read_text(
                errors="replace")]
    assert mine == []


def test_the_script_alone_fails_without_a_result(tmp_path):
    """In a directory that holds chip_smoke.py and nothing else of the repo."""
    shutil.copy(SMOKE, tmp_path / "chip_smoke.py")
    out = subprocess.run(
        [sys.executable, "chip_smoke.py"], cwd=str(tmp_path), env=_env(),
        capture_output=True, text=True, timeout=60,
    )
    assert out.returncode != 0
    assert out.stdout.strip() == ""
    assert "no finetune_controller_tpu package" in out.stderr


@pytest.mark.slow
def test_full_mode_fails_in_a_sandbox_without_a_chip():
    """What the driver checks first: no accelerator -> non-zero, no result.
    The v5e-1 flavor pins the trainer to the TPU, which is not there."""
    out = _run([], _env())
    assert out.returncode == 1
    assert "Unable to initialize backend 'tpu'" in out.stderr
    for line in out.stderr.splitlines():
        if line.startswith("work directory kept: "):
            shutil.rmtree(line.split(": ", 1)[1])
    assert _result_lines(out.stdout) == []


# ---------------------------------------------------------------------------
# the last line (in-process: the parent is stdlib only)
# ---------------------------------------------------------------------------

V5E = {"platform": "tpu", "kind": "TPU v5 lite", "count": 1}


def test_last_line_after_a_pass_is_exactly_the_contract(
        smoke, monkeypatch, capsys, off_jax):
    monkeypatch.setattr(smoke, "run_lifecycle", lambda *a, **k: dict(V5E))
    assert smoke.main([]) == 0
    last = capsys.readouterr().out.strip().splitlines()[-1]
    assert last == ('{"ok": true, "device": {"platform": "tpu", '
                    '"kind": "TPU v5 lite", "count": 1}}')


@pytest.mark.parametrize("device", [
    {"platform": "cpu", "kind": "cpu", "count": 1},
    {"platform": "gpu", "kind": "A100", "count": 1},
])
def test_no_result_when_the_children_ran_anywhere_but_a_tpu(
        smoke, monkeypatch, capsys, device, off_jax):
    monkeypatch.setattr(smoke, "run_lifecycle", lambda *a, **k: dict(device))
    with pytest.raises(smoke.SmokeFailure, match="expected 1 TPU chip"):
        smoke.main([])
    assert _result_lines(capsys.readouterr().out) == []


def test_four_chip_option_runs_only_its_path_and_reports_count_4(
        smoke, monkeypatch, capsys, off_jax):
    def never(*a, **k):
        raise AssertionError("--chips 4 ran the one-chip lifecycle")

    monkeypatch.setattr(smoke, "run_lifecycle", never)
    monkeypatch.setattr(smoke, "run_four_chips",
                        lambda *a, **k: {**V5E, "count": 4})
    assert smoke.main(["--chips", "4"]) == 0
    last = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert last == {"ok": True, "device": {**V5E, "count": 4}}
    # one chip seen where four were asked for is not a pass
    monkeypatch.setattr(smoke, "run_four_chips", lambda *a, **k: dict(V5E))
    with pytest.raises(smoke.SmokeFailure, match="expected 4 TPU chip"):
        smoke.main(["--chips", "4"])
    assert _result_lines(capsys.readouterr().out) == []


def test_tiny_mode_never_prints_ok_even_for_a_tpu_device(
        smoke, monkeypatch, capsys, off_jax):
    monkeypatch.setattr(smoke, "run_lifecycle", lambda *a, **k: dict(V5E))
    assert smoke.main(["--tiny"]) == 0
    assert all("ok" not in rec
               for rec in _result_lines(capsys.readouterr().out))


def test_full_mode_runs_the_published_tinyllama_config(smoke):
    """Full width AND depth of tinyllama-1.1b at the shape where ``auto``
    takes the Pallas flash kernels, bf16 frozen base, rank 8, one checkpoint
    at the last of 24 steps."""
    from finetune_controller_tpu.controller.devices import default_catalog
    from finetune_controller_tpu.controller.examples import BUILTIN_JOB_SPECS
    from finetune_controller_tpu.models.llama import PRESETS
    from finetune_controller_tpu.ops.attention import resolve_attention_impl

    cfg = smoke.mode_config(tiny=False, seed=0)
    (spec_cls,) = [c for c in BUILTIN_JOB_SPECS
                   if c.model_name == cfg["model_name"]]
    assert spec_cls.model_preset == cfg["preset"] == "tinyllama-1.1b"
    model = PRESETS[cfg["preset"]]
    assert (model.n_layers, model.d_model, model.n_heads, model.n_kv_heads,
            model.vocab_size) == (22, 2048, 32, 4, cfg["vocab"])
    args = spec_cls(training_arguments=cfg["arguments"]).training_arguments
    assert (args.batch_size, args.seq_len, args.lora_rank,
            args.frozen_dtype) == (8, 2048, 8, "bfloat16")
    assert args.checkpoint_every == args.total_steps == 24
    assert resolve_attention_impl(
        model.attention_impl, args.seq_len, backend="tpu"
    ) == cfg["attention_impl"] == "pallas"
    flavor = default_catalog().get(cfg["device"])
    assert flavor.runtime == "tpu" and flavor.total_chips == 1
    assert default_catalog().quota_for(cfg["device"]) == 1


def test_full_mode_checks_the_grouped_product_at_both_expert_cells_shapes(smoke):
    """(rows, groups, k, n, layers of the stack): the JoyAI cell's 65,536
    pairs over 256 experts of 2048 x 768 and the 16k cell's pass of 16,384
    rows over 16 held experts of 6144 x 2048, up and down — the activation
    gradient of one has the other's shapes — and a decode step's 256 rows."""
    from finetune_controller_tpu.models import moe

    shapes = smoke.mode_config(tiny=False, seed=0)["grouped_shapes"]
    assert [65536, 256, 2048, 768, 4] in shapes and [65536, 256, 768, 2048, 4] in shapes
    assert [16384, 16, 6144, 2048, 2] in shapes and [16384, 16, 2048, 6144, 2] in shapes
    assert {moe.gmm_row_tile(m, g) for m, g, *_ in shapes} == {512, 256, 128}
    assert all(m % 128 == 0 for m, *_ in shapes)     # the Pallas kernel's rows


def test_full_mode_checks_the_chunked_scan_at_both_mixer_cells_widths(smoke):
    """(rows, heads, head size, groups, state size, chunk): one block's scan of
    the hybrid configuration as published — 32 heads of 128 over 128 x 256
    states, B and C in 2 groups, chunks of 128 — and of the pattern one — 128
    heads of 64 over 64 x 128 states, 8 groups — on 1,024 rows, eight chunks,
    within a tolerance that bf16 products allow and a dropped carry does not;
    the snippet runs what the MIXER runs (the chooser's function: the kernels
    on the chip) and says which form that was."""
    import json as _json

    published = {   # heads, head size, groups, state size, chunk: each file's keys
        "falcon-h1-34b-lora": ("mamba_n_heads", "mamba_d_head", "mamba_n_groups",
                               "mamba_d_state", "mamba_chunk_size"),
        "nemotron-3-super-lora": ("mamba_num_heads", "mamba_head_dim", "n_groups",
                                  "ssm_state_size", "chunk_size")}
    shapes = smoke.mode_config(tiny=False, seed=0)["ssd_shapes"]
    for shape, (name, keys) in zip(shapes, published.items(), strict=True):
        conf = _json.loads((REPO / f"benchmarks/configs/{name}.json").read_text())
        assert shape == [1024, *(conf[k] for k in keys)]
        assert shape[0] // shape[-1] == 8
    assert smoke.SSD_TOL == 2 ** -6
    snippet = smoke.SSD_PARITY_SNIPPET
    assert "recurrence" in snippet and "ssd_scan(x, dt, a, b, c, d" in snippet
    assert "ssd_chunked" not in snippet and "ssm_scan_impl" in snippet


def test_full_mode_checks_both_attention_kinds_at_the_published_shapes(smoke):
    """(rows, query heads, key/value heads, q/k width, v width, window, sink?):
    the window/full configuration's two attention calls as published — one row
    of 16,384, 64 query heads of 192 beside v heads of 128, over 8 key/value
    heads under a window of 128 keys beside a sink, over 4 with every earlier
    key and none — the kernels against the XLA form a head at a time."""
    import json as _json

    conf = _json.loads(
        (REPO / "benchmarks/configs/mimo-v2-flash-lora.json").read_text())
    window, full = smoke.mode_config(tiny=False, seed=0)["window_shapes"]
    assert window == [16384, conf["num_attention_heads"],
                      conf["swa_num_key_value_heads"], conf["swa_head_dim"],
                      conf["swa_v_head_dim"], conf["sliding_window"],
                      int(conf["add_swa_attention_sink_bias"])]
    assert full == [16384, conf["num_attention_heads"],
                    conf["num_key_value_heads"], conf["head_dim"],
                    conf["v_head_dim"], 0,
                    int(conf["add_full_attention_sink_bias"])]
    assert smoke.WINDOW_TOL == 2 ** -5
    snippet = smoke.WINDOW_PARITY_SNIPPET
    assert "flash_attention(q, k, v, window=window" in snippet
    assert "xla_causal_attention" in snippet and "window_work_over_need" in snippet
    assert '"dsink"' in snippet and "jax.checkpoint" in snippet


def test_full_mode_checks_a_held_share_at_the_pattern_cells_widths(smoke):
    """(rows, d_model, latent, expert width, experts, held, per token): one
    expert layer of the pattern configuration as published — top-22 of 512
    experts of 1024 x 2688 in a 1024-wide latent of a 4096-wide state, the
    cell's 128 held — on 1,024 rows, the share's grouped path against the
    masked plain form."""
    import json as _json

    conf = _json.loads(
        (REPO / "benchmarks/configs/nemotron-3-super-lora.json").read_text())
    (shape,) = smoke.mode_config(tiny=False, seed=0)["latent_shapes"]
    assert shape == [1024, conf["hidden_size"], conf["moe_latent_size"],
                     conf["moe_intermediate_size"],
                     conf["published"]["n_routed_experts"],
                     conf["n_routed_experts"], conf["num_experts_per_tok"]]
    assert smoke.LATENT_TOL == 2 ** -5
    for word in ("held_row_bound", "gated=False", "fc1_latent_proj",
                 "fc2_latent_proj", "one_hot"):
        assert word in smoke.LATENT_SHARE_PARITY_SNIPPET, word


def test_full_mode_checks_the_joined_product_at_a_mistral_projections_width(smoke):
    """(rows, in, out, rank, quantisation block, scale): the gate / up
    projection of the Mistral configuration as published over its int4 base,
    2,048 rows at the cell's rank — the joined form, value and gradients, against
    the expression the layer had, within two bf16 ulps of the largest value."""
    import json as _json

    conf = _json.loads((REPO / "benchmarks/configs/mistral-7b-qlora.json").read_text())
    (shape,) = smoke.mode_config(tiny=False, seed=0)["lora_shapes"]
    assert shape[:5] == [2048, conf["hidden_size"], conf["intermediate_size"],
                         conf["run"]["lora_rank"], conf["run"]["quant_block"]]
    assert shape[5] != 1.0 and smoke.LORA_TOL == 2 ** -6
    assert "joined_product" in smoke.LORA_PARITY_SNIPPET


# ---------------------------------------------------------------------------
# what the smoke leans on
# ---------------------------------------------------------------------------


def test_tpu_flavor_pins_its_trainer_to_the_tpu(tmp_path):
    """Only cpu flavors used to get a platform; a TPU flavor inherited the
    server's (scripts/serve_local.sh exports cpu) and trained on the CPU
    under a TPU flavor's name."""
    from finetune_controller_tpu.controller.backends.local import (
        LocalProcessBackend,
    )
    from finetune_controller_tpu.controller.devices import default_catalog
    from finetune_controller_tpu.controller.objectstore import LocalObjectStore

    catalog = default_catalog()
    backend = LocalProcessBackend(
        tmp_path / "sandbox", LocalObjectStore(tmp_path / "objects"), catalog,
        extra_env={"JAX_PLATFORMS": "cpu"},  # the operator's env says cpu
    )
    assert backend._runtime_env(catalog.get("v5e-1"), 1)["JAX_PLATFORMS"] == "tpu"
    assert backend._runtime_env(catalog.get("v5e-16"), 1)["JAX_PLATFORMS"] == "tpu"
    cpu_env = backend._runtime_env(catalog.get("cpu-test-2"), 1)
    assert cpu_env["JAX_PLATFORMS"] == "cpu"
    assert "--xla_force_host_platform_device_count=2" in cpu_env["XLA_FLAGS"]


def test_job_on_a_tpu_flavor_fails_when_its_trainer_finds_no_tpu(tmp_path):
    from conftest import tiny_job_spec

    from finetune_controller_tpu.controller.backends.local import (
        LocalProcessBackend,
    )
    from finetune_controller_tpu.controller.devices import default_catalog
    from finetune_controller_tpu.controller.objectstore import LocalObjectStore
    from finetune_controller_tpu.controller.schemas import (
        BackendJobState,
        JobInput,
    )

    async def main():
        catalog = default_catalog()
        backend = LocalProcessBackend(
            tmp_path / "sandbox", LocalObjectStore(tmp_path / "objects"),
            catalog, sync_interval_s=0.2, backoff_limit=0,
        )
        await backend.submit(
            JobInput(job_id="no-chip", user_id="u",
                     model_name="tiny-test-lora", device="v5e-1", arguments={}),
            tiny_job_spec(), catalog.get("v5e-1"), dataset_uri=None,
            artifacts_uri="obj://artifacts/u/no-chip",
        )
        for _ in range(300):
            report = await backend.get_job("no-chip")
            if report.state in (BackendJobState.FAILED,
                                BackendJobState.SUCCEEDED):
                break
            await asyncio.sleep(0.1)
        logs = [line async for line in await backend.read_logs("no-chip")]
        await backend.close()
        return report, "\n".join(logs)

    report, logs = asyncio.run(main())
    assert report.state is BackendJobState.FAILED, logs[-2000:]
    assert "Unable to initialize backend 'tpu'" in logs


_SERVER_PROBE = r"""
import asyncio, json, os, sys
from pathlib import Path
from aiohttp.test_utils import TestClient, TestServer
from finetune_controller_tpu.controller import server
from finetune_controller_tpu.controller.runtime import build_runtime
from finetune_controller_tpu.serve.loader import stage_meta

async def main():
    app = server.build_app(build_runtime())
    async with TestClient(TestServer(app)) as client:
        assert (await client.get("/api/v1/models")).status == 200
        assert (await client.get("/api/v1/admin/serve")).status == 200
        assert (await client.get("/metrics")).status == 200
        # what a process-transport load does in THIS process: read the staged
        # spec and list the checkpoint steps (the workers load the weights)
        staged = Path("staged")
        (staged / "checkpoints" / "step_3").mkdir(parents=True)
        (staged / "resolved_config.json").write_text(json.dumps({
            "model": {"preset": "tiny-test", "lora": {"rank": 2}},
            "training": {"mode": "lora"}}))
        assert stage_meta(staged)["checkpoint_step"] == 3
        return await (await client.get("/api/v1/health")).json()

health = asyncio.run(main())
initialised = False
if "jax" in sys.modules:
    from jax._src import xla_bridge
    initialised = xla_bridge.backends_are_initialized()
print(json.dumps({"health": health, "backend_initialised": initialised}))
"""


def test_starting_the_api_server_initialises_no_jax_backend(tmp_path):
    """The server must never hold the chip: its trainers and serve workers
    need it.  Import it, build it, serve requests — no backend comes up."""
    out = subprocess.run(
        [sys.executable, "-c", _SERVER_PROBE], cwd=str(tmp_path),
        env=_env(PYTHONPATH=str(REPO), FTC_STATE_DIR=str(tmp_path / "state"),
                 FTC_OBJECT_STORE_ROOT=str(tmp_path / "objects"),
                 FTC_SERVE_TRANSPORT="process"),
        capture_output=True, text=True, timeout=120,
    )
    assert out.returncode == 0, out.stderr[-3000:]
    rec = json.loads(out.stdout.strip().splitlines()[-1])
    assert rec["backend_initialised"] is False, rec
    # and the server says so itself: chip_smoke.py asks after every phase
    assert rec["health"] == {"status": "ok", "jax_backend": False}
