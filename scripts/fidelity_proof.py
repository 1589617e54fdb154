"""Run the full-scale fidelity proof and record the result.

``python scripts/fidelity_proof.py [--work-dir DIR]`` executes
``finetune_controller_tpu/fidelity.py`` at its full scale (600-step pretrain
on 400 KB of real English, 200-step controller-submitted LoRA SFT), prints
the record, and writes it to ``FIDELITY.json`` at the repo root — the raw
evidence behind BASELINE.md's fidelity row.

With ``--session-log FILE`` a TPU run also appends the record to that file
with ``step: "fidelity"``.
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

REPO = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(REPO))


def main(argv: list[str] | None = None) -> int:
    from finetune_controller_tpu.platform import enable_compile_cache

    enable_compile_cache()

    p = argparse.ArgumentParser(prog="fidelity-proof")
    p.add_argument("--work-dir", default=str(REPO / "artifacts" / "fidelity"))
    p.add_argument("--pretrain-steps", type=int, default=600)
    p.add_argument("--sft-steps", type=int, default=200)
    p.add_argument("--corpus-bytes", type=int, default=400_000)
    p.add_argument("--max-new-tokens", type=int, default=48)
    p.add_argument("--session-log", default=None,
                   help="a file the TPU-run record is appended to")
    args = p.parse_args(argv)

    import jax

    from finetune_controller_tpu.fidelity import run_proof

    device = jax.devices()[0]
    t0 = time.time()
    record = run_proof(
        args.work_dir,
        pretrain_steps=args.pretrain_steps,
        sft_steps=args.sft_steps,
        corpus_bytes=args.corpus_bytes,
        max_new_tokens=args.max_new_tokens,
    )
    record["wall_s"] = round(time.time() - t0, 1)
    record["device_kind"] = device.device_kind
    record["platform"] = device.platform

    print(json.dumps(record, indent=2))
    (REPO / "FIDELITY.json").write_text(json.dumps(record, indent=2) + "\n")

    if device.platform == "tpu" and args.session_log:
        session_rec = {
            "ts": round(time.time(), 1),
            "step": "fidelity",
            "metric": "fidelity_final_loss",
            "value": record["final_loss"],
            "device_kind": device.device_kind,
            "detail": {
                k: record[k]
                for k in (
                    "random_init_loss", "base_step0_loss", "final_loss",
                    "pretrain_final_loss", "passed",
                )
            },
        }
        with open(args.session_log, "a") as f:
            f.write(json.dumps(session_rec) + "\n")

    return 0 if record["passed"] else 1


if __name__ == "__main__":
    raise SystemExit(main())
