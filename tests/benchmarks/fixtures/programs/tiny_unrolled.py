"""Tests only: a program module added by a new file alone — the Llama
program with its layers UNROLLED, so every adapter leaf lies outside the
scanned stack (``layer_0/attn/q_proj/lora_a`` ...) and has no layer axis."""

from benchmarks.harness.programs import llama


def model_config(conf: dict, **overrides):
    return llama.model_config(conf, scan_layers=False, **overrides)
