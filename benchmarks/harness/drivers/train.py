"""Driver of the training cells: ``Trainer.step`` in this process.

Set-up builds ONE trainer and ONE state from the seed, drives it through its
first steps by the window's own call and feed (the input pipeline's prefetch
thread, ``Trainer.step``), and hands that same object to the window.  The
window dispatches steps the way an uninstrumented training loop does — at
most two in flight — and ends in ``block_until_ready`` on the whole state.
After the window the state is freed and the plain reference follows the
first steps from the seed.  The program's model configuration and the
reference are the modules the configuration's ``run`` names
(``manifest.py``); this file is the one definition of a training cell's
window and of ``correct``, whatever the architecture.
"""

from __future__ import annotations

import math
import time

import numpy as np

from benchmarks.harness import compare, data, program, result, trace as trace_mod
from benchmarks.harness.compiles import CompileCounter

HOST_SPANS = ("input", "dispatch", "wait")


def _flat(tree) -> dict:
    import jax

    return {program.canonical(path): leaf for path, leaf in
            jax.tree_util.tree_flatten_with_path(tree)[0]}


def _adam_mu(opt_state):
    import jax

    for node in jax.tree_util.tree_leaves(
            opt_state, is_leaf=lambda n: hasattr(n, "mu")):
        if hasattr(node, "mu"):
            return node.mu
    raise ValueError("no Adam state found in the optimizer state")


def judge(cmp: compare.Comparison, limits: dict, prog: dict, ref: dict) -> None:
    for k, (lp, lr) in enumerate(zip(prog["losses"], ref["losses"])):
        cmp.check(f"loss_step{k + 1}_gap", abs(lp - lr), limits["loss_gap"])
    cmp.check("first_grad_norm_gap",
              compare.worst_leaf_gap(prog["grad_norms"], ref["grad_norms"]),
              limits["first_grad_norm_gap"])
    cmp.check("param_change_norm_gap",
              compare.worst_leaf_gap(prog["delta_norms"], ref["delta_norms"]),
              limits["param_change_norm_gap"])


def build_trainer(run, devices=None):
    """The program's trainer for this cell: the published widths and full
    depth, its guards armed so a recompile, a stray transfer or a lost
    sharding aborts the run.  ``devices``: described ones, for a compile
    without the chip (``tools/compile_step.py``)."""
    import jax

    from finetune_controller_tpu.parallel.mesh import MeshSpec
    from finetune_controller_tpu.train.trainer import TrainConfig, Trainer

    conf, wl = run.conf, run.workload
    model_cfg = run.manifest.program(conf).model_config(
        conf, max_seq_len=wl["seq"])
    mesh = MeshSpec(**conf["run"]["mesh"]).build(
        devices or jax.devices()[:run.chips])
    return Trainer(model_cfg, TrainConfig(
        mode="lora", batch_size=wl["batch"], seq_len=wl["seq"],
        learning_rate=wl["lr"], warmup_steps=0, schedule="constant",
        total_steps=10**6, weight_decay=0.0, clip_norm=wl["clip_norm"],
        log_every=10**9, checkpoint_every=10**9,
        frozen_dtype=conf["run"]["frozen_dtype"],
        recompile_budget=1, recompile_action="raise",
        transfer_guard="raise", shard_audit="raise", trace=False,
    ), mesh=mesh)


def first_steps(run, trainer, seed: int):
    """The state from ``seed`` driven through its first steps by the
    window's own call and feed.  Returns the live ``(state, step, feed)``
    for the window to go on with, the program's numbers for the comparison,
    the token batches the reference needs and the steps' seconds."""
    import jax

    from finetune_controller_tpu.data.prefetch import prefetch_batches

    conf, wl, rec = run.conf, run.workload, run.recorder
    batch, seq = wl["batch"], wl["seq"]
    n_first, n_ref = wl["first_steps"], wl["reference_steps"]
    state = jax.block_until_ready(program.seeded_train_state(trainer, seed))
    run.stage("weights from the seed")
    first_tokens: list[np.ndarray] = []

    def recorded():
        for b in data.increment_batches(batch, seq, conf["vocab_size"], seed):
            if len(first_tokens) < n_ref:
                first_tokens.append(b["tokens"].copy())
            yield b

    feed = prefetch_batches(recorded(), depth=wl["prefetch"],
                            transfer=trainer._shard_batch)
    lora0 = _flat(compare.host(state.trainable))

    def step(state):
        with rec.span("input"):
            b = next(feed)
        with rec.span("dispatch"):
            return trainer.step(state, b)

    prog = {"losses": []}
    step_s = []
    for k in range(n_first):
        t = time.perf_counter()
        state, m = step(state)
        state = jax.block_until_ready(state)
        step_s.append(time.perf_counter() - t)
        prog["losses"].append(float(m["loss"]))
        if k == 0:
            mu = _flat(compare.host(_adam_mu(state.opt_state)))
            prog["grad_norms"] = compare.layer_norms(
                {n: a / (1.0 - 0.9) for n, a in mu.items()})
            run.stage("first step (compile or cache hit, one step)")
        if k == n_ref - 1:
            now = _flat(compare.host(state.trainable))
            prog["delta_norms"] = compare.layer_norms(
                {n: now[n] - lora0[n] for n in now})
    prog["losses"] = prog["losses"][:n_ref]
    run.stage(f"steps 2..{n_first}")
    return state, step, feed, prog, first_tokens, step_s


def _step_program_bytes(trainer, state, tokens) -> int:
    """What the compiled step needs on the fullest chip: arguments + outputs
    - aliased + temporaries, from ``memory_analysis()``.  The allocator's
    ``peak_bytes_in_use`` leaves the program's temporaries out (it read
    4.8 GB beside a 16.5 GB step; my chip runs, PR 23)."""
    import jax

    try:
        step_fn = next(iter(trainer._step_jits.values()))
        shapes = jax.tree.map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=x.sharding),
            (state, trainer._shard_batch(
                {"tokens": tokens,
                 "loss_mask": np.ones(tokens.shape, np.float32)})))
        with trainer.mesh:
            ma = step_fn.lower(*shapes).compile().memory_analysis()
    except Exception as e:  # the analysis is a reading, not the result
        print(f"memory: no compiled-program analysis ({e!r})", flush=True)
        return 0
    total = int(ma.argument_size_in_bytes + ma.output_size_in_bytes
                - ma.alias_size_in_bytes + ma.temp_size_in_bytes)
    print(f"memory: step program arguments {ma.argument_size_in_bytes} + "
          f"outputs {ma.output_size_in_bytes} - aliased "
          f"{ma.alias_size_in_bytes} + temporaries {ma.temp_size_in_bytes} = "
          f"{total} B per chip", flush=True)
    return total


def run(run):
    import jax

    conf, wl, rec = run.conf, run.workload, run.recorder
    compiles = CompileCounter()
    batch, seq = wl["batch"], wl["seq"]
    n_ref = wl["reference_steps"]
    trainer = build_trainer(run)
    run.stage("program imports, trainer built")
    state, step, feed, prog, first_tokens, step_s = first_steps(
        run, trainer, run.seed)
    probe = min(step_s[1:]) if len(step_s) > 1 else step_s[0]
    n_steps = max(1, round(run.seconds / probe))

    # ---- the measured window --------------------------------------------------
    traced_steps = min(n_steps, wl.get("trace_steps", 2)) if run.trace_on else 0
    trace_dir = str(run.scratch / "trace")
    setup_s = run.since_start()
    rec.on = compiles.armed = True
    t0 = time.perf_counter()
    inflight, losses = [], []
    for k in range(n_steps):
        if traced_steps and k == n_steps - traced_steps:
            # the trace covers the window's LAST steps, so stopping it (slow:
            # it writes the file) falls outside the window
            import shutil

            shutil.rmtree(trace_dir, ignore_errors=True)
            state = jax.block_until_ready(state)
            jax.profiler.start_trace(trace_dir)
        state, m = step(state)
        inflight.append(m["loss"])
        if len(inflight) > 1:
            with rec.span("wait"):
                losses.append(float(inflight.pop(0)))
    state = jax.block_until_ready(state)
    window_s = time.perf_counter() - t0
    rec.on = compiles.armed = False
    if traced_steps:
        jax.profiler.stop_trace()
        run.notes.update(traced_steps=traced_steps)
    losses += [float(x) for x in inflight]
    feed.close()

    failed = sum(1 for x in losses if not math.isfinite(x))
    tokens = n_steps * batch * seq
    run.window_s = window_s
    run.end_to_end = {
        "train_tokens_per_s_chip": tokens / window_s / run.chips,
        "setup_s": setup_s,
    }
    run.notes.update(batch=batch, seq=seq, steps=n_steps)
    print(run.setup_split(), flush=True)
    print(f"probe step {probe:.3f} s; window {n_steps} steps in {window_s:.3f} s; "
          f"mean step {window_s / n_steps:.4f} s", flush=True)

    # ---- memory, then free the program's state --------------------------------
    peak = max(result.allocator_peak_bytes(run.chips),
               _step_program_bytes(trainer, state, first_tokens[0]))
    devices = jax.devices()[:run.chips]
    del state, trainer, feed
    cmp = compare.Comparison()
    cmp.require("losses_finite", failed == 0 and len(losses) == n_steps,
                f"{len(losses)} of {n_steps} losses read, {failed} not finite")
    cmp.require("no_compile_in_window", compiles.count == 0,
                f"{compiles.count} program(s) compiled or loaded in the window")

    # ---- the plain reference follows the first steps --------------------------
    t = time.perf_counter()
    ref = run.manifest.reference(conf).reference_numbers(
        conf, wl, run.seed, first_tokens,
        devices=devices if run.chips > 1 else None)
    print(f"reference: {n_ref} step(s) in {time.perf_counter() - t:.1f} s",
          flush=True)
    judge(cmp, wl["limits"], prog, ref)

    out = {"correct": cmp.correct, "compared": cmp.compared(),
           "attempted": n_steps, "failed": failed,
           "device": {"memory_peak_bytes": peak}}
    if run.trace_on:
        trace_mod.attach(run, out, trace_dir, HOST_SPANS)
    return out
