"""Driver of the serving cells: ``Batcher`` over a paged ``BatchEngine`` in
this process, offered an open-loop schedule (``loadgen.py``).

Set-up makes the weights from the seed, builds the engine, warms every
program the traffic uses (the program's own ``warm_engine``) and runs the
lead-in, which fills the lanes.  The window then measures the requests DUE
inside it; the run drains for a bounded time after it and what is then
unfinished is ``failed``.  Once the window has closed and the engine is
freed, the plain reference reads a seeded sample of the finished requests:
prompt plus served tokens in one forward pass each, and the widest gap by
which a served token's logit lies below the reference's best is compared.
"""

from __future__ import annotations

import asyncio
import time

import numpy as np

from benchmarks.harness import compare, loadgen, program, result, stats, trace as trace_mod, weights
from benchmarks.harness.compiles import CompileCounter
from benchmarks.reference import model as ref_model

HOST_SPANS = ("admit", "step")
PAD_LENGTHS = (64, 128, 256, 512, 1024, 2304)


def served_gaps(conf, seed, samples, *, q=ref_model.identity,
                precision="highest", control_q=None, n_layers=None):
    """For each ``(prompt, served)`` pair: the reference's logits at every
    position that produced a served token, and the gap of that token below
    the reference's best.  With ``control_q`` the same positions are also
    computed in the lower precision and the gap of ITS first choice is read
    instead (the control need not decode).  Returns the widest gap, the MEAN
    gap over the tokens read (steady from seed to seed where the widest one
    swings), the share of tokens that are not the reference's first choice,
    and the number of tokens read."""
    import jax.numpy as jnp

    arch = ref_model.Arch.from_config(conf, n_layers)
    key = weights.root_key(seed)
    lora = ref_model.init_lora(arch, key)
    fwd = ref_model.make_forward(arch, q, precision)
    low = ref_model.make_forward(arch, control_q, "default") if control_q else None
    all_gaps: list = []
    for prompt, served in samples:
        # sequences are padded to a few fixed lengths (causal: what follows a
        # position cannot reach it) so the reference compiles a few shapes
        real = len(prompt) + len(served) - 1
        padded = next(n for n in PAD_LENGTHS if n >= real) \
            if real <= PAD_LENGTHS[-1] else real
        seq = np.zeros((1, padded), np.int32)
        seq[0, :real] = list(prompt) + list(served[:-1])
        n_rows = 1 << (len(served) - 1).bit_length()
        rows = np.minimum(len(prompt) - 1 + np.arange(n_rows), real - 1)
        keep = slice(0, len(served))
        logits = np.asarray(fwd(key, lora, jnp.asarray(seq), jnp.asarray(rows))[0],
                            np.float64)[keep]
        if low is not None:
            chosen = np.asarray(low(key, lora, jnp.asarray(seq),
                                    jnp.asarray(rows))[0])[keep].argmax(-1)
        else:
            chosen = np.asarray(served)
        gaps = logits.max(-1) - logits[np.arange(len(chosen)), chosen]
        all_gaps.extend(gaps.tolist())
    g = np.asarray(all_gaps)
    return {"widest": float(g.max()), "mean": float(g.mean()),
            "flipped_share": float((g > 0).mean()), "tokens": int(g.size)}


def finished_in_window(sent) -> list:
    """``(record, GenResult)`` of every request due in the window that
    finished without an error."""
    return [(s, s.task.result()) for s in sent
            if s.arrival.in_window and s.task.done() and not s.task.cancelled()
            and s.task.exception() is None]


def sample_finished(run, sent) -> list:
    """A sample, drawn from the seed, of the window's finished requests,
    the longest among them: ``(prompt tokens, served tokens)`` pairs."""
    done = [res for _s, res in finished_in_window(sent)]
    if not done:
        return []
    rng = np.random.default_rng([int(run.seed), 0xC0DE])
    longest = max(range(len(done)), key=lambda i: (
        len(done[i].prompt_tokens) + len(done[i].generated)))
    others = [i for i in range(len(done)) if i != longest]
    want = min(len(others), run.workload["check_requests"] - 1)
    picks = [longest] + rng.choice(others, want, replace=False).tolist()
    return [(done[i].prompt_tokens, done[i].generated) for i in sorted(picks)]


def build_engine(run):
    """Weights from the seed, the paged engine, every program warmed."""
    import jax

    from finetune_controller_tpu.models.llama import LlamaForCausalLM
    from finetune_controller_tpu.serve.engine import (
        BatchEngine, EngineConfig, warm_engine)

    eng = run.workload["engine"]
    model = LlamaForCausalLM(run.manifest.program(run.conf).model_config(
        run.conf, max_seq_len=max(eng["prompt_buckets"]) + eng["max_new_tokens"]))
    run.stage("program imports, model built")
    variables = program.seeded_serving_variables(model, run.seed)
    jax.block_until_ready(variables)
    run.stage("weights from the seed")
    engine = BatchEngine(model, variables, EngineConfig(
        slots=eng["slots"], prompt_buckets=tuple(eng["prompt_buckets"]),
        max_new_tokens=eng["max_new_tokens"], page_tokens=eng["page_tokens"],
        pool_pages=eng["pool_pages"], prefix_cache_bytes=0))
    run.stage("engine built, cache allocated")
    warm_engine(engine)
    run.stage("warm-up (compile or cache hit, one request a bucket)")
    return engine


def offer_session(run, engine, traffic: dict, seconds: float,
                  compiles: CompileCounter | None = None):
    """Lead-in, window and drain of one schedule against ``engine``.
    Returns ``(sent, marks)``; spans, series and counters go to
    ``run.recorder`` while the window is open."""
    import jax

    from finetune_controller_tpu.serve.batcher import Batcher
    from finetune_controller_tpu.serve.engine import GenRequest

    conf, wl, rec = run.conf, run.workload, run.recorder
    eng = wl["engine"]
    arrivals = loadgen.schedule(traffic, seconds, run.seed)
    lead_s = float(traffic["lead_in_s"])
    trace_dir = str(run.scratch / "trace")
    due_at: dict[str, float] = {}
    kv_tokens: list[int] = []
    tracing = {"on": False}

    # ---- spans and counters around the calls into the engine ---------------
    admit0, step0 = engine.admit, engine.step

    def admit(req):
        if req.request_id in due_at:
            rec.add("queue_wait", time.monotonic() - due_at[req.request_id])
        with rec.span("admit"):
            return admit0(req)

    def step():
        rec.add("lanes_busy", engine.active_requests)
        if tracing["on"]:
            kv_tokens.append(sum(s.next_pos for s in engine._slots if s.active))
        with rec.span("step"):
            return step0()

    def armed(on: bool):
        rec.on = on
        if compiles is not None:
            compiles.armed = on

    async def session():
        batcher = Batcher(engine, max_queue=eng["max_queue"],
                          default_timeout_s=0.0)
        loop = asyncio.get_running_loop()
        marks = {}

        async def send(a: loadgen.Arrival):
            rid = f"r{a.index}"
            due_at[rid] = t_start + a.due_s
            return await batcher.submit(GenRequest(
                request_id=rid, max_new_tokens=a.output_len,
                tokens=loadgen.prompt_tokens(run.seed, a, conf["vocab_size"]),
            ), timeout_s=0.0)

        def open_window():
            marks["setup_s"] = run.since_start()
            marks["tokens0"] = engine.tokens_generated_total
            marks["steps0"] = engine.steps_total
            marks["queue0"] = batcher.queue_depth
            marks["t0"] = time.perf_counter()
            armed(True)

        def mid_window():
            marks["queue_mid"] = batcher.queue_depth

        def start_trace():
            import shutil

            shutil.rmtree(trace_dir, ignore_errors=True)
            jax.profiler.start_trace(trace_dir)
            tracing["on"] = True

        t_start = time.monotonic() + 0.05
        base = loop.time() + 0.05 + lead_s
        loop.call_at(base, open_window)
        loop.call_at(base + seconds / 2, mid_window)
        if run.trace_on:
            # the trace covers the window's LAST seconds, so stopping it
            # (slow: it writes the file) falls into the drain
            loop.call_at(base + seconds - min(seconds, wl["trace_seconds"]),
                         start_trace)
        sent = await loadgen.offer(arrivals, send, t_start)
        await asyncio.sleep(max(0.0, t_start + lead_s + seconds
                                - time.monotonic()))
        marks["tokens1"] = engine.tokens_generated_total
        marks["steps1"] = engine.steps_total
        marks["window_s"] = time.perf_counter() - marks["t0"]
        marks["queue1"] = batcher.queue_depth
        armed(False)
        if tracing["on"]:
            tracing["on"] = False
            jax.profiler.stop_trace()
        pending = [s.task for s in sent if not s.task.done()]
        if pending:
            await asyncio.wait(pending, timeout=wl["drain_s"])
        marks["t_drained"] = time.monotonic()
        marks["rejected"] = batcher.rejected_total
        await batcher.close()
        await asyncio.gather(*(s.task for s in sent), return_exceptions=True)
        for rid in due_at:          # free the lanes of what never finished
            engine.evict(rid)
        return sent, marks

    engine.admit, engine.step = admit, step
    try:
        sent, marks = asyncio.run(session())
    finally:
        engine.admit, engine.step = admit0, step0
    marks["kv_tokens"] = kv_tokens
    return sent, marks


def run(run):
    import jax

    conf, wl = run.conf, run.workload
    compiles = CompileCounter()
    engine = build_engine(run)
    sent, marks = offer_session(run, engine, wl["traffic"], run.seconds, compiles)
    rec, kv_tokens = run.recorder, marks["kv_tokens"]

    # ---- end-to-end metrics: every request due in the window ----------------
    ttft, tpot, late, finished, wrong_len = [], [], [], [], 0
    attempted = failed = 0
    for s in sent:
        if not s.arrival.in_window:
            continue
        attempted += 1
        late.append(s.sent - s.due)
        ok = s.task.done() and not s.task.cancelled() and s.task.exception() is None
        if not ok:
            failed += 1
            ttft.append(marks["t_drained"] - s.due)   # counts as the worst
            continue
        res = s.task.result()
        finished.append((s, res))
        ttft.append(res.admitted_at - s.due)
        if len(res.generated) != s.arrival.output_len:
            wrong_len += 1
        if len(res.generated) > 1:
            tpot.append((res.finished_at - res.admitted_at)
                        / (len(res.generated) - 1))
    for v in late:
        rec.series.setdefault("late", []).append(v)
    run.window_s = marks["window_s"]
    tokens = marks["tokens1"] - marks["tokens0"]
    run.end_to_end = {
        "ttft_p90_ms": 1e3 * stats.percentile(ttft, 90),
        "tpot_p90_ms": 1e3 * stats.percentile(tpot, 90) if tpot else float("nan"),
        "serve_tokens_per_s": tokens / marks["window_s"],
        "setup_s": marks["setup_s"],
    }
    run.notes.update(kv_tokens=kv_tokens, steps=marks["steps1"] - marks["steps0"])
    run.stages.append(("lead-in", marks["setup_s"]))
    print(run.setup_split(), flush=True)
    print(f"window {marks['window_s']:.3f} s: {attempted} requests due, "
          f"{failed} failed, {marks['rejected']} refused (all run), queue at "
          f"close {marks['queue1']}; ttft median "
          f"{1e3 * stats.median(ttft):.1f} ms p90 "
          f"{run.end_to_end['ttft_p90_ms']:.1f} ms (n={len(ttft)}); tpot median "
          f"{1e3 * stats.median(tpot):.2f} ms (n={len(tpot)}); "
          f"{tokens} tokens, {run.notes['steps']} decode steps; generator late "
          f"p99 {1e3 * stats.percentile(late, 99):.2f} ms", flush=True)

    # ---- memory, then free the engine ----------------------------------------
    peak = result.allocator_peak_bytes(run.chips)
    print(f"memory: allocator peak {peak} B", flush=True)
    engine._cache = engine.variables = None
    del engine
    cmp = compare.Comparison()
    cmp.require("no_compile_in_window", compiles.count == 0,
                f"{compiles.count} program(s) compiled or loaded in the window")
    cmp.require("asked_token_counts", wrong_len == 0,
                f"{wrong_len} finished request(s) with another number of tokens")
    cmp.require("some_finished", len(finished) > 0, f"{len(finished)} finished")

    # ---- the plain reference over a seeded sample, the longest in it ---------
    samples = sample_finished(run, sent)
    if samples:
        t = time.perf_counter()
        gaps = served_gaps(conf, run.seed, samples)
        print(f"reference: {len(samples)} requests, {gaps['tokens']} served "
              f"tokens in {time.perf_counter() - t:.1f} s; "
              f"{100 * gaps['flipped_share']:.2f} % are not the reference's "
              f"first choice", flush=True)
        cmp.check("served_token_mean_logit_gap", gaps["mean"],
                  wl["limits"]["served_token_mean_logit_gap"])
        cmp.check("served_token_widest_logit_gap", gaps["widest"],
                  wl["limits"]["served_token_widest_logit_gap"])

    out = {"correct": cmp.correct, "compared": cmp.compared(),
           "attempted": attempted, "failed": failed,
           "device": {"memory_peak_bytes": peak}}
    if run.trace_on:
        trace_mod.attach(run, out, str(run.scratch / "trace"), HOST_SPANS)
    return out
