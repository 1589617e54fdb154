"""The overlapped input pipeline's contract (data/prefetch.py): order
preservation, bounded memory, crash transparency, clean shutdown — and the
trainer-level guarantee that turning prefetch on changes WHEN batches are
built, never WHICH batches a step sees (bit-identical loss trajectories,
including across a checkpoint-resume)."""

import threading
import time

import numpy as np
import pytest

from finetune_controller_tpu.data.prefetch import (
    PrefetchIterator,
    prefetch_batches,
)


def test_order_preserved_exactly():
    src = list(range(200))
    with PrefetchIterator(iter(src), depth=4) as it:
        assert list(it) == src


def test_depth_zero_is_the_synchronous_passthrough():
    it = prefetch_batches(iter([1, 2, 3]), depth=0)
    assert not isinstance(it, PrefetchIterator)
    assert list(it) == [1, 2, 3]


def test_invalid_depth_rejected():
    with pytest.raises(ValueError, match="depth"):
        PrefetchIterator(iter([]), depth=0)


def test_queue_is_bounded():
    """The producer must build at most depth+1 batches ahead of the consumer
    (depth finished in the queue + one in flight) — not eat the dataset."""
    built = []

    def gen():
        for i in range(100):
            built.append(i)
            yield i

    with PrefetchIterator(gen(), depth=2) as it:
        deadline = time.monotonic() + 5.0
        while len(built) < 3 and time.monotonic() < deadline:
            time.sleep(0.01)
        time.sleep(0.2)  # would overrun here if the queue were unbounded
        assert len(built) <= 3, f"producer ran ahead: built {len(built)}"
        assert next(it) == 0


def test_producer_exception_reraised_verbatim():
    """A producer crash must surface on the consumer thread as the ORIGINAL
    exception — no hang, no wrapper type — after the good batches drain."""

    class BoomError(RuntimeError):
        pass

    def gen():
        yield 1
        yield 2
        raise BoomError("decoder exploded")

    it = PrefetchIterator(gen(), depth=2)
    assert next(it) == 1
    assert next(it) == 2
    with pytest.raises(BoomError, match="decoder exploded"):
        next(it)
    # the iterator is dead, not wedged
    with pytest.raises(StopIteration):
        next(it)


def test_close_unblocks_producer_stuck_on_full_queue():
    """close() while the producer is waiting for queue space must stop the
    thread promptly — the shutdown path a trainer's finally block takes."""
    it = PrefetchIterator(iter(range(1000)), depth=1)
    deadline = time.monotonic() + 5.0
    while it._queue.empty() and time.monotonic() < deadline:
        time.sleep(0.01)
    it.close()
    it._thread.join(timeout=5.0)
    assert not it._thread.is_alive()
    it.close()  # idempotent


def test_next_after_close_raises_instead_of_hanging():
    """close() drains the queue and the producer exits without posting the
    done sentinel — a later next() must StopIteration, not block forever."""
    it = PrefetchIterator(iter(range(1000)), depth=1)
    next(it)
    it.close()
    with pytest.raises(StopIteration):
        next(it)


def test_transfer_stage_runs_on_producer_thread():
    seen_threads = []

    def transfer(x):
        seen_threads.append(threading.current_thread())
        return x * 10

    with PrefetchIterator(iter([1, 2, 3]), depth=2, transfer=transfer) as it:
        assert list(it) == [10, 20, 30]
    main = threading.main_thread()
    assert all(t is not main for t in seen_threads)


def test_a_profiler_session_holds_build_and_take_spans(tmp_path):
    """The pipeline keeps no timing of its own: inside a profiler session
    the producer's builds and the consumer's takes are spans on the
    profiler's clock, one per batch (``obs.annotate``)."""
    import glob

    import jax
    from jax.profiler import ProfileData

    def slow_gen():
        for i in range(4):
            time.sleep(0.01)
            yield i

    jax.profiler.start_trace(str(tmp_path))
    try:
        with PrefetchIterator(slow_gen(), depth=2) as it:
            assert list(it) == [0, 1, 2, 3]
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(str(tmp_path / "plugins/profile/*/*.xplane.pb"))
    spans: dict[str, list] = {}
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith("prefetch."):
                        spans.setdefault(e.name, []).append(e)
    # four batches and the end-of-data marker; no transfer stage was given
    assert len(spans["prefetch.build"]) == 5
    assert sum(e.duration_ns for e in spans["prefetch.build"]) >= 0.03e9
    assert len(spans["prefetch.put"]) == 4
    assert len(spans["prefetch.take"]) == 5
    assert all(0 <= int(dict(e.stats)["depth"]) <= 2
               for e in spans["prefetch.take"])
    assert "prefetch.transfer" not in spans


# ---------------------------------------------------------------------------
# trainer-level: prefetch on/off bit-identity, incl. checkpoint-resume
# ---------------------------------------------------------------------------


def _run_losses(tmp_path, prefetch, legs):
    """Train len(legs) legs into one artifacts dir (later legs resume from
    the earlier legs' checkpoints); return the full loss trajectory."""
    from finetune_controller_tpu.data import synthetic_batches
    from finetune_controller_tpu.models import PRESETS, LoRAConfig
    from finetune_controller_tpu.train import Trainer, TrainConfig

    model_cfg = PRESETS["tiny-test"].replace(lora=LoRAConfig(rank=4))
    losses = []
    for total_steps in legs:
        cfg = TrainConfig(
            mode="lora", total_steps=total_steps, batch_size=4, seq_len=16,
            log_every=1, checkpoint_every=4, prefetch=prefetch,
        )
        trainer = Trainer(model_cfg, cfg)
        batches = synthetic_batches(
            4, 16, model_cfg.vocab_size, task="increment"
        )
        trainer.fit(
            batches, str(tmp_path),
            on_metrics=lambda s, m: losses.append(float(m["loss"])),
        )
    return losses


def test_prefetch_bit_identical_losses_and_resume(tmp_path):
    """Acceptance: prefetch on (default, with the device_put transfer stage)
    reproduces the synchronous iterator's loss trajectory BIT-identically —
    same batches, same order — including after a checkpoint-resume whose
    fast-forward skip must consume the same stream positions."""
    sync = _run_losses(tmp_path / "sync", 0, legs=(8,))
    over = _run_losses(tmp_path / "over", 2, legs=(8,))
    assert over == sync  # exact float equality, not approx

    # interrupted at step 4 (checkpoint) then resumed to 8: the resumed
    # prefetch producer must start AFTER the fast-forward skip, seeing
    # exactly the batches an uninterrupted run would have
    resumed = _run_losses(tmp_path / "resumed", 2, legs=(4, 8))
    assert resumed == sync


def test_trainer_metrics_csv_carries_input_columns(tmp_path):
    """input_ms / input_fraction are first-class metrics.csv columns with
    sane values, and the step metrics callback carries them too."""
    import csv

    from finetune_controller_tpu.data import synthetic_batches
    from finetune_controller_tpu.models import PRESETS, LoRAConfig
    from finetune_controller_tpu.train import Trainer, TrainConfig

    model_cfg = PRESETS["tiny-test"].replace(lora=LoRAConfig(rank=4))
    cfg = TrainConfig(
        mode="lora", total_steps=4, batch_size=4, seq_len=16,
        log_every=2, checkpoint_every=100,
    )
    seen = []
    Trainer(model_cfg, cfg).fit(
        synthetic_batches(4, 16, model_cfg.vocab_size),
        str(tmp_path), on_metrics=lambda s, m: seen.append(m),
    )
    rows = list(csv.DictReader(open(tmp_path / "metrics.csv")))
    assert rows, "no metrics rows written"
    for row in rows:
        assert float(row["input_ms"]) >= 0.0
        assert 0.0 <= float(row["input_fraction"]) <= 1.0
    assert all("input_fraction" in m for m in seen)
