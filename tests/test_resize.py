"""Resize-instead-of-evict (docs/elasticity.md): scheduler plans,
reservations, elastic admission, the grow pass, the simulator's
progress-lost gates, and the supervisor's topology handling.

The e2e (real subprocesses, cross-topology resume) lives in
tests/test_sched_e2e.py; this module is the millisecond-scale policy layer.
"""

import dataclasses

import pytest

from conftest import run_async as run

from finetune_controller_tpu.controller.backends.local import LocalProcessBackend
from finetune_controller_tpu.controller.devices import (
    DeviceCatalog,
    DeviceFlavor,
    FlavorQuota,
)
from finetune_controller_tpu.controller.objectstore import LocalObjectStore
from finetune_controller_tpu.controller.schemas import DatabaseStatus, JobRecord
from finetune_controller_tpu.controller.statestore import StateStore
from finetune_controller_tpu.sched import FairShareScheduler
from finetune_controller_tpu.sched.preemption import (
    ResizeDecision,
    plan_preemption,
)
from finetune_controller_tpu.sched.queues import Workload
from finetune_controller_tpu.sched.sim import (
    TRACE_QUEUES,
    ClusterSim,
    elastic_trace,
    percentile,
    sim_catalog,
)
from finetune_controller_tpu.resilience.policy import RetryPolicy
from finetune_controller_tpu.resilience.supervisor import RetrySupervisor


def _catalog(quota=4, chips_per_slice=1):
    return DeviceCatalog(
        flavors=[DeviceFlavor(name="chip", generation="cpu", hosts=1,
                              chips_per_host=chips_per_slice, runtime="cpu",
                              queue="q")],
        quotas=[FlavorQuota(flavor="chip", nominal_chips=quota)],
        default_flavor="chip",
    )


class FakeClock:
    def __init__(self, t=0.0):
        self.t = t

    def __call__(self):
        return self.t


# ---------------------------------------------------------------------------
# Planner
# ---------------------------------------------------------------------------


def _w(job_id, slices, *, queue="default", priority=50, seq=0, admitted=True):
    return Workload(
        job_id=job_id, flavor="chip", chips=slices, queue=queue,
        priority=priority, seq=seq, admitted=admitted,
        num_slices=slices, requested_slices=slices,
    )


def test_planner_prefers_shrink_over_evict():
    head = _w("hi", 2, priority=100, admitted=False)
    victim = _w("lo", 4, priority=0)
    plans = plan_preemption(
        head, [victim], 2, over_share={}, preemptor_under_share=False,
    )
    assert [(d.job_id, d.kind, d.from_slices, d.to_slices) for d in plans] == [
        ("lo", "shrink", 4, 2)
    ]
    assert plans[0].preemptor_id == "hi"


def test_planner_shrinks_to_fair_share_beyond_shortfall():
    """A victim whose queue is over share sheds its borrowed chips too —
    the freed headroom absorbs the next arrivals without another restart."""
    head = _w("hi", 1, queue="prod", priority=100, admitted=False)
    victim = _w("lo", 4, queue="batch", priority=0)
    plans = plan_preemption(
        head, [victim], 1,
        over_share={"batch": 3.0}, preemptor_under_share=False,
    )
    # need 1, fair deepening 3 -> shrink all the way to 1 slice
    assert [(d.kind, d.to_slices) for d in plans] == [("shrink", 1)]


def test_planner_escalates_to_evict_and_stays_all_or_nothing():
    head = _w("hi", 4, priority=100, admitted=False)
    victim = _w("lo", 2, priority=0)
    # shrink frees 1 < 4; eviction frees 2 < 4 -> nothing is touched
    assert plan_preemption(
        head, [victim], 4, over_share={}, preemptor_under_share=False,
    ) == []
    # 2 needed: shrink (1) cannot cover, escalates to a full eviction
    plans = plan_preemption(
        head, [victim], 2, over_share={}, preemptor_under_share=False,
    )
    assert [(d.kind, d.to_slices) for d in plans] == [("evict", 0)]


def test_planner_resize_off_degrades_to_pr5():
    head = _w("hi", 2, priority=100, admitted=False)
    victim = _w("lo", 4, priority=0)
    plans = plan_preemption(
        head, [victim], 2, over_share={}, preemptor_under_share=False,
        resize=False,
    )
    assert [(d.kind, d.to_slices) for d in plans] == [("evict", 0)]


def test_decision_kinds():
    assert ResizeDecision("j", "p", 4, 0).kind == "evict"
    assert ResizeDecision("j", "p", 4, 2).kind == "shrink"
    assert ResizeDecision("j", None, 2, 4).kind == "grow"


# ---------------------------------------------------------------------------
# Scheduler: shrink + reservation + resubmit
# ---------------------------------------------------------------------------


def test_shrink_reserves_survivor_chips_for_resubmit():
    """A shrunk victim's surviving slices are fenced: the preemptor gets
    exactly the shed chips, later arrivals get nothing, and the victim's
    resubmit admits through its own reservation within one pass."""
    sched = FairShareScheduler(_catalog(quota=4))
    sched.submit("lo", "chip", num_slices=4, priority="low")
    sched.try_admit()
    sched.submit("hi", "chip", num_slices=2, priority="high")
    sched.try_admit()
    decisions = sched.take_preemptions()
    assert [(d.job_id, d.kind, d.to_slices) for d in decisions] == [
        ("lo", "shrink", 2)
    ]
    # victim still holds its chips while exiting: nothing admits
    assert sched.try_admit() == []
    sched.release("lo")  # the backend reports the exit
    sched.submit("sneak", "chip", num_slices=2, priority="normal")
    admitted = [w.job_id for w in sched.try_admit()]
    # the preemptor takes the shed 2 chips; sneak must NOT take the 2
    # reserved for lo's resubmit
    assert admitted == ["hi"]
    assert not sched.is_admitted("sneak")
    sched.submit("lo", "chip", num_slices=2, requested_slices=4,
                 priority="low")
    admitted = [w.job_id for w in sched.try_admit()]
    assert admitted == ["lo"]
    w = sched.workload("lo")
    assert w.num_slices == 2 and w.requested_slices == 4 and w.shrunk
    snap = sched.snapshot()
    assert snap["shrinks_total"] == 1
    assert snap["shrunk_workloads"]["lo"]["num_slices"] == 2
    assert snap["resize_reservations"] == {}  # consumed on admission


def test_inflight_shrink_victim_not_double_counted():
    """While a shrink victim is still exiting it is counted in used chips
    AND holds a reservation for its surviving slices — the reservation must
    only cover the part BEYOND what it holds, or repeated admission passes
    see phantom negative capacity and evict innocent bystanders."""
    sched = FairShareScheduler(_catalog(quota=6))
    sched.submit("v1", "chip", num_slices=4, priority="low")
    sched.submit("bystander", "chip", num_slices=1, priority="low")
    sched.submit("v2", "chip", num_slices=1, priority="low")
    sched.try_admit()
    sched.submit("p", "chip", num_slices=2, priority="high")
    sched.try_admit()
    # youngest victims are 1-slice (unshrinkable): the 4-slice job sheds 2
    assert [(d.job_id, d.kind, d.to_slices)
            for d in sched.take_preemptions()] == [("v1", "shrink", 2)]
    # v1 has not exited yet: further passes must see the head as covered —
    # no new plans, and the bystanders (whose chips are not needed) untouched
    for _ in range(3):
        assert sched.try_admit() == []
        assert sched.take_preemptions() == []
    assert not sched.workload("v2").preempting
    assert not sched.workload("bystander").preempting
    sched.release("v1")
    assert [w.job_id for w in sched.try_admit()] == ["p"]


def test_elastic_admission_when_no_preemption_possible():
    """A blocked multi-slice head with no eligible victims starts SHRUNK on
    the free chips instead of starving behind a reservation (the PR-5
    anti-starvation pin, upgraded: the head RUNS instead of waiting)."""
    sched = FairShareScheduler(_catalog(quota=2))
    sched.submit("s0", "chip")
    sched.submit("s1", "chip")
    sched.try_admit()
    sched.submit("big", "chip", num_slices=2)
    sched.release("s0")  # one chip free; s1 is same-priority: no victims
    admitted = [w.job_id for w in sched.try_admit()]
    assert admitted == ["big"]
    w = sched.workload("big")
    assert w.num_slices == 1 and w.requested_slices == 2 and w.shrunk
    assert sched.take_preemptions() == []  # nobody was killed for this
    assert sched.snapshot()["resizes_total"] == 1
    assert sched.admitted_shrunk_total == 1


def test_elastic_admission_respects_fair_share_cap():
    """Elastic admission must not let a queue absorb idle capacity past its
    nominal share during contention — the share cap parks the workload as a
    blocked head instead."""
    clock = FakeClock()
    sched = FairShareScheduler(
        _catalog(quota=4), {"a": 1.0, "b": 1.0}, clock=clock,
    )
    sched.submit("a0", "chip", queue="a")
    sched.submit("a1", "chip", queue="a")
    sched.submit("b0", "chip", queue="b")
    sched.try_admit()
    # a is AT its share (2 of 4 with two active queues): a 3-slice a-job
    # must not elastically admit into the free chip
    sched.submit("a-big", "chip", num_slices=3, queue="a")
    assert sched.try_admit() == []
    assert not sched.is_admitted("a-big")
    sched.release("a-big")
    # b is under share: its 3-slice job may start shrunk on the free chip
    # (same priority everywhere, so no preemption path exists)
    sched.submit("b-big", "chip", num_slices=3, queue="b")
    admitted = [w.job_id for w in sched.try_admit()]
    assert "b-big" in admitted
    assert sched.workload("b-big").num_slices == 1


def test_grow_pass_restores_after_tenant_quiet():
    """A shrunk workload grows back (via a SIGTERM-shaped decision) once the
    flavor has been free of other tenants' demand for grow_delay_s."""
    clock = FakeClock()
    sched = FairShareScheduler(
        _catalog(quota=4), {"a": 1.0, "b": 1.0},
        clock=clock, grow_delay_s=10.0,
    )
    sched.submit("b0", "chip", num_slices=2, queue="b")
    sched.try_admit()
    # same priority + a not over share: no preemption path, so the 4-slice
    # job elastically admits at its share (2 of 4 chips)
    sched.submit("a-big", "chip", num_slices=4, queue="a")
    sched.try_admit()
    assert sched.workload("a-big").num_slices == 2
    clock.t = 5.0
    sched.release("b0")  # b finishes; flavor becomes tenant-quiet
    sched.try_admit()
    assert sched.take_preemptions() == []  # quiet window not yet elapsed
    clock.t = 20.0
    sched.try_admit()
    decisions = sched.take_preemptions()
    assert [(d.job_id, d.kind, d.from_slices, d.to_slices)
            for d in decisions] == [("a-big", "grow", 2, 4)]
    # the grown size is reserved through the exit/requeue window
    sched.release("a-big")
    sched.submit("squatter", "chip", num_slices=2, queue="b")
    assert [w.job_id for w in sched.try_admit()] == []
    sched.submit("a-big", "chip", num_slices=4, queue="a")
    assert [w.job_id for w in sched.try_admit()] == ["a-big"]
    assert sched.workload("a-big").num_slices == 4
    snap = sched.snapshot()
    assert snap["grows_total"] == 1
    assert [h["kind"] for h in snap["resize_history"]] == ["shrink", "grow"]


def test_resize_reservation_expires_on_ttl():
    """A reservation whose resubmit never arrives (cancel mid-resize) must
    not fence chips forever."""
    clock = FakeClock()
    sched = FairShareScheduler(
        _catalog(quota=2), clock=clock, reservation_ttl_s=30.0,
    )
    sched.submit("lo", "chip", num_slices=2, priority="low")
    sched.try_admit()
    sched.submit("hi", "chip", num_slices=1, priority="high")
    sched.try_admit()
    assert [d.kind for d in sched.take_preemptions()] == ["shrink"]
    sched.release("lo")  # exits; 1 chip reserved for lo's resubmit
    sched.try_admit()
    sched.submit("later", "chip", num_slices=1)
    assert not sched.try_admit()  # reservation holds
    clock.t = 100.0  # ... until the TTL
    assert [w.job_id for w in sched.try_admit()] == ["later"]


def test_forget_drops_reservation():
    sched = FairShareScheduler(_catalog(quota=2))
    sched.submit("lo", "chip", num_slices=2, priority="low")
    sched.try_admit()
    sched.submit("hi", "chip", num_slices=1, priority="high")
    sched.try_admit()
    sched.take_preemptions()
    sched.forget("lo")  # cancelled for good: reservation must die too
    sched.submit("later", "chip", num_slices=1)
    admitted = {w.job_id for w in sched.try_admit()}
    assert admitted == {"hi", "later"}


def test_fifo_scheduler_ignores_requested_slices():
    from finetune_controller_tpu.controller.backends.scheduler import (
        GangScheduler,
    )

    sched = GangScheduler(_catalog(quota=2))
    w = sched.submit("j", "chip", 1, requested_slices=2)
    assert w.chips == 1


# ---------------------------------------------------------------------------
# Simulator: the ISSUE 7 gated metric
# ---------------------------------------------------------------------------


def _run_leg(trace, *, resize, grow_delay_s=5.0):
    catalog = sim_catalog(8)
    report = ClusterSim(
        catalog,
        lambda clock: FairShareScheduler(
            catalog, TRACE_QUEUES, clock=clock,
            resize=resize, grow_delay_s=grow_delay_s,
        ),
        queue_weights=TRACE_QUEUES,
    ).run(trace)
    for o in report.outcomes.values():
        assert o.finish_s is not None, f"{o.job_id} never finished"
    return report


def test_sim_resize_beats_evict_on_progress_lost():
    """On the capacity-reclaim trace,
    resize strictly beats full eviction on chip-seconds of progress lost,
    with Jain fairness no worse and small-job p95 wait within two exit
    graces of the evict leg."""
    trace = elastic_trace(0)
    evict = _run_leg(trace, resize=False)
    resize = _run_leg(trace, resize=True)
    assert resize.progress_lost_chip_seconds < evict.progress_lost_chip_seconds
    assert resize.jain_fairness >= evict.jain_fairness
    p95_e = percentile(evict.waits(max_chips=1), 95)
    p95_r = percentile(resize.waits(max_chips=1), 95)
    assert p95_r <= p95_e + 2.0 * 1.0 + 0.5  # two exit graces of slack
    assert resize.resizes > 0
    # the XL job ran through the contention window instead of parking
    xl = resize.outcomes["xl-0"]
    assert min(xl.sizes) < 8 and xl.sizes[-1] == 8  # shrank, grew back


def test_sim_resized_jobs_always_resume_and_finish():
    for seed in (0, 1, 2):
        report = _run_leg(elastic_trace(seed), resize=True)
        for o in report.outcomes.values():
            assert len(o.resumed_at) == len(o.preempted_at), o.job_id


def test_sim_deterministic_with_resize():
    a = _run_leg(elastic_trace(0), resize=True)
    b = _run_leg(elastic_trace(0), resize=True)
    assert dataclasses.asdict(a) == dataclasses.asdict(b)


# ---------------------------------------------------------------------------
# Supervisor: resize intake + topology downgrade
# ---------------------------------------------------------------------------


class _StubBackend:
    """Records submissions; always succeeds."""

    def __init__(self):
        self.submitted = []
        self.deleted = []

    async def submit(self, job, spec, flavor, *, dataset_uri, artifacts_uri):
        self.submitted.append(job)

    async def delete_job(self, job_id, *, forget_reservations=False):
        self.deleted.append(job_id)
        return True


def test_supervisor_resize_intake_skips_backoff_and_budget(tmp_path):
    """A resize rides the failure path but is not a failure: zero backoff,
    no attempt burned, topology recorded crash-safe."""

    async def main():
        from finetune_controller_tpu.controller import registry

        registry.reset()
        registry.load_builtin_models()
        state = StateStore(tmp_path / "state")
        await state.connect()
        backend = _StubBackend()
        clock = FakeClock(t=1000.0)
        sup = RetrySupervisor(
            state, backend, _catalog(quota=4),
            policy=RetryPolicy(max_attempts=2, base_delay_s=30.0, seed=0),
            _clock=clock,
        )
        job = JobRecord(
            job_id="rz-1", user_id="u", model_name="tiny-test-lora",
            device="chip", num_slices=4, status=DatabaseStatus.RUNNING,
        )
        await state.create_job(job)
        # three consecutive resizes: none burns the retry budget
        for i, to in enumerate((2, 1, 2)):
            rec = await state.get_job("rz-1")
            assert await sup.on_job_failed(
                rec, exit_code=143, message="resized by scheduler",
                resize_to=to,
            )
            rec = await state.get_job("rz-1")
            assert rec.status is DatabaseStatus.RETRYING
            assert rec.metadata["current_num_slices"] == to
            history = rec.metadata["attempt_history"]
            assert history[-1]["resize"] is True
            assert history[-1]["delay_s"] == 0.0  # no backoff on a resize
            assert history[-1]["attempt"] == 1  # budget untouched
            assert rec.metadata["retry_next_at"] <= clock()
            # resubmit happens on the next tick, at the resized topology
            assert await sup.tick() == 1
            sub = backend.submitted[-1]
            assert sub.num_slices == to
            assert sub.requested_num_slices == 4
            rec = await state.get_job("rz-1")
            assert rec.status is DatabaseStatus.QUEUED
            assert rec.metadata["last_ran_num_slices"] == to
            await state.update_job_status("rz-1", DatabaseStatus.RUNNING)
        assert sup.resizes == 3
        # 2->1, 1->2 changed topology; 4->2 (first) also differs from the
        # original 4: every resubmit here was an elastic restore
        assert sup.elastic_restores == 3
        await state.close()

    run(main())


def test_supervisor_downgrades_topology_that_no_longer_fits(tmp_path):
    """A RETRYING job whose recorded topology exceeds the (shrunk) catalog
    quota is requeued at the largest feasible size with a logged downgrade
    — not stranded (ISSUE 7 satellite)."""

    async def main():
        from finetune_controller_tpu.controller import registry

        registry.reset()
        registry.load_builtin_models()
        state = StateStore(tmp_path / "state")
        await state.connect()
        backend = _StubBackend()
        # the catalog the CONTROLLER restarts with: quota shrank to 2
        sup = RetrySupervisor(
            state, backend, _catalog(quota=2),
            policy=RetryPolicy(max_attempts=3, base_delay_s=0.0, seed=0),
            _clock=FakeClock(t=1000.0),
        )
        job = JobRecord(
            job_id="dg-1", user_id="u", model_name="tiny-test-lora",
            device="chip", num_slices=4, status=DatabaseStatus.RETRYING,
            metadata={"retry_next_at": 0.0},
        )
        await state.create_job(job)
        assert await sup.tick() == 1
        sub = backend.submitted[-1]
        assert sub.num_slices == 2  # largest feasible under the new quota
        rec = await state.get_job("dg-1")
        assert rec.status is DatabaseStatus.QUEUED
        assert rec.metadata["topology_downgraded"]["from_num_slices"] == 4
        assert rec.metadata["topology_downgraded"]["to_num_slices"] == 2
        assert sup.topology_downgrades == 1

        # a flavor that no longer fits even ONE slice is terminal, clearly
        big_flavor = DeviceCatalog(
            flavors=[DeviceFlavor(name="chip", generation="cpu", hosts=1,
                                  chips_per_host=4, runtime="cpu", queue="q")],
            quotas=[FlavorQuota(flavor="chip", nominal_chips=2)],
            default_flavor="chip",
        )
        sup2 = RetrySupervisor(
            state, backend, big_flavor,
            policy=RetryPolicy(max_attempts=3, base_delay_s=0.0, seed=0),
            _clock=FakeClock(t=1000.0),
        )
        job2 = JobRecord(
            job_id="dg-2", user_id="u", model_name="tiny-test-lora",
            device="chip", num_slices=1, status=DatabaseStatus.RETRYING,
            metadata={"retry_next_at": 0.0},
        )
        await state.create_job(job2)
        assert await sup2.tick() == 0
        rec = await state.get_job("dg-2")
        assert rec.status is DatabaseStatus.FAILED
        assert "no longer fits" in rec.metadata["backend_message"]
        await state.close()

    run(main())


# ---------------------------------------------------------------------------
# Backend: elastic admission re-renders the trainer spec
# ---------------------------------------------------------------------------


def test_backend_rerenders_spec_on_elastic_admission(tmp_path):
    """When the scheduler grants fewer slices than asked, the local backend
    rewrites the trainer spec's mesh and the XLA device-count env before
    spawning."""

    async def main():
        import json

        from finetune_controller_tpu.controller import registry
        from finetune_controller_tpu.controller.schemas import JobInput
        from finetune_controller_tpu.controller.task_builder import (
            DatasetInput,
            task_builder,
        )
        from conftest import tiny_job_spec

        registry.reset()
        registry.load_builtin_models()
        state = StateStore(tmp_path / "state")
        await state.connect()
        store = LocalObjectStore(tmp_path / "objects")
        catalog = _catalog(quota=2)
        backend = LocalProcessBackend(
            tmp_path / "sandboxes", store, catalog, sync_interval_s=5.0,
        )
        # a 1-chip job occupies half the cluster
        spec = tiny_job_spec()
        await task_builder(
            JobInput(job_id="occupant", user_id="u",
                     model_name="tiny-test-lora", device="chip",
                     arguments=spec.training_arguments.model_dump()),
            spec, DatasetInput(),
            state=state, store=store, backend=backend, catalog=catalog,
            datasets_bucket="d", artifacts_bucket="a",
        )
        # a 2-slice job elastically admits at 1 slice
        spec2 = tiny_job_spec()
        await task_builder(
            JobInput(job_id="elastic", user_id="u",
                     model_name="tiny-test-lora", device="chip",
                     num_slices=2,
                     arguments=spec2.training_arguments.model_dump()),
            spec2, DatasetInput(),
            state=state, store=store, backend=backend, catalog=catalog,
            datasets_bucket="d", artifacts_bucket="a",
        )
        handle = backend._handles["elastic"]
        assert handle.granted_slices == 1
        assert handle.requested_slices == 2
        rendered = json.loads(handle.spec_path.read_text())
        assert rendered["mesh"]["dp"] == 1  # re-rendered at the grant
        assert "device_count=1" in handle.env["XLA_FLAGS"]
        report = await backend.get_job("elastic")
        assert report.metadata["current_num_slices"] == 1
        assert report.metadata["requested_num_slices"] == 2
        await backend.close()
        await state.close()

    run(main())
