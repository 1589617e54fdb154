"""Tests for the K8s TPU backend: JobSet rendering, state mapping, Kueue CRDs.

Covers the capability surface of the reference's PyTorchJob deployer + Kueue
CRDs (SURVEY.md §2 components 6/24) re-targeted at TPU JobSets, exercised
against the in-memory Kubernetes API fake — the reference has zero cluster
test coverage (SURVEY.md §4: 'no kind/minikube harness, no fake
kube-apiserver').
"""

import json

import pytest

from conftest import run_async, tiny_job_spec
from finetune_controller_tpu.controller.backends.base import BackendError
from finetune_controller_tpu.controller.backends.k8s import (
    InMemoryKubeClient,
    K8sJobSetBackend,
    map_jobset_state,
    render_jobset,
    render_kueue_crds,
    render_spec_configmap,
    render_trainer_spec,
)
from finetune_controller_tpu.controller.config import Settings
from finetune_controller_tpu.controller.devices import default_catalog
from finetune_controller_tpu.controller.schemas import BackendJobState, JobInput
from finetune_controller_tpu.controller.monitor import JobMonitor
from finetune_controller_tpu.controller.objectstore import LocalObjectStore
from finetune_controller_tpu.controller.statestore import StateStore
from finetune_controller_tpu.controller.task_builder import DatasetInput, task_builder


CATALOG = default_catalog()


def _job(num_slices=1, device="v5e-16"):
    return JobInput(
        job_id="llama3-8b-lora-abc12345", user_id="alice",
        model_name="llama3-8b-lora", device=device, num_slices=num_slices,
        arguments={},
    )


def test_render_jobset_tpu_topology_and_resources():
    flavor = CATALOG.get("v5e-16")
    js = render_jobset(
        _job(), tiny_job_spec(), flavor,
        namespace="ftc", image="ftc:test",
        dataset_uri="obj://datasets/alice/d1/train.jsonl",
        artifacts_uri="obj://artifacts/finetune_jobs/alice/j/artifacts",
    )
    assert js["kind"] == "JobSet"
    # Kueue integration: suspended with a queue label
    assert js["spec"]["suspend"] is True
    assert js["metadata"]["labels"]["kueue.x-k8s.io/queue-name"] == flavor.queue
    assert js["metadata"]["labels"]["ftc/chips"] == "16"
    rj = js["spec"]["replicatedJobs"][0]
    job_spec = rj["template"]["spec"]
    # 4 hosts per v5e-16 slice, indexed gang
    assert job_spec["parallelism"] == 4 and job_spec["completions"] == 4
    assert job_spec["completionMode"] == "Indexed"
    pod = job_spec["template"]["spec"]
    # TPU slice topology selectors replace GPU counts (SURVEY §2.2)
    assert pod["nodeSelector"]["cloud.google.com/gke-tpu-topology"] == "4x4"
    trainer = pod["containers"][0]
    assert trainer["resources"]["limits"]["google.com/tpu"] == "4"
    # jax.distributed bootstrap env
    env = {e["name"]: e.get("value") for e in trainer["env"]}
    assert env["FTC_NUM_PROCESSES"] == "4"
    assert env["FTC_COORDINATOR_ADDRESS"].startswith("llama3-8b-lora-abc12345-slice-0-0.")
    # init container fetches the dataset; NATIVE sidecar (init container with
    # restartPolicy Always) syncs artifacts so a crashed trainer can't wedge
    # the pod in Running
    assert pod["initContainers"][0]["name"] == "dataset-fetch"
    sync = pod["initContainers"][1]
    assert sync["name"] == "artifact-sync"
    assert sync["restartPolicy"] == "Always"
    assert "done.txt" in " ".join(sync["command"])
    # the sidecar only ships the spec's asset patterns
    assert "--pattern" in sync["command"]
    # only the trainer is a main container
    assert [c["name"] for c in pod["containers"]] == ["trainer"]


def test_render_jobset_multislice():
    flavor = CATALOG.get("v5e-16")
    js = render_jobset(
        _job(num_slices=2), tiny_job_spec(), flavor,
        namespace="ftc", image="ftc:test",
        dataset_uri=None, artifacts_uri="obj://artifacts/x",
    )
    rj = js["spec"]["replicatedJobs"][0]
    assert rj["replicas"] == 2
    env = {e["name"]: e.get("value")
           for e in rj["template"]["spec"]["template"]["spec"]["containers"][0]["env"]}
    assert env["FTC_NUM_PROCESSES"] == "8"  # 2 slices x 4 hosts
    assert js["metadata"]["labels"]["ftc/chips"] == "32"
    # multi-slice jobs carry the libtpu DCN contract alongside the FTC_* seam
    assert env["MEGASCALE_NUM_SLICES"] == "2"
    assert env["MEGASCALE_COORDINATOR_ADDRESS"].startswith(_job().job_id)
    assert "MEGASCALE_SLICE_ID" in env  # downward-API valueFrom (value=None)

    # single-slice jobs must NOT get MEGASCALE env (libtpu would try DCN init)
    js1 = render_jobset(
        _job(), tiny_job_spec(), flavor,
        namespace="ftc", image="ftc:test",
        dataset_uri=None, artifacts_uri="obj://artifacts/x",
    )
    env1 = {e["name"] for e in
            js1["spec"]["replicatedJobs"][0]["template"]["spec"]["template"]["spec"]["containers"][0]["env"]}
    assert not any(n.startswith("MEGASCALE") for n in env1)


def test_render_trainer_spec_mesh_covers_slice():
    flavor = CATALOG.get("v5e-16")
    spec = render_trainer_spec(_job(num_slices=2), tiny_job_spec(), flavor,
                               dataset_uri=None)
    mesh = spec["mesh"]
    assert mesh["dp"] == 2 and mesh["fsdp"] == 16
    assert all(mesh.get(a, 1) == 1 for a in ("ep", "pp", "sp", "tp"))


def test_spec_configmap_roundtrip():
    spec = render_trainer_spec(_job(), tiny_job_spec(), CATALOG.get("v5e-16"),
                               dataset_uri="obj://d/x/train.jsonl")
    cm = render_spec_configmap(_job(), spec, "ftc")
    parsed = json.loads(cm["data"]["job.json"])
    assert parsed["dataset"]["path"] == "/data/dataset/train.jsonl"


def test_map_jobset_state():
    assert map_jobset_state({"spec": {"suspend": True}})[0] is BackendJobState.SUSPENDED
    assert map_jobset_state({"spec": {}})[0] is BackendJobState.CREATED
    assert map_jobset_state(
        {"spec": {}, "status": {"replicatedJobsStatus": [{"active": 1}]}}
    )[0] is BackendJobState.RUNNING
    assert map_jobset_state(
        {"spec": {}, "status": {"restarts": 1}}
    )[0] is BackendJobState.RESTARTING
    assert map_jobset_state(
        {"spec": {}, "status": {"conditions": [{"type": "Completed", "status": "True"}]}}
    )[0] is BackendJobState.SUCCEEDED
    assert map_jobset_state(
        {"spec": {}, "status": {"conditions": [{"type": "Failed", "status": "True",
                                                "message": "boom"}]}}
    ) == (BackendJobState.FAILED, "boom")


def test_kueue_crds_from_catalog():
    crds = render_kueue_crds(CATALOG, namespace="ftc")
    kinds = [c["kind"] for c in crds]
    assert kinds.count("ResourceFlavor") == len(CATALOG.flavors)
    assert kinds.count("ClusterQueue") == 1
    cq = next(c for c in crds if c["kind"] == "ClusterQueue")
    groups = cq["spec"]["resourceGroups"]
    # Kueue demands each resource in exactly ONE group: all TPU flavors share
    # the google.com/tpu group, the cpu flavor gets its own
    covered = [tuple(g["coveredResources"]) for g in groups]
    assert sorted(covered) == [("cpu",), ("google.com/tpu",)]
    tpu_group = next(g for g in groups if g["coveredResources"] == ["google.com/tpu"])
    by_name = {f["name"]: f for f in tpu_group["flavors"]}
    assert set(by_name) == {"v5e-1", "v5e-4", "v5e-8", "v5e-16", "v5p-64"}
    assert by_name["v5e-16"]["resources"][0]["nominalQuota"] == 32
    assert by_name["v5e-1"]["resources"][0]["nominalQuota"] == 1  # the machine
    local_queues = [c for c in crds if c["kind"] == "LocalQueue"]
    assert {q["metadata"]["name"] for q in local_queues} == {
        f.queue for f in CATALOG.flavors
    }
    rf = next(c for c in crds if c["kind"] == "ResourceFlavor"
              and c["metadata"]["name"] == "v5p-64")
    assert rf["spec"]["nodeLabels"]["cloud.google.com/gke-tpu-topology"] == "4x4x4"


def test_k8s_backend_lifecycle_with_fake_api(tmp_path):
    async def main():
        client = InMemoryKubeClient()
        settings = Settings(namespace="ftc")
        backend = K8sJobSetBackend(CATALOG, settings, client=client)
        job = _job()
        await backend.submit(
            job, tiny_job_spec(), CATALOG.get("v5e-16"),
            dataset_uri=None, artifacts_uri="obj://artifacts/x",
        )
        # configmap + suspended jobset created
        reports = await backend.list_jobs()
        assert len(reports) == 1
        assert reports[0].state is BackendJobState.SUSPENDED
        assert await backend.queue_snapshot() == [job.job_id]

        # Kueue admits: unsuspend + mark running
        key = (backend._jobsets_path, job.job_id)
        obj = client.objects[key]
        obj["spec"]["suspend"] = False
        obj["status"] = {"replicatedJobsStatus": [{"active": 1}], "startTime": 100.0}
        report = await backend.get_job(job.job_id)
        assert report.state is BackendJobState.RUNNING
        assert report.start_time == 100.0
        assert await backend.queue_snapshot() == []

        # completes
        obj["status"] = {
            "conditions": [{"type": "Completed", "status": "True"}],
            "startTime": 100.0, "completionTime": 200.0,
        }
        report = await backend.get_job(job.job_id)
        assert report.state is BackendJobState.SUCCEEDED

        # pod logs: rank-0 pod resolved by labels (real pods have random
        # name suffixes), logs read through the client seam
        pod_name = f"{job.job_id}-slice-0-0-x7k2p"
        client.objects[(f"/api/v1/namespaces/ftc/pods", pod_name)] = {
            "metadata": {
                "name": pod_name,
                "creationTimestamp": "2026-07-29T10:00:00Z",
                "labels": {
                    "jobset.sigs.k8s.io/jobset-name": job.job_id,
                    "batch.kubernetes.io/job-completion-index": "0",
                    "jobset.sigs.k8s.io/job-index": "0",
                },
            }
        }
        client.pod_logs[pod_name] = ["step 1", "step 2"]
        lines = [l async for l in await backend.read_logs(job.job_id, last_lines=1)]
        assert lines == ["step 2"]

        # delete removes jobset + configmap
        assert await backend.delete_job(job.job_id)
        assert await backend.list_jobs() == []
        assert (backend._configmaps_path, f"{job.job_id}-spec") not in client.objects
        await backend.close()

    run_async(main())


def test_k8s_backend_with_monitor_reconciliation(tmp_path):
    """The monitor works unchanged over the K8s backend (backend-neutral seam)."""

    async def main():
        client = InMemoryKubeClient()
        settings = Settings(namespace="ftc")
        backend = K8sJobSetBackend(CATALOG, settings, client=client)
        state = StateStore(tmp_path / "state")
        store = LocalObjectStore(tmp_path / "objects")
        await state.connect()
        monitor = JobMonitor(state, store, backend, interval_s=0.1)

        job = _job(device="v5e-16")
        await task_builder(
            job, tiny_job_spec(), DatasetInput(),
            state=state, store=store, backend=backend, catalog=CATALOG,
            datasets_bucket="datasets", artifacts_bucket="artifacts",
        )
        await monitor.tick()
        rec = await state.get_job(job.job_id)
        assert rec.status.value == "queued"
        assert rec.queue_position == 1

        obj = client.objects[(backend._jobsets_path, job.job_id)]
        obj["spec"]["suspend"] = False
        obj["status"] = {"replicatedJobsStatus": [{"active": 1}], "startTime": 5.0}
        await monitor.tick()
        rec = await state.get_job(job.job_id)
        assert rec.status.value == "running"

        obj["status"] = {
            "conditions": [{"type": "Completed", "status": "True"}],
            "startTime": 5.0, "completionTime": 65.0,
        }
        await monitor.tick()
        rec = await state.get_job(job.job_id)
        assert rec.status.value == "succeeded"
        assert rec.training_duration == 60.0
        # monitor cleaned the cluster objects after success
        assert await backend.list_jobs() == []
        await state.close()

    run_async(main())


def test_storage_cli_get_and_sync(tmp_path, monkeypatch):
    """The pod-side storage CLI (init/sidecar replacement) round-trips."""
    import asyncio

    from finetune_controller_tpu.controller import config as cfg
    from finetune_controller_tpu.controller import storage_cli

    monkeypatch.setenv("FTC_OBJECT_STORE_ROOT", str(tmp_path / "objects"))
    cfg.set_settings(None)  # force re-read of env
    store = LocalObjectStore(tmp_path / "objects")
    run_async(store.put_bytes("obj://datasets/u/d/train.jsonl", b"data\n"))

    dest = tmp_path / "fetched.jsonl"
    assert storage_cli.main(["get", "obj://datasets/u/d/train.jsonl", str(dest)]) == 0
    assert dest.read_bytes() == b"data\n"

    art = tmp_path / "artifacts"
    art.mkdir()
    (art / "metrics.csv").write_text("loss\n1.0\n")
    (art / "done.txt").write_text("done")
    rc = storage_cli.main([
        "sync", str(art), "obj://artifacts/u/j",
        "--interval", "0.1", "--until-done-file", str(art / "done.txt"),
    ])
    assert rc == 0
    assert run_async(store.get_bytes("obj://artifacts/u/j/metrics.csv")) == b"loss\n1.0\n"
    cfg.set_settings(None)


def test_parse_k8s_time_rfc3339():
    from finetune_controller_tpu.controller.backends.k8s import _parse_k8s_time

    assert _parse_k8s_time(100.5) == 100.5
    ts = _parse_k8s_time("2026-07-29T10:00:00Z")
    assert ts is not None and ts > 1.7e9
    assert _parse_k8s_time("not-a-time") is None
    assert _parse_k8s_time(None) is None


def test_report_uses_condition_transition_time():
    """Real JobSet status has no completionTime — the terminal condition's
    lastTransitionTime is the fallback."""
    client = InMemoryKubeClient()
    backend = K8sJobSetBackend(CATALOG, Settings(namespace="ftc"), client=client)
    obj = {
        "metadata": {"name": "j1"},
        "spec": {},
        "status": {
            "startTime": "2026-07-29T10:00:00Z",
            "conditions": [{
                "type": "Completed", "status": "True",
                "lastTransitionTime": "2026-07-29T11:00:00Z",
            }],
        },
    }
    report = backend._report(obj)
    assert report.state is BackendJobState.SUCCEEDED
    assert report.completion_time - report.start_time == 3600.0


def test_k8s_backend_simulated_kueue_lifecycle(tmp_path):
    """Full lifecycle against the SIMULATED Kueue/JobSet operators (round-1
    weak spot: transitions were only ever hand-written fixtures): FIFO
    admission under chip quota, pod materialisation with real JobSet labels,
    rank-0 log resolution against simulator-created pods, terminal states."""

    async def main():
        # quota fits one v5e-16 job (16 chips) at a time
        client = InMemoryKubeClient(quota_chips=16)
        backend = K8sJobSetBackend(CATALOG, Settings(namespace="ftc"), client=client)
        def mk(jid):
            return JobInput(job_id=jid, user_id="alice",
                            model_name="llama3-8b-lora", device="v5e-16",
                            arguments={})
        j1, j2 = mk("sim-1"), mk("sim-2")
        for j in (j1, j2):
            await backend.submit(
                j, tiny_job_spec(), CATALOG.get("v5e-16"),
                dataset_uri=None, artifacts_uri="obj://artifacts/x",
            )
        assert await backend.queue_snapshot() == ["sim-1", "sim-2"]

        # fake Kueue admits FIFO within quota: sim-1 runs, sim-2 waits
        client.kueue_tick()
        r1 = await backend.get_job("sim-1")
        r2 = await backend.get_job("sim-2")
        assert r1.state is BackendJobState.RUNNING
        assert r2.state is BackendJobState.SUSPENDED
        assert await backend.queue_snapshot() == ["sim-2"]

        # rank-0 pod was materialised by the simulator with real labels;
        # logs stream through it
        lines = [l async for l in await backend.read_logs("sim-1")]
        assert any("training started" in l for l in lines)

        # sim-1 finishes -> quota frees -> sim-2 admitted on the next tick
        client.finish_jobset("sim-1")
        assert (await backend.get_job("sim-1")).state is BackendJobState.SUCCEEDED
        client.kueue_tick()
        assert (await backend.get_job("sim-2")).state is BackendJobState.RUNNING

        # failed jobs keep their pods for forensics
        client.finish_jobset("sim-2", failed=True, message="boom")
        r2 = await backend.get_job("sim-2")
        assert r2.state is BackendJobState.FAILED and "boom" in r2.message
        pods = await client.list(
            "/api/v1/namespaces/ftc/pods",
            "jobset.sigs.k8s.io/jobset-name=sim-2",
        )
        assert pods, "failed job's pods must be retained"
        await backend.close()

    run_async(main())


def test_k8s_fake_rejects_malformed_jobset():
    """The fake API server enforces the operator contracts a real cluster
    would: coordinator DNS convention + downward-API annotations."""

    async def main():
        client = InMemoryKubeClient()
        backend = K8sJobSetBackend(CATALOG, Settings(namespace="ftc"), client=client)
        from finetune_controller_tpu.controller.backends.k8s import render_jobset

        js = render_jobset(
            JobInput(job_id="bad-1", user_id="a", model_name="m", device="v5e-16", arguments={}), tiny_job_spec(), CATALOG.get("v5e-16"),
            namespace="ftc", image="x", dataset_uri=None,
            artifacts_uri="obj://artifacts/x",
        )
        # break the coordinator address convention
        env = js["spec"]["replicatedJobs"][0]["template"]["spec"]["template"][
            "spec"]["containers"][0]["env"]
        next(e for e in env if e["name"] == "FTC_COORDINATOR_ADDRESS")[
            "value"] = "wrong-host:1234"
        with pytest.raises(BackendError, match="DNS convention"):
            await client.create(backend._jobsets_path, js)

        # break a downward-API annotation path
        js2 = render_jobset(
            JobInput(job_id="bad-2", user_id="a", model_name="m", device="v5e-16", arguments={}), tiny_job_spec(), CATALOG.get("v5e-16"),
            namespace="ftc", image="x", dataset_uri=None,
            artifacts_uri="obj://artifacts/x",
        )
        env2 = js2["spec"]["replicatedJobs"][0]["template"]["spec"]["template"][
            "spec"]["containers"][0]["env"]
        next(e for e in env2 if e["name"] == "FTC_SLICE_INDEX")["valueFrom"][
            "fieldRef"]["fieldPath"] = "metadata.annotations['wrong/key']"
        with pytest.raises(BackendError, match="downward-API"):
            await client.create(backend._jobsets_path, js2)

    run_async(main())
