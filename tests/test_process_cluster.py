"""Process-per-replica cluster: token identity vs the in-process
baseline, request/wire byte conservation, graceful shutdown, and clear
failure surfacing (a dead or raising worker must error, never hang)."""

import time

import pytest

from benchmarks.serving import micro_config


def _trace(cfg, seed=11, n=8):
    from repro.serving import loadgen

    return loadgen.poisson_schedule(
        cfg.vocab_size, rate_rps=300.0, n_requests=n,
        prompt_lens=(8, 16, 24), max_new=4, seed=seed,
    )


KW = dict(max_batch=2, max_seq=64)


def test_process_cluster_token_identity_and_conservation():
    """A seeded trace through backend='process' (2 worker processes, each
    its own XLA client, params rebuilt from the shared seed) must produce
    byte-identical token streams to the in-process Router baseline, with
    request/record counts and payload bytes conserved across the RPC
    boundary."""
    import jax

    from benchmarks.serving import micro_config
    from repro.models.model import Model
    from repro.serving import loadgen
    from repro.serving.cluster import ServingCluster

    cfg = micro_config()
    model = Model(cfg)
    params = model.init(jax.random.key(0))

    # in-process baseline (round_robin: routing independent of timing, so
    # the request->replica map is identical across backends)
    cl = ServingCluster.build(model, params, n_replicas=2,
                              policy="round_robin", **KW)
    out_a = loadgen.run_open_loop(cl, _trace(cfg))
    toks_a = {r.request_id: r.tokens for r in out_a}
    assert cl.parallelism == "sequential-in-process"

    with ServingCluster.build(model, params, n_replicas=2,
                              policy="round_robin", backend="process",
                              param_seed=0, **KW) as pc:
        assert pc.parallelism == "process-per-replica"
        assert pc.async_draining
        out_b = loadgen.run_open_loop(pc, _trace(cfg))
        toks_b = {r.request_id: r.tokens for r in out_b}

        # token identity, aligned by submission order (ids are fresh)
        a = [toks_a[i] for i in sorted(toks_a)]
        b = [toks_b[i] for i in sorted(toks_b)]
        assert a == b

        # conservation across the wire: every submit acknowledged, every
        # request emitted exactly once, payload bytes matching
        tel = pc.telemetry()
        assert tel["parallelism"] == "process-per-replica"
        assert sum(r["submitted"] for r in tel["ipc"]) == len(out_b)
        for row in tel["ipc"]:
            assert row["submitted"] == row["emitted"]
            assert row["request_payload_bytes"] == row["submitted_bytes"]
            assert row["rpc_bytes_sent"] > 0 and row["rpc_bytes_recv"] > 0

        # merged store: one rebased record per request, completion-sorted,
        # with the parent-clock issue stamp preceding the done stamp
        recs = pc.store.records
        assert len(recs) == len(out_b)
        assert all(recs[i].t_done <= recs[i + 1].t_done
                   for i in range(len(recs) - 1))
        assert all(r.t_done > r.t_issue for r in recs)
        procs = [rep.client.proc for rep in pc.replicas]
    # context-manager exit reaps every worker process
    for p in procs:
        assert p.poll() is not None


def test_dead_and_raising_workers_surface_errors():
    """A replica process that dies mid-service must surface a
    ReplicaError naming the exit (not hang the Router); a worker-side
    exception must cross the wire as a ReplicaError with the child's
    traceback; close() must stay safe afterwards."""
    from repro.serving.ipc import ReplicaClient, ReplicaError

    cfg = micro_config()
    client = ReplicaClient(devices=1, label="doomed", call_timeout_s=60.0)
    try:
        client.init({
            "cfg": cfg, "dtype": "float32", "param_seed": 0,
            "engine": "fused", "engine_kw": dict(KW), "backlog": 2,
        })
        # worker-side exception: an op before any crash — unknown ops
        # come back as error frames with the child traceback
        with pytest.raises(ReplicaError, match="unknown op"):
            client._call("definitely_not_an_op", None)
        # hard-kill the worker; the next RPC must error promptly
        client.proc.kill()
        client.proc.wait(timeout=10.0)
        t0 = time.perf_counter()
        with pytest.raises(ReplicaError, match="exited|unresponsive"):
            client.load()
        assert time.perf_counter() - t0 < 30.0  # surfaced, not hung
    finally:
        client.close()
        client.close()  # idempotent
    assert client.proc.poll() is not None


def test_worker_init_failure_reports_traceback():
    """A spec the worker cannot build (bogus engine kwargs) must fail
    init with the child's traceback, and the spawn path must clean the
    process up."""
    from repro.serving.ipc import ReplicaClient, ReplicaError

    cfg = micro_config()
    client = ReplicaClient(devices=1, label="misbuilt")
    try:
        with pytest.raises(ReplicaError, match="failed to initialize"):
            client.init({
                "cfg": cfg, "dtype": "float32", "param_seed": 0,
                "engine": "fused",
                "engine_kw": {"max_batch": 2, "max_seq": 64,
                              "no_such_kwarg": True},
                "backlog": 2,
            })
    finally:
        client.close()
    assert client.proc.poll() is not None


@pytest.mark.slow
def test_process_cluster_policy_sweep_drains():
    """Fuller multiprocess sweep (slow tier): jsq routing over 2 process
    replicas drains a longer trace with conservation intact."""
    import jax

    from repro.models.model import Model
    from repro.serving import loadgen
    from repro.serving.cluster import ServingCluster

    cfg = micro_config()
    model = Model(cfg)
    params = model.init(jax.random.key(0))
    with ServingCluster.build(model, params, n_replicas=2, policy="jsq",
                              backend="process", param_seed=0,
                              **KW) as pc:
        out = loadgen.run_open_loop(pc, _trace(cfg, seed=5, n=24))
        assert len(out) == 24
        tel = pc.telemetry()
        assert sum(r["emitted"] for r in tel["ipc"]) == 24
        assert all(r["submitted"] == r["emitted"] for r in tel["ipc"])


def test_process_backend_refuses_accelerator_parent(monkeypatch):
    """A parent holding a chip must not spawn workers that would fall back
    to the CPU unseen: backend='process' raises before spawning any."""
    import jax

    from repro.models import Model
    from repro.serving import ServingCluster
    from repro.serving import ipc

    spawned = []
    monkeypatch.setattr(ipc, "ReplicaClient",
                        lambda *a, **k: spawned.append(1))
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    with pytest.raises(RuntimeError, match="holds the 'tpu' backend"):
        ServingCluster.build(Model(micro_config()), None, n_replicas=2,
                             backend="process", **KW)
    assert not spawned
