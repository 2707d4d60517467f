"""Replica worker: the child-process half of the socket RPC control plane.

``python -m repro.serving.worker --port P`` is what
:class:`~repro.serving.ipc.ReplicaClient` spawns — one per
``backend="process"`` replica. The worker connects back to the parent's
listening socket, answers the ``hello`` clock handshake **before
importing jax** (so the offset estimate is a socket RTT, not an import
stall), then receives an ``init`` spec and builds its OWN copy of the
serving stack inside this process:

* its own XLA client over the forced host-device subset the parent put
  in this process's ``XLA_FLAGS`` (each replica process owns its devices
  the way each of the paper's stage nodes owns its accelerators);
* the model + params, rebuilt deterministically from
  ``model.init(jax.random.key(param_seed))`` — params cross the process
  boundary as a seed, not as tensors, which is why the token-identity
  check against the in-process baseline is meaningful (both sides must
  reconstruct the SAME weights from the same seed);
* a :class:`~repro.serving.engine.ServingEngine` (or
  ``DisaggregatedEngine``) wrapped in the threaded
  :class:`~repro.serving.engine.EnginePipeline`, so dispatch, device
  harvest, and detokenize/record-finalize overlap inside the replica
  while the parent's router is off doing something else entirely.

After init it is a plain RPC server: submit / harvest / load /
telemetry / drain / shutdown, each answered with one frame. Any
exception is caught and shipped back as an ``("error", {traceback})``
frame — the parent surfaces it as a :class:`~repro.serving.ipc.
ReplicaError` instead of hanging. EOF from the parent (a crashed or
impatient router) exits the process, so workers can't outlive their
cluster even if the atexit reaper never runs.
"""

from __future__ import annotations

import argparse
import socket
import sys
import time
import traceback


def _build_pipeline(spec: dict):
    """Build model -> params -> engine -> EnginePipeline from the init
    spec. Runs after the handshake; this is where jax gets imported and
    the replica's own XLA client comes up over its forced devices."""
    import jax
    import jax.numpy as jnp

    from repro.launch.compile_cache import use_compile_cache
    from repro.models.model import Model
    from repro.serving.engine import EnginePipeline, ServingEngine

    use_compile_cache()

    model = Model(spec["cfg"], dtype=getattr(jnp, spec.get("dtype", "float32")))
    # weights from the seed, not the wire: deterministic reconstruction is
    # the cheap, exact alternative to shipping tensors through the RPC
    params = model.init(jax.random.key(int(spec.get("param_seed", 0))))
    if spec.get("tracing"):
        from repro.core import trace

        # spans are stamped with THIS process's perf_counter; the parent
        # rebases on ingest and overrides the label with the replica name
        trace.enable_tracing(process=spec.get("trace_label", "worker"))
    engine_kw = dict(spec.get("engine_kw") or {})
    if spec.get("engine", "fused") == "disagg":
        from repro.serving.disagg import DisaggregatedEngine

        eng = DisaggregatedEngine(model, params, **engine_kw)
    else:
        eng = ServingEngine(model, params, **engine_kw)
    return EnginePipeline(eng, backlog=int(spec.get("backlog", 2)))


def _snapshot(pipe) -> dict:
    return pipe.load_snapshot()


def _spans() -> list:
    """Drain this process's trace buffer as wire tuples — piggybacked on
    harvest/telemetry/drain replies, rebased parent-side."""
    from repro.core import trace

    return trace.tracer().drain_wire()


def _harvest(pipe) -> dict:
    """Finished responses + their records since the last harvest, in
    completion order, plus a fresh load snapshot."""
    from repro.serving import ipc

    done = []
    for rsp in pipe.step():
        rec = pipe.engine._records[rsp.request_id]
        done.append((ipc.response_to_wire(rsp), ipc.record_to_wire(rec)))
    return {"done": done, "load": _snapshot(pipe), "spans": _spans()}


def _telemetry(pipe) -> dict:
    eng = pipe.engine
    return {
        "load": _snapshot(pipe),
        "decode_steps": eng.decode_steps,
        "useful_steps": eng.useful_steps,
        "prefill_compile_count": eng.prefill_compile_count,
        "prefill_tokens_total": eng.prefill_tokens_total,
        "prefill_tokens_uncached": eng.prefill_tokens_uncached,
        "prefix_hits": eng.prefix_hits,
        "warm_s": eng.warm_s,
        "metrics": pipe.metrics_snapshot(),
        "spans": _spans(),
    }


def _drain(pipe, deadline_s: float) -> dict:
    """Run the pipeline to idle (bounded), returning every finished pair
    harvested along the way."""
    from repro.serving import ipc

    done = []
    t_end = time.perf_counter() + float(deadline_s)
    while not pipe.idle:
        for rsp in pipe.step():
            rec = pipe.engine._records[rsp.request_id]
            done.append((ipc.response_to_wire(rsp), ipc.record_to_wire(rec)))
        if time.perf_counter() > t_end:
            raise TimeoutError(
                f"drain deadline {deadline_s}s lapsed with the pipeline "
                f"still busy: {pipe.load_snapshot()}"
            )
        time.sleep(0.0005)
    for rsp in pipe.step():  # finals surfaced by the last transition to idle
        rec = pipe.engine._records[rsp.request_id]
        done.append((ipc.response_to_wire(rsp), ipc.record_to_wire(rec)))
    return {"done": done, "load": _snapshot(pipe), "spans": _spans()}


def serve(port: int) -> int:
    # framing helpers only — repro.serving.ipc must stay importable
    # without jax side effects (it is: pure stdlib at module level)
    from repro.serving import ipc

    sock = socket.create_connection(("127.0.0.1", port), timeout=30.0)
    sock.settimeout(None)  # parent owns all deadlines; the worker blocks
    pipe = None
    try:
        while True:
            try:
                op, payload, _ = ipc.recv_msg(sock)
            except ipc.ConnectionClosed:
                return 0  # parent went away: die with it, leave no orphan
            try:
                if op == "hello":
                    # pre-jax clock sample for the parent's skew estimate
                    ipc.send_msg(sock, "ok", {"t_child": time.perf_counter()})
                elif op == "init":
                    t0 = time.perf_counter()
                    pipe = _build_pipeline(payload)
                    import jax

                    ipc.send_msg(sock, "ok", {
                        "init_s": time.perf_counter() - t0,
                        "devices": jax.device_count(),
                        "warm_s": pipe.engine.warm_s,
                    })
                elif pipe is None:
                    raise RuntimeError(f"op {op!r} before init")
                elif op == "submit":
                    req = ipc.request_from_wire(payload)
                    pipe.submit(req)
                    ipc.send_msg(sock, "ok", _snapshot(pipe))
                elif op == "harvest":
                    ipc.send_msg(sock, "ok", _harvest(pipe))
                elif op == "load":
                    ipc.send_msg(sock, "ok", _snapshot(pipe))
                elif op == "telemetry":
                    ipc.send_msg(sock, "ok", _telemetry(pipe))
                elif op == "drain":
                    ipc.send_msg(
                        sock, "ok",
                        _drain(pipe, payload.get("deadline_s", 120.0)),
                    )
                elif op == "shutdown":
                    if pipe is not None:
                        pipe.close()
                    ipc.send_msg(sock, "ok", None)
                    return 0
                else:
                    raise RuntimeError(f"unknown op {op!r}")
            except Exception:
                # ship the traceback; the parent raises it as ReplicaError
                ipc.send_msg(sock, "error",
                             {"traceback": traceback.format_exc()})
    finally:
        if pipe is not None:
            pipe.close()
        try:
            sock.close()
        except OSError:
            pass


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--port", type=int, required=True,
                    help="parent's listening port on 127.0.0.1")
    args = ap.parse_args(argv)
    return serve(args.port)


if __name__ == "__main__":
    sys.exit(main())
