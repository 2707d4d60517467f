"""One run of one cell: set up, serve the window, check, report.

The run builds the model from the cell's configuration file, makes the
weights from the seed on the device, builds the deployment the cell
names with its warm-up on (every prefill bucket and handoff extent is
compiled and run before the window), and then offers the traffic mix
open loop for ``seconds``. The client side stamps tokens on the
harness's clock (``client.py``). After the window it reads the peak
memory, frees the program's state, and compares a sample of the served
tokens with the float32 reference (``check.py``).

With ``trace`` on, the profiler records the whole window, and the
per-layer metrics are read from the trace, the program's counters and
its stage records by the readers in ``metrics/``.
"""

from __future__ import annotations

import contextlib
import gc
import math
import os
import shutil
import sys
import time
from pathlib import Path
from types import SimpleNamespace

import numpy as np

from bench import check, client, loadgen, manifest, peaks, trace_reduce


class NoAccelerator(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell asks for."""


def require_chips(n: int):
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoAccelerator(f"needs a TPU; JAX found {devs[0].platform!r}")
    if len(devs) < n:
        raise NoAccelerator(f"needs {n} TPU chips; JAX found {len(devs)}")
    return devs


def use_cache(root: Path):
    """The compile cache at the checkout's fixed ``.jax_cache/``, every
    program in it, through the program's own switch."""
    cache = str(Path(root) / ".jax_cache")
    os.environ["JAX_COMPILATION_CACHE_DIR"] = cache
    import jax

    from repro.launch.compile_cache import use_compile_cache

    jax.config.update("jax_compilation_cache_dir", cache)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return use_compile_cache()


def model_config(cfg: dict, block):
    """The program's config for the cell's sizes, as the configuration's
    block sets them; one the block's reference does not cover is
    refused."""
    from repro.configs import get_config

    mc = block.configure(get_config(cfg["repro_config"]), cfg)
    if not block.accepts(mc):
        raise ValueError(f"{cfg['name']}: the {cfg['family']!r} block's "
                         "reference does not cover this configuration")
    return mc


def check_tree(model, cfg: dict, block):
    """The generated tree has the program's layout, leaf for leaf."""
    import jax

    want = model.param_specs()
    got = block.weights.abstract_tree(cfg)
    if jax.tree.structure(want) != jax.tree.structure(got):
        raise ValueError("generated weights do not match the program's "
                         f"parameter tree:\n{jax.tree.structure(want)}\n"
                         f"{jax.tree.structure(got)}")
    for a, b in zip(jax.tree.leaves(want), jax.tree.leaves(got)):
        if a.shape != b.shape or a.dtype != b.dtype:
            raise ValueError(f"leaf {a} != generated {b}")


DEPLOYMENT_KEYS = {"engine", "cluster", "engine_options", "check"}


def engine_options(dep: dict) -> dict:
    """The deployment's ``engine_options``, passed to the engine as they
    stand (an option the engine lacks fails there), with the transfer
    mode resolved by name and the warm-up always on."""
    unknown = set(dep) - DEPLOYMENT_KEYS
    if unknown:
        raise ValueError(f"unknown deployment keys {sorted(unknown)}")
    opts = dict(dep["engine_options"], warmup=True)
    if "transfer_mode" in opts:
        from repro.core.transfer import TransferMode

        opts["transfer_mode"] = TransferMode(opts["transfer_mode"])
    return opts


def build(dep: dict, model, params):
    """(front, engines, devices) for the deployment: ``front`` takes
    submit/step, ``engines`` are the engines whose counters are read."""
    import jax

    opts = engine_options(dep)
    if dep["engine"] == "fused":
        from repro.serving import ServingEngine

        eng = ServingEngine(model, params, **opts)
        return eng, [eng], [jax.devices()[0]]
    if dep["engine"] == "disagg":
        from repro.launch.mesh import make_cluster_mesh
        from repro.serving import ServingCluster

        cl = dict(dep["cluster"])
        mesh = make_cluster_mesh(cl["n_replicas"], cl["pods_per_replica"])
        cluster = ServingCluster.build(model, params, engine="disagg",
                                       mesh=mesh, **cl, **opts)
        engines = [r.engine for r in cluster.replicas]
        return cluster, engines, list(np.asarray(mesh.devices).flat)
    raise ValueError(f"unknown engine kind {dep['engine']!r}")


COUNTERS = ("prefill_tokens_total", "prefill_padded_tokens", "decode_steps",
            "useful_steps")


def counters(engines) -> dict:
    return {k: sum(getattr(e, k) for e in engines) for k in COUNTERS}


def records(engines) -> dict:
    out = {}
    for e in engines:
        out.update(e._records)
    return out


class _Compiles:
    """Counts XLA compilations while armed (none belong in the window)."""

    def __init__(self):
        import jax

        self.armed, self.n = False, 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, duration: float, **kw):
        if self.armed and event == "/jax/core/compile/backend_compile_duration":
            self.n += 1


def serve_window(front, schedule: list, seconds: float, annotate,
                 clients=None) -> tuple:
    """Offer ``schedule`` for ``seconds``; return (client, t0, t_end,
    failed). Requests are submitted between steps: open loop when due,
    or, with ``clients``, as soon as fewer than that many are open (the
    request is then due when sent)."""
    from repro.serving.request import Request

    cl = client.Client()
    failed = 0
    t0 = time.perf_counter()
    end = t0 + seconds
    i, n = 0, len(schedule)

    def ready(now):
        if clients is None:
            return t0 + schedule[i].t <= now
        return len(cl.open) < clients

    while True:
        now = time.perf_counter()
        if now >= end:
            break
        with annotate("bench.submit"):
            while i < n and ready(now):
                a = schedule[i]
                due = t0 + a.t if clients is None else now
                req = Request(prompt_tokens=a.prompt, max_new_tokens=a.max_new)
                try:
                    front.submit(req)
                except ValueError:
                    failed += 1
                else:
                    cl.sent(req, due, time.perf_counter(), a.max_new)
                i += 1
        if front.idle:
            nxt = t0 + schedule[i].t if i < n and clients is None else end
            with annotate("bench.idle"):
                time.sleep(max(0.0, min(nxt, end) - time.perf_counter()))
            continue
        with annotate("bench.step"):
            front.step()
        cl.observe(time.perf_counter())
    t_end = time.perf_counter()
    for a in schedule[i:] if clients is None else ():
        if t0 + a.t < t_end:  # due in the window, never sent: all wait
            cl.seen.append(client.Seen(t0 + a.t, t_end, None, a.max_new))
    return cl, t0, t_end, failed


def free(engines) -> None:
    """Delete every device array the engines hold (weights, KV pools,
    in-flight steps), so the reference after the window runs on a chip
    that holds none of the program's state."""
    import jax

    for e in engines:
        for obj in (e, e.pool):
            for leaf in jax.tree.leaves(list(vars(obj).values())):
                if isinstance(leaf, jax.Array) and not leaf.is_deleted():
                    leaf.delete()


def peak_bytes(devices) -> int:
    out = 0
    for d in devices:
        st = d.memory_stats() or {}
        out = max(out, int(st.get("peak_bytes_in_use", 0)))
    return out


def end_to_end(cl, t_end: float, setup_s: float) -> dict:
    ttft = client.ttft_s(cl.seen, t_end)
    tpot = client.tpot_s(cl.seen)
    out = {"setup_s": {"value": setup_s, "unit": "s"}}
    if len(ttft):
        out["ttft_p95_ms"] = {"value": client.percentile(ttft, 95) * 1e3,
                              "unit": "ms"}
    if tpot is not None:
        out["tpot_ms"] = {"value": tpot * 1e3, "unit": "ms"}
    return out


def layer_context(cfg, block, cl, recs, c0, c1, reduced,
                  kind) -> SimpleNamespace:
    """What the metric readers read: the configuration and its block, the
    window's requests as the client saw them, the program's stage records
    and counter deltas, the trace reduction, and the chip's peaks."""
    seen = [s for s in cl.seen if s.req is not None]
    prefilled = [len(s.req.prompt_tokens) for s in seen if s.first is not None]
    contexts = [len(s.req.prompt_tokens) + j for s in seen
                for j in range(1, s.n)]
    return SimpleNamespace(
        cfg=cfg, block=block,
        records={s.req.request_id: recs[s.req.request_id] for s in seen
                 if s.req.request_id in recs},
        counters={k: c1[k] - c0[k] for k in COUNTERS},
        trace=reduced, prefilled=prefilled, decode_contexts=contexts,
        peaks=peaks.peaks(kind) if kind else None,
    )


def run(name: str, seed: int, seconds: float, trace: bool, *,
        t_start: float, accelerator: bool = True, root: Path = manifest.ROOT,
        bench_dir: Path = manifest.BENCH, control: bool = False,
        log=sys.stderr) -> dict:
    """One run of cell ``name``; returns the result object. With
    ``control`` the float8 control's tokens are compared in place of the
    served ones (``check.gaps``), and both readings go under
    ``readings``."""
    cell = manifest.load_cell(name, root, bench_dir)
    import jax

    if accelerator:
        require_chips(cell.chips)
    use_cache(root)
    trace_dir = Path(root) / ".bench_trace"
    from repro.models import Model

    cfg, dep, mix = cell.config, cell.deployment, cell.traffic
    max_seq = engine_options(dep)["max_seq"]
    if loadgen.longest(mix) > max_seq:
        raise ValueError(f"{name}: a request of {loadgen.longest(mix)} tokens "
                         f"does not fit max_seq {max_seq}")
    block = manifest.load_block(cfg, bench_dir)
    model = Model(model_config(cfg, block))
    check_tree(model, cfg, block)
    # uncommitted, like the program's own init: the engines' warm-up then
    # compiles for the same argument placement the window uses
    params = jax.block_until_ready(block.weights.make_params(cfg, seed))
    front, engines, used = build(dep, model, params)
    del params
    schedule = loadgen.schedule(mix, seed, seconds, cfg["vocab_size"])
    compiles = _Compiles()

    annotate = _null
    if trace:
        shutil.rmtree(trace_dir, ignore_errors=True)
        jax.profiler.start_trace(str(trace_dir))
        annotate = jax.profiler.TraceAnnotation
    c0, setup_s = counters(engines), time.perf_counter() - t_start
    compiles.armed = True
    with annotate("bench.window"):
        cl, t0, t_end, failed = serve_window(front, schedule, seconds,
                                             annotate, loadgen.clients(mix))
    compiles.armed = False
    c1, recs = counters(engines), records(engines)
    if trace:
        for e in engines:  # steps dispatched in the window finish in it
            jax.block_until_ready(e.pool.tokens)
        jax.profiler.stop_trace()
    peak = peak_bytes(used)
    finished = [s for s in cl.seen if s.req is not None and s.finished]
    free(engines)
    del front, engines
    gc.collect()

    metrics = end_to_end(cl, t_end, setup_s)
    kind = used[0].device_kind
    breakdown, dev_extra = None, {}
    if trace:
        reduced = trace_reduce.reduce_dir(trace_dir, bench_dir / "layers.json")
        ctx = layer_context(cfg, block, cl, recs, c0, c1, reduced,
                            kind if accelerator else None)
        metrics = {}
        for m in cell.per_layer:
            v = manifest.load_reader(m["name"], bench_dir)(ctx)
            if v is not None:
                metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}
        if reduced["chips"]:
            busy = [c["busy_s"] for c in reduced["chips"].values()]
            dev_extra = {"busy_s": float(np.mean(busy)),
                         "window_s": reduced["window_s"]}
            breakdown = {"device_ops": reduced["top_programs"],
                         "idle_gaps": reduced["idle_gaps"]}
    else:
        metrics = {m["name"]: metrics[m["name"]] for m in cell.end_to_end
                   if m["name"] in metrics}

    # the control's tokens take the served tokens' place in the comparison
    picked = check.sample(finished, seed)
    got = check.gaps(block, cfg, seed, picked, control) if picked else None
    gap = got["control_gap" if control else "served_gap"] if got else math.inf
    limit = dep["check"]
    checks = {
        "served_gap": {"value": gap, "limit": limit["served_gap"]},
        "tokens_checked": {"value": got["served_tokens"] if got else 0,
                           "limit": limit["tokens_checked"]},
    }
    correct = bool(checks["served_gap"]["value"]
                   <= checks["served_gap"]["limit"]
                   and checks["tokens_checked"]["value"]
                   >= checks["tokens_checked"]["limit"])

    late = max((s.submitted - s.due for s in cl.seen), default=0.0)
    print(f"window: {len(cl.seen)} due, {len(finished)} finished, "
          f"{compiles.n} compiles, {failed} refused, sent up to "
          f"{late * 1e3:.1f} ms late, "
          f"{sum(s.n for s in cl.seen)} tokens, peak {peak} bytes",
          file=log)
    out = {
        "correct": correct,
        "attempted": len(cl.seen) + failed,
        "failed": failed,
        "metrics": metrics,
        "device": {"platform": used[0].platform, "kind": kind,
                   "count": len(jax.devices()), "memory_peak_bytes": peak,
                   **dev_extra},
    }
    if breakdown is not None:
        out["breakdown"] = breakdown
    if control and got:  # both readings of one sample, for the limit
        out["readings"] = {"program_gap": got["served_gap"],
                           "control_gap": got["control_gap"]}
    out["checks"] = checks
    for k, v in checks.items():
        print(f"check {k} {v['value']!r} limit {v['limit']!r}", file=log)
    return out


@contextlib.contextmanager
def _null(_name):
    yield
