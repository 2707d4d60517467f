"""Smoke test of the serving path on TPU: the quickest proof it still runs.

  python chip_smoke.py               # one chip
  python chip_smoke.py --four-chips  # four chips (one host, 2x2)

One chip: full-width starcoder2-3b (30 layers, d_model 3072, random
weights from seed 0) is served through ``repro.launch.serve`` by closed-loop
clients with prompts of mixed length. Every request must get all its
tokens, each in ``[0, vocab)``, and for two requests a teacher-forced
``Model.forward`` over prompt + generated tokens must rank every greedy
token as the maximum logit at its position, up to ``TIE_ULPS``.

Four chips: a two-replica disaggregated cluster (each replica prefills on
one chip and decodes on another, the KV handoff a ``ppermute`` over ICI)
serves the same requests as the fused engine on one chip, and must return
the same tokens.

The script checks for a TPU before anything else, runs everything in this
one process (a chip belongs to one process), and exits non-zero without a
result line when no TPU is found or any phase fails. The last line of a
passing run is one JSON object naming the device.
"""

from __future__ import annotations

import argparse
import gc
import json
import sys
import time
from pathlib import Path

import jax
import numpy as np

ARCH = "starcoder2-3b"
MAX_BATCH = 8
NEW_TOKENS = 32
PROMPT_MIN, PROMPT_MAX = 100, 600
CLIENTS, PER_CLIENT = 4, 2
TEACHER_FORCED = 2  # requests re-checked against a full forward

# Greedy tokens come from the decode step's bf16 logits; the reference is
# a full forward of the same bf16 model, whose reductions run in another
# order. A token passes when its reference logit is within TIE_ULPS bf16
# spacings (taken at the maximum logit's magnitude) of the maximum. Chosen
# before the first chip run from CPU runs of 30-layer starcoder2 stacks at
# d_model 256 and 1024, whose largest gaps were 16 and 10 spacings; a wrong
# token sits about four logit standard deviations (~150 spacings at full
# width) below the maximum.
TIE_ULPS = 16


def fail(msg: str) -> None:
    print(f"chip_smoke: FAIL: {msg}", file=sys.stderr)
    sys.exit(1)


def require_tpu(n_chips: int) -> None:
    backend = jax.default_backend()
    if backend != "tpu":
        fail(f"needs the 'tpu' backend; JAX found {backend!r}")
    if len(jax.devices()) < n_chips:
        fail(f"needs {n_chips} TPU chips; JAX found {len(jax.devices())}")


def import_repro() -> None:
    src = Path(__file__).resolve().parent / "src"
    if not (src / "repro").is_dir():
        fail(f"the repro package is not beside this script (looked in {src})")
    sys.path.insert(0, str(src))


def bf16_spacing(x: np.ndarray) -> np.ndarray:
    """Distance between adjacent bf16 numbers at ``|x|`` (7 mantissa bits)."""
    tiny = np.finfo(np.float32).tiny
    return np.exp2(np.floor(np.log2(np.maximum(np.abs(x), tiny))) - 7)


def teacher_forced_check(model, params, requests) -> dict:
    """Rank each request's greedy tokens under one jitted teacher-forced
    forward over prompt + generated tokens (rows right-padded; causal
    attention keeps the pad out of every checked position)."""
    import jax.numpy as jnp

    seqs = [np.concatenate([r.prompt_tokens, np.asarray(r.generated, np.int32)])
            for r in requests]
    batch = np.zeros((len(seqs), max(len(s) for s in seqs)), np.int32)
    for i, s in enumerate(seqs):
        batch[i, :len(s)] = s
    fwd = jax.jit(lambda p, t: model.forward(p, {"tokens": t})[0])
    logits = np.asarray(fwd(params, jnp.asarray(batch)).astype(jnp.float32))
    exact = tied = bad = 0
    worst = 0.0
    for i, r in enumerate(requests):
        p = len(r.prompt_tokens)
        for j, tok in enumerate(r.generated):
            row = logits[i, p - 1 + j]
            top = row.max()
            gap_ulps = float((top - row[tok]) / bf16_spacing(top))
            worst = max(worst, gap_ulps)
            if row[tok] == top:
                exact += 1
            elif gap_ulps <= TIE_ULPS:
                tied += 1
            else:
                bad += 1
    return {"exact": exact, "within_tolerance": tied, "outside": bad,
            "worst_gap_ulps": worst}


def peak_bytes(device) -> int:
    stats = device.memory_stats()
    if not stats or "peak_bytes_in_use" not in stats:
        fail(f"{device} reports no peak_bytes_in_use")
    return int(stats["peak_bytes_in_use"])


def check_answers(requests, vocab: int) -> int:
    for r in requests:
        toks = np.asarray(r.generated)
        if len(toks) != r.max_new_tokens:
            fail(f"request {r.request_id} got {len(toks)} of "
                 f"{r.max_new_tokens} tokens")
        if toks.min() < 0 or toks.max() >= vocab:
            fail(f"request {r.request_id} has tokens outside [0, {vocab})")
    return sum(len(r.generated) for r in requests)


# --------------------------------------------------------------------------- #
def one_chip() -> None:
    from repro.launch import serve

    run = serve.main([
        "--arch", ARCH, "--max-batch", str(MAX_BATCH),
        "--clients", str(CLIENTS), "--requests", str(PER_CLIENT),
        "--prompt-len", str(PROMPT_MIN), "--prompt-len-max", str(PROMPT_MAX),
        "--new-tokens", str(NEW_TOKENS),
    ])
    cfg = run.model.cfg
    if len(run.requests) != CLIENTS * PER_CLIENT:
        fail(f"{len(run.requests)} requests sent, want {CLIENTS * PER_CLIENT}")
    if len(run.engine.store.records) != len(run.requests):
        fail(f"{len(run.engine.store.records)} of {len(run.requests)} "
             "requests answered")
    n_tok = check_answers(run.requests, cfg.vocab_size)
    print(f"answered {len(run.requests)} requests, {n_tok} tokens, "
          f"prompt lengths {sorted(len(r.prompt_tokens) for r in run.requests)}")

    by_len = sorted(run.requests, key=lambda r: len(r.prompt_tokens))
    picked = [by_len[0], by_len[-1]][:TEACHER_FORCED]
    t0 = time.perf_counter()
    tf = teacher_forced_check(run.model, run.params, picked)
    print(f"teacher-forced check ({time.perf_counter() - t0:.3f} s incl. "
          f"compile) on prompts of {[len(r.prompt_tokens) for r in picked]} "
          f"tokens, tolerance {TIE_ULPS} bf16 ulps: {tf}")
    if tf["outside"]:
        fail(f"{tf['outside']} greedy tokens are not the max logit")
    dev = jax.devices()[0]
    print(f"peak_bytes_in_use {dev}: {peak_bytes(dev)}")


# --------------------------------------------------------------------------- #
def _fresh_requests(prompts):
    from repro.serving.request import Request

    return [Request(prompt_tokens=p, max_new_tokens=NEW_TOKENS) for p in prompts]


def _drain(front, requests) -> list:
    for r in requests:
        front.submit(r)
    front.run_until_drained()
    return [list(r.generated) for r in requests]


def _devices_of(tree) -> set:
    return set().union(*(x.devices() for x in jax.tree.leaves(tree)))


def _buffer(x) -> int:
    return x.addressable_shards[0].data.unsafe_buffer_pointer()


def four_chips() -> None:
    from repro.configs import get_config
    from repro.core.transfer import TransferMode
    from repro.launch.compile_cache import use_compile_cache
    from repro.launch.mesh import make_cluster_mesh
    from repro.models import Model
    from repro.serving import ServingCluster, ServingEngine

    print(f"devices: {jax.devices()} kind={jax.devices()[0].device_kind!r}; "
          f"compile cache {use_compile_cache()}")
    cfg = get_config(ARCH)
    model = Model(cfg)
    params = jax.block_until_ready(model.init(jax.random.key(0)))
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, cfg.vocab_size, n, dtype=np.int32)
               for n in rng.integers(PROMPT_MIN, PROMPT_MAX + 1,
                                     CLIENTS * PER_CLIENT)]
    max_seq = PROMPT_MAX + NEW_TOKENS + 8
    kw = dict(max_batch=MAX_BATCH, max_seq=max_seq)

    # the reference: fused engine on chip 0, its params uncommitted there
    t0 = time.perf_counter()
    fused = ServingEngine(model, params, **kw)
    want = _drain(fused, _fresh_requests(prompts))
    print(f"fused reference on {_devices_of(fused.pool.caches)}: "
          f"{time.perf_counter() - t0:.3f} s incl. compile")
    del fused
    gc.collect()

    mesh = make_cluster_mesh(2, 2)
    cluster = ServingCluster.build(
        model, params, n_replicas=2, engine="disagg", mesh=mesh,
        transfer_mode=TransferMode.DIRECT_HBM, **kw,
    )
    engines = [rep.engine for rep in cluster.replicas]
    # replica 0 prefills on chip 0, where the source params already live:
    # its committed copy must share their buffers, or chip 0 holds two
    aliased = all(_buffer(a) == _buffer(b) for a, b in zip(
        jax.tree.leaves(params), jax.tree.leaves(engines[0].prefill_params)))
    print(f"replica0 prefill params alias the source params: {aliased}")
    if not aliased:
        fail("replica 0's prefill weights are a second copy on chip 0")
    del params
    stage_devs = []
    for i, eng in enumerate(engines):
        pre = _devices_of(eng.prefill_params)
        dec = _devices_of(eng.decode_params) | _devices_of(eng.pool.caches)
        print(f"replica{i}: prefill committed to {pre}, decode to {dec}")
        stage_devs += [frozenset(pre), frozenset(dec)]
    if len(set().union(*stage_devs)) != 4 or any(len(d) != 1 for d in stage_devs):
        fail(f"stages do not sit on four distinct chips: {stage_devs}")

    for attempt in ("cold (compiles)", "warm"):
        reqs = _fresh_requests(prompts)
        t0 = time.perf_counter()
        got = _drain(cluster, reqs)
        wall = time.perf_counter() - t0
        same = sum(g == w for g, w in zip(got, want))
        print(f"cluster pass {attempt}: {wall:.3f} s, tokens identical to "
              f"fused on {same} of {len(want)} requests")
        if same != len(want):
            fail("disaggregated cluster tokens differ from the fused engine")
    recs = cluster._records
    xfer = [recs[r.request_id].stage_s.get("transfer", 0.0) for r in reqs]
    print("measured transfer stage per request (ms, warm pass):",
          [round(x * 1e3, 3) for x in xfer])
    print("handoffs / wire bytes per replica:",
          [(e.handoffs, e.handoff_wire_bytes) for e in engines])
    if not all(e._measured() for e in engines) or not all(x > 0 for x in xfer):
        fail("the transfer stage was not measured on every request")
    for d in jax.devices():
        print(f"peak_bytes_in_use {d}: {peak_bytes(d)}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the four-chip disaggregated-cluster phase")
    args = ap.parse_args(argv)
    n_chips = 4 if args.four_chips else 1
    require_tpu(n_chips)
    import_repro()
    t0 = time.perf_counter()
    (four_chips if args.four_chips else one_chip)()
    print(f"smoke wall {time.perf_counter() - t0:.3f} s")
    dev = jax.devices()[0]
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices()),
    }}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
