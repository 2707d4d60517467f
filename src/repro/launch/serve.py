"""Serving launcher: continuous-batching engine + closed-loop load.

  PYTHONPATH=src python -m repro.launch.serve --arch llama3-8b --reduced \
      --transport gdr --clients 4

The engine is warmed before the clients start, so compile time is
reported apart from serving. ``main(argv)`` returns a :class:`ServeRun`
for callers such as ``chip_smoke.py``.
"""

from __future__ import annotations

import argparse
import dataclasses
import time

import jax

from repro.configs import get_config
from repro.core.transport import PROFILES, Transport
from repro.launch.compile_cache import use_compile_cache
from repro.models import Model
from repro.serving import ClosedLoopClient, Gateway, ServingEngine, run_closed_loop

# stages the engine charges from the transport profile, not from a clock
MODELED_STAGES = ("request", "copy_in", "copy_out", "response")


@dataclasses.dataclass
class ServeRun:
    model: Model
    params: dict
    engine: ServingEngine
    requests: list  # every Request sent, with ``generated`` filled in


def main(argv=None) -> ServeRun:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--transport", default="gdr",
                    choices=["local", "tcp", "rdma", "gdr"])
    ap.add_argument("--first-hop", default="",
                    choices=["", "tcp", "rdma"], help="proxied connection")
    ap.add_argument("--profile", default="paper_a2", choices=sorted(PROFILES))
    ap.add_argument("--clients", type=int, default=4)
    ap.add_argument("--requests", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--prompt-len-max", type=int, default=0,
                    help="draw prompt lengths uniformly from "
                         "[--prompt-len, this] (default: fixed length)")
    ap.add_argument("--new-tokens", type=int, default=16)
    ap.add_argument("--max-batch", type=int, default=8)
    args = ap.parse_args(argv)

    cache_dir = use_compile_cache()
    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    dev = jax.devices()[0]
    print(f"model {cfg.name}: layers={cfg.n_layers} d_model={cfg.d_model} "
          f"heads={cfg.n_heads} kv_heads={cfg.n_kv_heads} "
          f"head_dim={cfg.head_dim} d_ff={cfg.d_ff} vocab={cfg.vocab_size}")
    print(f"devices: {jax.devices()} platform={dev.platform} "
          f"kind={dev.device_kind!r}; compile cache {cache_dir}")

    model = Model(cfg)
    t0 = time.perf_counter()
    params = jax.block_until_ready(model.init(jax.random.key(0)))
    init_s = time.perf_counter() - t0
    longest = max(args.prompt_len, args.prompt_len_max)
    engine = ServingEngine(
        model, params, max_batch=args.max_batch,
        max_seq=longest + args.new_tokens + 8,
        transport=Transport(args.transport), profile=PROFILES[args.profile],
        warmup=True,
    )
    front = engine
    if args.first_hop:
        front = Gateway(engine, first_hop=Transport(args.first_hop),
                        profile=PROFILES[args.profile])
    clients = [
        ClosedLoopClient(i, cfg.vocab_size, prompt_len=args.prompt_len,
                         prompt_len_max=args.prompt_len_max,
                         max_new_tokens=args.new_tokens)
        for i in range(args.clients)
    ]
    t0 = time.perf_counter()
    run_closed_loop(front, clients, requests_per_client=args.requests)
    serve_s = time.perf_counter() - t0
    requests = [r for c in clients for r in c.sent]

    s = engine.store
    print(f"{cfg.name} via {args.transport}"
          + (f" (proxied {args.first_hop})" if args.first_hop else ""))
    print(f"  params built in {init_s:.3f} s; warmup (compiles) "
          f"{engine.warm_s:.3f} s; serving {serve_s:.3f} s")
    print(f"  requests answered: {len(s.records)} of {len(requests)}; "
          f"tokens generated: {sum(len(r.generated) for r in requests)}")
    print("  mean total: %.2f ms  p99: %.2f ms"
          % (s.summary()["mean"] * 1e3, s.summary()["p99"] * 1e3))
    print("  stage means (ms):", {
        k: f"{v * 1e3:.3f} "
           + ("modeled" if k in MODELED_STAGES else "measured")
        for k, v in s.stage_means().items() if v
    })
    return ServeRun(model, params, engine, requests)


if __name__ == "__main__":
    main()
