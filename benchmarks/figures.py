"""One function per paper table/figure. Each returns rows AND checks the
paper's corresponding quantitative claim, reporting PASS/FAIL deltas."""

from __future__ import annotations

from repro.core import TABLE_II, ScenarioConfig, Transport, local_reference, run_scenario
from repro.core.metrics import cov

from benchmarks.common import T_NET, emit, mean_ms, run_ms


def fig05_transport_single_client():
    """Fig. 5: ResNet50, direct connection, with/without preprocessing."""
    claims = []
    for pre, tag in ((False, "raw"), (True, "pre")):
        row = {t: run_ms("resnet50", t, preprocessed=pre) for t in T_NET}
        loc = run_ms("resnet50", Transport.LOCAL, preprocessed=pre)
        for t in T_NET:
            emit(f"fig05/resnet50/{tag}/{t.value}", row[t] * 1e3)
        emit(f"fig05/resnet50/{tag}/local", loc * 1e3)
        gdr_save = (row[Transport.TCP] - row[Transport.GDR]) / row[Transport.TCP]
        rdma_save = (row[Transport.TCP] - row[Transport.RDMA]) / row[Transport.TCP]
        # paper: GDR 20.3/23.2 % and RDMA 11.4/15.2 % less than TCP
        target_g, target_r = (23.2, 15.2) if tag == "raw" else (20.3, 11.4)
        claims.append((f"fig05 {tag}: GDR saves {gdr_save:.1%} (paper {target_g}%)",
                       abs(gdr_save * 100 - target_g) < 8))
        claims.append((f"fig05 {tag}: RDMA saves {rdma_save:.1%} (paper {target_r}%)",
                       abs(rdma_save * 100 - target_r) < 8))
        claims.append((f"fig05 {tag}: GDR-local = {row[Transport.GDR]-loc:.2f}ms (paper 0.27-0.53)",
                       0.1 < row[Transport.GDR] - loc < 0.8))
    return claims


def fig06_breakdown():
    """Fig. 6: stage breakdown, ResNet50 — the whole delta is data movement."""
    claims = []
    for t in T_NET:
        s = run_scenario(ScenarioConfig(workload=TABLE_II["resnet50"], transport=t))
        means = s.stage_means()
        for stage, v in means.items():
            if v:
                emit(f"fig06/resnet50/{t.value}/{stage}", v * 1e6)
    s_tcp = run_scenario(ScenarioConfig(workload=TABLE_II["resnet50"], transport=Transport.TCP))
    s_gdr = run_scenario(ScenarioConfig(workload=TABLE_II["resnet50"], transport=Transport.GDR))
    dm = lambda s: sum(r.data_movement for r in s.records) / len(s.records)
    pr = lambda s: sum(r.processing for r in s.records) / len(s.records)
    claims.append(("fig06: TCP-GDR delta is data movement (processing ~equal)",
                   abs(pr(s_tcp) - pr(s_gdr)) < 0.3e-3 and dm(s_tcp) > dm(s_gdr)))
    return claims


def fig07_overhead_vs_local():
    """Fig. 7: offload overhead vs local across the six models."""
    claims = []
    over = {}
    for pre, tag in ((False, "raw"), (True, "pre")):
        for w in TABLE_II:
            loc = run_ms(w, Transport.LOCAL, preprocessed=pre)
            for t in T_NET:
                o = (run_ms(w, t, preprocessed=pre) - loc) / loc
                emit(f"fig07/{w}/{tag}/{t.value}", o * 1e6, "overhead_ppm")
                over[(w, tag, t)] = o
    claims.append(("fig07: mobilenet overhead > wideresnet101 overhead (all transports)",
                   all(over[("mobilenetv3", g, t)] > over[("wideresnet101", g, t)]
                       for g in ("raw", "pre") for t in T_NET)))
    claims.append(("fig07 pre: wideresnet101 overhead ~2% (paper)",
                   over[("wideresnet101", "pre", Transport.GDR)] < 0.06))
    claims.append(("fig07: large-I/O deeplab overhead high with TCP (paper: very high)",
                   over[("deeplabv3", "raw", Transport.TCP)] > 0.4))
    return claims


def fig08_stage_fractions():
    """Fig. 8: fraction of time in data movement per transport."""
    claims = []
    fr = {}
    for w in ("mobilenetv3", "wideresnet101", "deeplabv3"):
        for t in T_NET:
            s = run_scenario(ScenarioConfig(workload=TABLE_II[w], transport=t))
            f = sum(r.data_movement for r in s.records) / sum(r.total for r in s.records)
            fr[(w, t)] = f
            emit(f"fig08/{w}/{t.value}/data_movement_fraction", f * 1e6, "ppm")
    claims.append(("fig08: mobilenet TCP fraction > RDMA > GDR (paper 62/42/30%)",
                   fr[("mobilenetv3", Transport.TCP)] > fr[("mobilenetv3", Transport.RDMA)]
                   > fr[("mobilenetv3", Transport.GDR)]))
    claims.append(("fig08: wideresnet fraction < 12% all transports (paper <10%)",
                   all(fr[("wideresnet101", t)] < 0.12 for t in T_NET)))
    claims.append(("fig08: deeplab TCP ~60% vs GDR ~23% (paper)",
                   fr[("deeplabv3", Transport.TCP)] > 0.35
                   and fr[("deeplabv3", Transport.GDR)] < 0.30))
    return claims


def fig09_cpu_usage():
    claims = []
    cpu = {}
    for t in T_NET:
        s = run_scenario(ScenarioConfig(workload=TABLE_II["deeplabv3"], transport=t))
        cpu[t] = s.cpu_per_request()
        emit(f"fig09/deeplabv3/{t.value}/cpu", cpu[t] * 1e6)
    claims.append(("fig09: TCP CPU ~2x GDR on deeplab (paper: +100%)",
                   cpu[Transport.TCP] > 1.8 * max(cpu[Transport.GDR], 1e-9)))
    return claims


def fig10_proxied_single():
    """Fig. 10: proxied connection, MobileNetV3 raw, single client."""
    claims = []
    combos = [("rdma", "gdr"), ("rdma", "rdma"), ("tcp", "gdr"), ("tcp", "rdma"),
              ("tcp", "tcp")]
    res = {}
    for first, second in combos:
        s = run_scenario(ScenarioConfig(
            workload=TABLE_II["mobilenetv3"],
            transport=Transport(second), first_hop=Transport(first)))
        res[(first, second)] = mean_ms(s)
        emit(f"fig10/mobilenetv3/{first}-{second}", res[(first, second)] * 1e3)
    save_rdma = 1 - res[("tcp", "rdma")] / res[("tcp", "tcp")]
    save_gdr = 1 - res[("tcp", "gdr")] / res[("tcp", "tcp")]
    claims.append((f"fig10: TCP/RDMA saves {save_rdma:.0%} vs TCP/TCP (paper 23%)",
                   0.05 < save_rdma < 0.45))
    claims.append((f"fig10: TCP/GDR saves {save_gdr:.0%} vs TCP/TCP (paper 57%)",
                   0.25 < save_gdr < 0.70))
    return claims


def fig11_scalability():
    """Fig. 11: total time vs #clients, raw images."""
    claims = []
    res = {}
    for w in ("mobilenetv3", "deeplabv3"):
        for n in (1, 4, 8, 16):
            for t in T_NET:
                s = run_scenario(ScenarioConfig(
                    workload=TABLE_II[w], transport=t, n_clients=n,
                    requests_per_client=40))
                res[(w, n, t)] = mean_ms(s)
                emit(f"fig11/{w}/n{n}/{t.value}", res[(w, n, t)] * 1e3)
    claims.append(("fig11: GDR best at 16 clients on both models",
                   all(res[(w, 16, Transport.GDR)] < res[(w, 16, Transport.RDMA)]
                       and res[(w, 16, Transport.GDR)] < res[(w, 16, Transport.TCP)]
                       for w in ("mobilenetv3", "deeplabv3"))))
    claims.append(("fig11: RDMA edge collapses at 16 clients on deeplab (paper: =TCP)",
                   res[("deeplabv3", 16, Transport.RDMA)] / res[("deeplabv3", 16, Transport.TCP)] > 0.85))
    claims.append((f"fig11: GDR saves {res[('deeplabv3',16,Transport.TCP)]-res[('deeplabv3',16,Transport.GDR)]:.0f}ms on deeplab@16 (paper 160ms)",
                   res[("deeplabv3", 16, Transport.TCP)] - res[("deeplabv3", 16, Transport.GDR)] > 25))
    return claims


def fig12_13_breakdown_scaling():
    """Figs. 12-13: stage-fraction evolution with #clients."""
    claims = []
    frac = {}
    for w in ("mobilenetv3", "deeplabv3"):
        for t in T_NET:
            for n in (1, 16):
                s = run_scenario(ScenarioConfig(
                    workload=TABLE_II[w], transport=t, n_clients=n,
                    requests_per_client=40))
                tot = sum(r.total for r in s.records)
                proc = sum(r.processing for r in s.records) / tot
                copy = sum(r.copy_time for r in s.records) / tot
                frac[(w, t, n)] = (proc, copy)
                emit(f"fig12/{w}/{t.value}/n{n}/processing", proc * 1e6, "ppm")
                emit(f"fig12/{w}/{t.value}/n{n}/copy", copy * 1e6, "ppm")
    claims.append(("fig12: mobilenet processing fraction rises with clients (GDR)",
                   frac[("mobilenetv3", Transport.GDR, 16)][0]
                   > frac[("mobilenetv3", Transport.GDR, 1)][0]))
    claims.append(("fig13: deeplab copy fraction rises with clients (RDMA, paper 12->28%)",
                   frac[("deeplabv3", Transport.RDMA, 16)][1]
                   > frac[("deeplabv3", Transport.RDMA, 1)][1]))
    return claims


def fig14_proxied_scaling():
    """Fig. 14: proxied configs under concurrency."""
    claims = []
    res = {}
    combos = [("rdma", "gdr"), ("rdma", "rdma"), ("tcp", "gdr"), ("tcp", "rdma"),
              ("tcp", "tcp")]
    for first, second in combos:
        s = run_scenario(ScenarioConfig(
            workload=TABLE_II["mobilenetv3"], transport=Transport(second),
            first_hop=Transport(first), n_clients=16, requests_per_client=40))
        res[(first, second)] = mean_ms(s)
        emit(f"fig14/mobilenetv3/n16/{first}-{second}", res[(first, second)] * 1e3)
    claims.append(("fig14: TCP/GDR beats RDMA/RDMA under concurrency (paper)",
                   res[("tcp", "gdr")] < res[("rdma", "rdma")]))
    claims.append(("fig14: last-hop GDR within 45% of RDMA/GDR (paper 4%; see EXPERIMENTS §Deviations)",
                   (res[("tcp", "gdr")] - res[("rdma", "gdr")]) / res[("rdma", "gdr")] < 0.45))
    claims.append(("fig14: TCP/TCP ~ TCP/RDMA ~ RDMA/RDMA (copy-engine bound, paper)",
                   res[("tcp", "rdma")] / res[("tcp", "tcp")] > 0.8))
    return claims


def fig15_concurrency_limit():
    """Fig. 15: limiting concurrent execution (streams), ResNet50."""
    claims = []
    tot = {}
    cv = {}
    for ns in (1, 2, 4, 8, 16):
        for t in (Transport.GDR, Transport.RDMA):
            s = run_scenario(ScenarioConfig(
                workload=TABLE_II["resnet50"], transport=t, n_clients=16,
                requests_per_client=40, max_streams=ns))
            tot[(ns, t)] = mean_ms(s)
            cv[(ns, t)] = s.processing_cov()
            emit(f"fig15/resnet50/streams{ns}/{t.value}", tot[(ns, t)] * 1e3)
            emit(f"fig15/resnet50/streams{ns}/{t.value}/cov", cv[(ns, t)] * 1e6, "ppm")
    claims.append((f"fig15: 1 stream {100*(tot[(1,Transport.GDR)]/tot[(16,Transport.GDR)]-1):.0f}% slower than 16 (paper 33%)",
                   1.1 < tot[(1, Transport.GDR)] / tot[(16, Transport.GDR)] < 2.0))
    claims.append(("fig15: latency decreases monotonically-ish with streams (GDR)",
                   tot[(1, Transport.GDR)] > tot[(4, Transport.GDR)] >= tot[(16, Transport.GDR)] * 0.95))
    claims.append(("fig15: GDR beats RDMA at 16 streams",
                   tot[(16, Transport.GDR)] < tot[(16, Transport.RDMA)]))
    claims.append(("fig15c: limited concurrency -> lower processing CoV",
                   cv[(1, Transport.GDR)] <= cv[(16, Transport.GDR)] + 1e-6))
    return claims


def fig16_priority():
    """Fig. 16: one priority client among normals, YoloV4 preprocessed."""
    claims = []
    res = {}
    for n in (2, 4, 8, 16):
        for t in (Transport.GDR, Transport.RDMA):
            s = run_scenario(ScenarioConfig(
                workload=TABLE_II["yolov4"], transport=t, preprocessed=True,
                n_clients=n, n_priority_clients=1, requests_per_client=30))
            hi = s.summary(priority=1)["mean"] * 1e3
            lo = s.summary(priority=0)["mean"] * 1e3
            res[(n, t)] = (hi, lo)
            emit(f"fig16/yolov4/n{n}/{t.value}/priority", hi * 1e3)
            emit(f"fig16/yolov4/n{n}/{t.value}/normal", lo * 1e3)
    claims.append(("fig16: GDR priority client protected at n=16 (paper: 54ms << normal)",
                   res[(16, Transport.GDR)][0] < 0.7 * res[(16, Transport.GDR)][1]))
    claims.append(("fig16: RDMA protection weaker than GDR at n=16 (copy engine)",
                   res[(16, Transport.RDMA)][0] / res[(16, Transport.RDMA)][1]
                   > res[(16, Transport.GDR)][0] / res[(16, Transport.GDR)][1]))
    claims.append(("fig16: priority latency ~flat until 8 clients (GDR)",
                   res[(8, Transport.GDR)][0] < 1.6 * res[(2, Transport.GDR)][0]))
    return claims


def fig17_sharing_modes():
    """Fig. 17: multi-stream vs multi-context vs MPS, EfficientNetB0 raw."""
    claims = []
    res = {}
    for t in (Transport.GDR, Transport.RDMA):
        for sharing in ("multi-stream", "multi-context", "mps"):
            s = run_scenario(ScenarioConfig(
                workload=TABLE_II["efficientnetb0"], transport=t,
                sharing=sharing, n_clients=8, requests_per_client=40))
            res[(t, sharing)] = mean_ms(s)
            emit(f"fig17/efficientnetb0/{t.value}/{sharing}", res[(t, sharing)] * 1e3)
    claims.append(("fig17: MPS beats multi-context (both transports, paper)",
                   all(res[(t, "mps")] < res[(t, "multi-context")]
                       for t in (Transport.GDR, Transport.RDMA))))
    claims.append(("fig17: GDR multi-stream ~ MPS (paper: identical)",
                   abs(res[(Transport.GDR, "multi-stream")] - res[(Transport.GDR, "mps")])
                   / res[(Transport.GDR, "mps")] < 0.10))
    claims.append(("fig17: RDMA MPS <= multi-stream (paper: MPS better)",
                   res[(Transport.RDMA, "mps")] <= res[(Transport.RDMA, "multi-stream")] * 1.02))
    return claims


def fig_prefix_hit_rate_sweep():
    """Repo-grown figure: the shared-prefix paged-KV sweep from
    ``BENCH_prefix.json`` (benchmarks/prefix.py). Same thesis as the
    paper's transport figures — bytes you don't move are latency you
    don't pay — applied to the KV handoff: as the prefix-hit rate rises,
    uncached prefill tokens, handoff wire bytes, and p99 TTFT all fall
    together. Validates the committed JSON's claims and, when matplotlib
    is importable, renders the sweep to ``BENCH_prefix.png``."""
    import json
    from pathlib import Path

    path = Path(__file__).resolve().parents[1] / "BENCH_prefix.json"
    if not path.exists():
        return [("fig-prefix: BENCH_prefix.json present "
                 "(run benchmarks.prefix first)", False)]
    data = json.loads(path.read_text())["prefix"]
    rows = data["hit_rate_sweep"]
    rates = sorted(rows, key=float)
    for k in rates:
        r = rows[k]
        emit(f"figprefix/hit{k}/uncached_tokens",
             r["prefill_tokens_uncached"], "tokens")
        emit(f"figprefix/hit{k}/handoff_wire_bytes",
             r["handoff_wire_bytes"], "bytes")
        emit(f"figprefix/hit{k}/ttft_p99", r["ttft_p99_s"] * 1e6)

    def series(field):
        return [rows[k][field] for k in rates]

    claims = [
        ("fig-prefix: uncached prefill tokens strictly fall with hit rate",
         all(a > b for a, b in zip(series("prefill_tokens_uncached"),
                                   series("prefill_tokens_uncached")[1:]))),
        ("fig-prefix: handoff wire bytes strictly fall with hit rate",
         all(a > b for a, b in zip(series("handoff_wire_bytes"),
                                   series("handoff_wire_bytes")[1:]))),
        ("fig-prefix: p99 TTFT strictly falls with hit rate",
         all(a > b for a, b in zip(series("ttft_p99_s"),
                                   series("ttft_p99_s")[1:]))),
        ("fig-prefix: wire bytes reconcile exactly at every hit rate",
         all(rows[k]["wire_reconciled_exact"] for k in rates)),
        ("fig-prefix: paged decode token-identical to ring (HBM + DMA)",
         all(v["token_match_vs_ring"] == 1.0
             for v in data["token_identity"].values())),
    ]
    _plot_prefix_sweep(rows, rates, path.with_suffix(".png"))
    return claims


def fig_stage_breakdown():
    """Repo-grown figure: stacked per-stage latency bars per KV-transfer
    mechanism (DIRECT_HBM / DIRECT_DMA / HOST_STAGED) — the repo's version
    of the paper's stage-breakdown figures (Figs. 6/8), rendered from the
    span walls exported by the traced drains in benchmarks/disagg.py
    (``stage_walls_s`` in ``BENCH_disagg.json``). The ``request`` root span
    covers its children, ``engine.step`` and ``engine.admit`` contain the
    phases stacked beside them, ``engine.prefill`` repeats the per-request
    ``prefill.*`` walls and the ``submit`` span is instant, so all are
    excluded from the stack."""
    import json
    from pathlib import Path

    path = Path(__file__).resolve().parents[1] / "BENCH_disagg.json"
    if not path.exists():
        return [("fig-stage: BENCH_disagg.json present "
                 "(run benchmarks.disagg first)", False)]
    rows = json.loads(path.read_text())["disagg"]["disaggregated"]
    mechs = sorted(rows)
    walls = {m: rows[m].get("stage_walls_s", {}) for m in mechs}
    for m in mechs:
        for stage, v in sorted(walls[m].items()):
            emit(f"figstage/{m}/{stage}", v * 1e6)

    stacked = {
        m: {k: v for k, v in walls[m].items()
            if k not in ("request", "submit", "engine.step", "engine.admit",
                         "engine.prefill") and v > 0}
        for m in mechs
    }
    claims = [
        ("fig-stage: every mechanism exports traced stage walls",
         all(walls[m] for m in mechs)),
        ("fig-stage: every mechanism has a handoff span wall",
         all(walls[m].get("engine.handoff", 0.0) > 0 for m in mechs)),
        ("fig-stage: every mechanism has prefill + decode span walls",
         all(any(k.startswith("prefill.") for k in walls[m])
             and "engine.harvest" in walls[m] for m in mechs)),
        ("fig-stage: stage vocabularies agree across mechanisms",
         len({frozenset(stacked[m]) for m in mechs}) == 1),
    ]
    _plot_stage_breakdown(stacked, mechs, path.parent / "BENCH_stages.png")
    return claims


def _plot_stage_breakdown(stacked, mechs, out_path):
    """Stacked-bar render (skipped when matplotlib is unavailable — the
    claims above carry the validation either way)."""
    try:
        import matplotlib

        matplotlib.use("Agg")
        import matplotlib.pyplot as plt
    except ImportError:
        return
    stages = sorted({k for m in mechs for k in stacked[m]})
    fig, ax = plt.subplots(figsize=(7, 4))
    bottom = [0.0] * len(mechs)
    for stage in stages:
        vals = [stacked[m].get(stage, 0.0) * 1e3 for m in mechs]
        ax.bar(mechs, vals, bottom=bottom, label=stage)
        bottom = [b + v for b, v in zip(bottom, vals)]
    ax.set_ylabel("summed span wall (ms)")
    ax.set_title("Per-stage breakdown by KV-transfer mechanism "
                 "(benchmarks/disagg.py traced drains)")
    ax.legend(fontsize=8)
    ax.grid(True, axis="y", alpha=0.3)
    fig.tight_layout()
    fig.savefig(out_path, dpi=120)
    plt.close(fig)


def _plot_prefix_sweep(rows, rates, out_path):
    """Three-panel hit-rate sweep plot (skipped when matplotlib is
    unavailable — the claims above carry the validation either way)."""
    try:
        import matplotlib

        matplotlib.use("Agg")
        import matplotlib.pyplot as plt
    except ImportError:
        return
    x = [rows[k]["hit_rate"] for k in rates]
    panels = [
        ("prefill_tokens_uncached", 1, "uncached prefill tokens"),
        ("handoff_wire_bytes", 1e-3, "handoff wire KB"),
        ("ttft_p99_s", 1e3, "p99 TTFT (ms)"),
    ]
    fig, axes = plt.subplots(1, 3, figsize=(10, 3.2))
    for ax, (field, scale, label) in zip(axes, panels):
        ax.plot(x, [rows[k][field] * scale for k in rates], "o-")
        ax.set_xlabel("prefix hit rate")
        ax.set_ylabel(label)
        ax.grid(True, alpha=0.3)
    fig.suptitle("Shared-prefix paged KV reuse (benchmarks/prefix.py)")
    fig.tight_layout()
    fig.savefig(out_path, dpi=120)
    plt.close(fig)


ALL_FIGURES = [
    fig05_transport_single_client,
    fig_prefix_hit_rate_sweep,
    fig_stage_breakdown,
    fig06_breakdown,
    fig07_overhead_vs_local,
    fig08_stage_fractions,
    fig09_cpu_usage,
    fig10_proxied_single,
    fig11_scalability,
    fig12_13_breakdown_scaling,
    fig14_proxied_scaling,
    fig15_concurrency_limit,
    fig16_priority,
    fig17_sharing_modes,
]
