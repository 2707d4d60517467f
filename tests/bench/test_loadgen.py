"""The load generator and the client-side stamps of the benchmark."""

from __future__ import annotations

import time
from types import SimpleNamespace

import numpy as np
import pytest

from bench import client, harness, loadgen
from tinycell import tiny_root  # noqa: F401  (fixture)

MIX = {
    "arrivals": {"process": "poisson", "rate_rps": 4.0}, "shape_seed": 5,
    "prompt_len": {"dist": "lognormal", "median": 512, "sigma": 0.7,
                   "min": 128, "max": 2048},
    "output_len": {"dist": "uniform", "min": 16, "max": 64},
}


def test_same_seed_same_schedule_and_tokens():
    a = loadgen.schedule(MIX, 2**31 + 77, 20.0, 49152)
    b = loadgen.schedule(MIX, 2**31 + 77, 20.0, 49152)
    assert [x.t for x in a] == [x.t for x in b]
    assert [x.max_new for x in a] == [x.max_new for x in b]
    assert all(np.array_equal(x.prompt, y.prompt) for x, y in zip(a, b))


def test_seeds_reorder_the_same_work():
    a = loadgen.schedule(MIX, 1, 20.0, 49152)
    b = loadgen.schedule(MIX, 2, 20.0, 49152)
    assert len(a) == len(b) == 80
    assert sorted(len(x.prompt) for x in a) == sorted(len(x.prompt) for x in b)
    assert sorted(x.max_new for x in a) == sorted(x.max_new for x in b)
    assert [x.t for x in a] != [x.t for x in b]
    gaps = lambda s: sorted(np.round(np.diff([x.t for x in s] + [20.0]), 9))
    assert gaps(a) == gaps(b)
    # one circle, cut elsewhere: each request keeps its lengths and the gap
    # to its successor
    ring = lambda s: [(len(x.prompt), x.max_new, g) for x, g in zip(
        s, np.round(np.diff([x.t for x in s] + [20.0]), 9))]
    ra, rb = ring(a), ring(b)
    assert any(rb == ra[k:] + ra[:k] for k in range(1, len(ra)))


def test_lengths_respect_the_mix():
    s = loadgen.schedule(MIX, 3, 50.0, 49152)
    lens = np.asarray([len(x.prompt) for x in s])
    outs = np.asarray([x.max_new for x in s])
    assert lens.min() >= 128 and lens.max() <= 2048
    assert 380 < np.median(lens) < 680
    assert outs.min() >= 16 and outs.max() <= 64
    assert s[0].t == 0.0 and all(x.t < 50.0 for x in s)
    assert all(0 <= x.prompt.min() and x.prompt.max() < 49152 for x in s)


def _seen(due, stamps):
    """A request due at ``due`` whose tokens came at ``stamps``."""
    req = SimpleNamespace(generated=[])
    s = client.Seen(due, due, req, len(stamps))
    cl = client.Client()
    cl._open = [s]
    for t in stamps:
        req.generated.append(0)
        cl.observe(t)
    return s


def test_ttft_runs_from_the_due_time():
    s = _seen(10.0, [10.5, 10.6])
    s.submitted = 10.3  # sent late: the lateness counts
    assert client.ttft_s([s], 20.0)[0] == pytest.approx(0.5)


def test_unfinished_request_counts_with_its_wait():
    waiting = client.Seen(due=12.0, submitted=12.0, req=None, max_new=8)
    done = _seen(10.0, [10.25])
    assert client.ttft_s([done, waiting], 15.0).tolist() == pytest.approx(
        [0.25, 3.0])


def test_tpot_is_one_ratio_over_the_window():
    a = _seen(0.0, [1.0, 1.1, 1.2, 1.3])  # 3 gaps over 0.3 s
    b = _seen(0.0, [2.0, 3.0])  # 1 gap of 1 s
    assert client.tpot_s([a, b]) == pytest.approx(1.3 / 4)
    # tokens that arrive together are gaps of 0
    c = _seen(0.0, [5.0, 5.0, 5.5])
    assert client.tpot_s([c]) == pytest.approx(0.25)


class _Modeled:
    """A transport profile whose modeled wires each take a minute."""

    def wire_time(self, transport, nbytes):
        return 60.0

    def copy_time(self, nbytes):
        return 60.0


def test_no_modeled_stage_reaches_the_metrics(tiny_root):
    """The engine charges a minute of modeled wire to every request; the
    client-side numbers never see it, while the engine's own response
    latencies do."""
    import jax

    from bench import manifest
    from repro.core.transport import Transport
    from repro.models import Model
    from repro.serving import ServingEngine

    cell = manifest.load_cell("tiny-fused", tiny_root, tiny_root / "bench")
    block = manifest.load_block(cell.config, tiny_root / "bench")
    model = Model(harness.model_config(cell.config, block))
    params = block.weights.make_params(cell.config, 4)
    dep = cell.deployment
    eng = ServingEngine(model, params, **harness.engine_options(dep),
                        transport=Transport.TCP, profile=_Modeled())
    jax.block_until_ready(params)
    sched = loadgen.schedule(cell.traffic, 9, 2.0, cell.config["vocab_size"])
    responses = []
    step = eng.step
    eng.step = lambda: responses.extend(step()) or []
    cl, t0, t_end, failed = harness.serve_window(eng, sched, 2.0,
                                                 harness._null)
    assert failed == 0 and any(s.finished for s in cl.seen)
    assert all(r.stage_s["request"] == 60.0 for r in eng._records.values())
    assert any(r.ttft_s > 60.0 for r in responses)
    m = harness.end_to_end(cl, t_end, 1.0)
    assert m["ttft_p95_ms"]["value"] < (t_end - t0) * 1e3
    assert m["tpot_ms"]["value"] < (t_end - t0) * 1e3


def test_window_stamps_on_the_harness_clock(tiny_root):
    """Every stamp lies inside the window, each request's first token comes
    after it was due, and tokens never outrun the budget."""
    import jax

    from bench import manifest
    from repro.models import Model
    from repro.serving import ServingEngine

    cell = manifest.load_cell("tiny-fused", tiny_root, tiny_root / "bench")
    block = manifest.load_block(cell.config, tiny_root / "bench")
    model = Model(harness.model_config(cell.config, block))
    params = jax.block_until_ready(block.weights.make_params(cell.config, 5))
    dep = cell.deployment
    eng = ServingEngine(model, params, **harness.engine_options(dep))
    sched = loadgen.schedule(cell.traffic, 6, 2.0, cell.config["vocab_size"])
    before = time.perf_counter()
    cl, t0, t_end, _ = harness.serve_window(eng, sched, 2.0, harness._null)
    assert before <= t0 and t_end - t0 >= 2.0
    for s in cl.seen:
        assert t0 <= s.due < t_end
        if s.first is not None:
            assert s.due <= s.first <= s.last <= t_end
            assert s.n <= s.max_new


def test_closed_loop_keeps_its_clients_busy(tiny_root):
    """A closed loop of two clients: a request is due when it is sent, and
    never more than two are open at once."""
    import jax

    from bench import manifest
    from repro.models import Model

    cell = manifest.load_cell("tiny-fused", tiny_root, tiny_root / "bench")
    mix = dict(cell.traffic, arrivals={"process": "closed", "clients": 2,
                                       "rate_rps": 400.0})
    assert loadgen.clients(mix) == 2
    sched = loadgen.schedule(mix, 8, 2.0, cell.config["vocab_size"])
    assert len(sched) == 800 and all(a.t == 0.0 for a in sched)
    block = manifest.load_block(cell.config, tiny_root / "bench")
    model = Model(harness.model_config(cell.config, block))
    params = jax.block_until_ready(block.weights.make_params(cell.config, 5))
    front, _, _ = harness.build(cell.deployment, model, params)
    cl, t0, t_end, failed = harness.serve_window(front, sched, 2.0,
                                                 harness._null, clients=2)
    done = [s for s in cl.seen if s.finished]
    assert failed == 0 and len(done) >= 4 and len(cl.seen) < len(sched)
    for s in cl.seen:
        assert s.due <= s.submitted
        still_open = [o for o in cl.seen if o.submitted < s.submitted
                      and not (o.finished and o.last <= s.due)]
        assert len(still_open) < 2
