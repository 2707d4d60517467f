"""Per-pod compute placement + construction-time warmup.

The disaggregated tier must (1) commit each stage's params and compute to
its own pod slice — proven by the committed device sets of every stage's
jit outputs on a real 2-pod mesh (subprocess with 2 forced host devices;
jit placement follows committed arguments, so an output living on a slice
means the compute ran there) — while staying token-identical to the fused
engine, and (2) with ``warmup=True``, pre-trace the whole pow2 shape grid
at construction so ZERO XLA compiles happen inside the timed serving
window (asserted via ``jax.log_compiles`` capture with a positive
control)."""

import logging
import os
import subprocess
import sys
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.core.transfer import TransferMode
from repro.serving import DisaggregatedEngine, PodPlacement, ServingEngine
from repro.serving.request import Request


def _requests(cfg, lens, max_new=4, seed=7):
    rng = np.random.default_rng(seed)
    return [
        Request(
            prompt_tokens=rng.integers(0, cfg.vocab_size, s, dtype=np.int32),
            max_new_tokens=max_new,
        )
        for s in lens
    ]


def _drain(eng, cfg, lens, max_new=4, seed=7):
    reqs = _requests(cfg, lens, max_new, seed)
    for r in reqs:
        eng.submit(r, time.perf_counter())
    out = eng.run_until_drained()
    assert len(out) == len(reqs)
    return reqs, out


# --------------------------------------------------------------------- #
# PodPlacement API (degenerate 1-device mesh)
# --------------------------------------------------------------------- #
def test_pod_placement_from_mesh_degenerate():
    from repro.serving import make_pod_mesh

    mesh = make_pod_mesh()  # 1 pod on the single test device
    pl = PodPlacement.from_mesh(mesh)
    assert pl.prefill_pods == (0,)
    assert pl.decode_pods == (mesh.shape["pod"] - 1,)
    if mesh.shape["pod"] == 1:
        assert not pl.disjoint  # both stages collapse onto one device
        assert pl.prefill_devices() == pl.decode_devices()
    # slice shardings are replicated over the slice by default
    assert pl.prefill_sharding().is_fully_replicated
    assert pl.decode_sharding().is_fully_replicated


def test_pod_slice_mesh_validation():
    from repro.serving import make_pod_mesh
    from repro.sharding.partition import pod_slice_mesh

    mesh = make_pod_mesh()
    with pytest.raises(ValueError, match="empty"):
        pod_slice_mesh(mesh, ())
    with pytest.raises(ValueError, match="out of range"):
        pod_slice_mesh(mesh, (99,))
    with pytest.raises(ValueError, match="no 'nope' axis"):
        pod_slice_mesh(mesh, (0,), axis="nope")
    sub = pod_slice_mesh(mesh, (0,))
    assert sub.axis_names == mesh.axis_names
    assert sub.shape["pod"] == 1


def test_placement_mesh_mismatch_rejected(model_bank):
    from repro.serving import make_pod_mesh
    from repro.sharding.partition import pod_slice_mesh

    cfg = get_config("llama3-8b").reduced()
    model, params = model_bank(cfg)
    mesh = make_pod_mesh()
    other = pod_slice_mesh(mesh, (0,))  # equal only if mesh is 1-pod
    pl = PodPlacement.from_mesh(other)
    if other != mesh:  # only meaningful when the meshes differ
        with pytest.raises(ValueError, match="placement.mesh"):
            DisaggregatedEngine(model, params, mesh=mesh, placement=pl,
                                max_batch=1, max_seq=32)


def test_placement_default_on_tokens_identical(model_bank):
    """Default placement on the degenerate mesh: both stages committed to
    the same device, decode tokens identical to the fused engine, and the
    pool state reports the decode slice as its committed device set."""
    cfg = get_config("llama3-8b").reduced()
    model, params = model_bank(cfg, dtype=jnp.float32, seed=1)
    lens = [5, 9, 17, 26]
    kw = dict(max_batch=2, max_seq=64)
    base, _ = _drain(ServingEngine(model, params, **kw), cfg, lens)
    eng = DisaggregatedEngine(
        model, params, transfer_mode=TransferMode.DIRECT_HBM, **kw
    )
    assert eng.placement is not None  # on by default
    dis, _ = _drain(eng, cfg, lens)
    assert [r.generated for r in dis] == [r.generated for r in base]
    ddev = set(eng.placement.decode_devices())
    for leaf in jax.tree.leaves(eng.pool.caches):
        assert set(leaf.devices()) == ddev
    for leaf in jax.tree.leaves(eng.decode_params):
        assert set(leaf.devices()) == ddev
    # equal slices share ONE committed replica (no weight triplication on
    # the degenerate mesh)
    if not eng.placement.disjoint:
        assert eng.decode_params is eng.prefill_params
    # placement=False restores uncommitted params (pre-placement behavior)
    off = DisaggregatedEngine(
        model, params, transfer_mode=TransferMode.DIRECT_HBM,
        placement=False, **kw
    )
    assert off.placement is None
    assert off.prefill_params is params and off.decode_params is params




# --------------------------------------------------------------------- #
# Real 2-pod placement: subprocess with 2 forced host devices
# --------------------------------------------------------------------- #
_TWO_POD_SCRIPT = r"""
import time
import numpy as np
import jax, jax.numpy as jnp
from repro.configs import get_config
from repro.models import Model
from repro.serving import DisaggregatedEngine, ServingEngine
from repro.core.transfer import TransferMode
from repro.serving.request import Request

assert len(jax.devices()) == 2, jax.devices()
cfg = get_config("llama3-8b").reduced()
model = Model(cfg, dtype=jnp.float32)
params = model.init(jax.random.key(1))
LENS, MAX_NEW = (5, 9, 17), 3
KW = dict(max_batch=2, max_seq=32)

def drain(eng):
    rng = np.random.default_rng(7)
    rs = [Request(prompt_tokens=rng.integers(0, cfg.vocab_size, s,
                                             dtype=np.int32),
                  max_new_tokens=MAX_NEW) for s in LENS]
    for r in rs:
        eng.submit(r, time.perf_counter())
    out = eng.run_until_drained()
    assert len(out) == len(rs)
    return [r.generated for r in rs]

def devset(tree):
    return {d for leaf in jax.tree.leaves(tree) for d in leaf.devices()}

base = drain(ServingEngine(model, params, **KW))
for i, mode in enumerate((TransferMode.DIRECT_HBM, TransferMode.DIRECT_DMA)):
    eng = DisaggregatedEngine(model, params, transfer_mode=mode,
                              warmup=(i == 0), **KW)
    pl = eng.placement
    assert pl.disjoint, pl  # a genuine two-pool split
    pdev, ddev = set(pl.prefill_devices()), set(pl.decode_devices())
    assert pdev != ddev and len(pdev) == len(ddev) == 1
    # params committed per stage slice
    assert devset(eng.prefill_params) == pdev
    assert devset(eng.decode_params) == ddev
    # decode pool state committed to the decode slice
    assert devset(eng.pool.caches) == ddev
    warmed, nshapes = set(eng._xfer_warm), eng.prefill_compile_count
    toks = drain(eng)
    assert toks == base, (mode, "tokens diverged from fused engine")
    if i == 0:  # warmed engine: the serving path compiled nothing new
        assert eng._xfer_warm == warmed
        assert eng.prefill_compile_count == nshapes
    assert eng.handoffs > 0
    # step-jit outputs live on the decode slice => decode compute ran there
    assert set(eng.pool.tokens.devices()) == ddev
    assert set(eng.pool.lengths.devices()) == ddev
    assert devset(eng.pool.caches) == ddev
    # prefill-jit outputs live on the prefill slice => prefill ran there
    nt, c1, _ = eng._prefill_bucket_jit(
        eng.prefill_params,
        jnp.zeros((KW["max_batch"], 16), jnp.int32),
        jnp.ones((KW["max_batch"],), jnp.int32),
        eng.prefill_key,
    )
    assert set(nt.devices()) == pdev
    assert devset(c1) == pdev
    # and the traced step compute carries the decode slice's sharding
    seen = []
    jax.jit(lambda x: jax.debug.inspect_array_sharding(
        x, callback=seen.append) or x + 1)(eng.pool.lengths)
    assert seen and set(seen[0].device_set) == ddev, seen

# the placed tiling enumerates one device per pod slot: a mesh with a
# non-trivial second axis must be refused (pointer at placement=False),
# not crash at the first handoff
from jax.sharding import Mesh
multi = Mesh(np.asarray(jax.devices()).reshape(1, 2), ("pod", "model"))
try:
    DisaggregatedEngine(model, params, mesh=multi, **KW)
except ValueError as e:
    assert "placement=False" in str(e), e
else:
    raise AssertionError("multi-axis mesh accepted with placement on")
print("TWO_POD_PLACEMENT_OK")
"""


def test_two_pod_placement_committed_and_token_identical():
    """On 2 forced host pods, each stage's jitted compute is committed to
    its own pod slice (params, pool state, and every stage output report
    exactly that slice's device) and decode output stays token-identical
    to the fused engine under DIRECT_HBM and DIRECT_DMA — with the warmed
    engine compiling nothing inside the serving window."""
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
    src = str(Path(__file__).resolve().parents[1] / "src")
    env["PYTHONPATH"] = src + os.pathsep + env.get("PYTHONPATH", "")
    proc = subprocess.run(
        [sys.executable, "-c", _TWO_POD_SCRIPT],
        capture_output=True, text=True, env=env, timeout=1200,
    )
    assert proc.returncode == 0, f"stdout:\n{proc.stdout}\nstderr:\n{proc.stderr}"
    assert "TWO_POD_PLACEMENT_OK" in proc.stdout


# --------------------------------------------------------------------- #
# Warmup: zero compiles inside the timed serving window
# --------------------------------------------------------------------- #
class _LogGrab(logging.Handler):
    def __init__(self):
        super().__init__(level=logging.DEBUG)
        self.messages = []

    def emit(self, record):
        self.messages.append(record.getMessage())


def _compiles_during(fn):
    """Run ``fn`` under jax.log_compiles and return the XLA 'Compiling'
    log messages it emitted."""
    grab = _LogGrab()
    logger = logging.getLogger("jax")
    old_level = logger.level
    logger.addHandler(grab)
    logger.setLevel(logging.DEBUG)
    try:
        with jax.log_compiles():
            fn()
    finally:
        logger.removeHandler(grab)
        logger.setLevel(old_level)
    return [m for m in grab.messages if m.startswith("Compiling ")]


def test_warmup_zero_compiles_in_timed_window(model_bank):
    cfg = get_config("llama3-8b").reduced()
    model, params = model_bank(cfg, dtype=jnp.float32, seed=1)
    kw = dict(max_batch=2, max_seq=64)
    lens = [5, 9, 17, 26]

    # positive control: a COLD engine's drain must compile (same capture
    # machinery, fresh jit wrappers) — otherwise the zero assertion below
    # would be vacuous
    cold = DisaggregatedEngine(
        model, params, transfer_mode=TransferMode.DIRECT_HBM, **kw
    )
    assert _compiles_during(lambda: _drain(cold, cfg, lens)), \
        "log capture saw no compiles from a cold engine"

    warm = DisaggregatedEngine(
        model, params, transfer_mode=TransferMode.DIRECT_HBM, warmup=True,
        **kw
    )
    assert warm.warm_s > 0  # construction paid the grid, outside any stage
    grid = dict.fromkeys(warm.handoff_extent_grid())
    assert {(m, r, p) for (m, r, p) in warm._xfer_warm} == {
        (warm.transfer_mode, r, p) for (r, p) in grid
    }
    warmed, nshapes = set(warm._xfer_warm), warm.prefill_compile_count
    compiles = _compiles_during(lambda: _drain(warm, cfg, lens))
    assert compiles == [], f"compiled inside timed window: {compiles}"
    assert warm._xfer_warm == warmed  # no new handoff extent
    assert warm.prefill_compile_count == nshapes  # no new prefill bucket


def test_warmup_fused_engine_and_bucket_grid(model_bank):
    """ServingEngine(warmup=True): the pow2 bucket grid is pre-traced at
    construction and a drain adds no prefill shapes; bucket_grid covers
    min_bucket..max_seq."""
    cfg = get_config("llama3-8b").reduced()
    model, params = model_bank(cfg, dtype=jnp.float32, seed=1)
    eng = ServingEngine(model, params, max_batch=2, max_seq=64, warmup=True,
                        packed=False)
    assert eng.bucket_grid() == [16, 32, 64]
    assert eng.prefill_compile_count == 3
    base, _ = _drain(ServingEngine(model, params, max_batch=2, max_seq=64,
                                   packed=False),
                     cfg, [5, 40])
    out, _ = _drain(eng, cfg, [5, 40])
    assert [r.generated for r in out] == [r.generated for r in base]
    assert eng.prefill_compile_count == 3  # drain compiled nothing new


def test_warmup_noop_on_legacy(model_bank):
    cfg = get_config("llama3-8b").reduced()
    model, params = model_bank(cfg, dtype=jnp.float32, seed=1)
    eng = ServingEngine(model, params, max_batch=2, max_seq=32, legacy=True,
                        warmup=True)
    assert eng.warm_s == 0.0
    assert eng.prefill_compile_count == 0


def test_pool_reset_state_guard(model_bank):
    """reset_state refuses to wipe an occupied pool (it exists for the
    post-warmup re-zero only)."""
    cfg = get_config("llama3-8b").reduced()
    model, params = model_bank(cfg, dtype=jnp.float32, seed=1)
    eng = ServingEngine(model, params, max_batch=1, max_seq=32)
    req = _requests(cfg, [4], max_new=8)[0]
    eng.submit(req, time.perf_counter())
    eng.step()  # admits -> slot occupied
    with pytest.raises(RuntimeError, match="occupied"):
        eng.pool.reset_state()
