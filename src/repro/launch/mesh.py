"""Production meshes. v5e pod = 16x16 = 256 chips; multi-pod = 2 pods.

Every mesh constructor here is a FUNCTION so importing this module never
touches jax device state (the dry-run must set XLA_FLAGS before the first
jax call). Four mesh families:

* :func:`make_production_mesh` — the full TPU mesh the dry-run/roofline
  lower against ('pod' x 'data' x 'model' when multi-pod).
* :func:`make_local_mesh` — single-device stand-in with the same axis
  names (CPU tests/examples).
* :func:`make_serving_pod_mesh` — the 1-D ('pod',) mesh the
  disaggregated serving tier runs on: prefill and decode stages sit on
  opposite ends of this axis, the KV handoff collective permutes across
  it, and ``serving.disagg.PodPlacement`` carves per-stage compute
  slices out of it (via ``sharding.partition.pod_slice_mesh``).
* :func:`make_cluster_mesh` — the 1-D ('pod',) mesh a multi-replica
  serving cluster carves into per-replica slices
  (``serving.cluster.ServingCluster``): replica i owns pods
  [i*ppr, (i+1)*ppr) and commits its engine's params/state there, so
  replicas are genuinely independent failure/queueing domains on a
  multi-device backend.
"""

from __future__ import annotations

import jax
import numpy as np
from jax.sharding import AxisType

# TPU v5e hardware constants (per chip) used by the roofline analysis.
PEAK_FLOPS_BF16 = 197e12  # FLOP/s
HBM_BW = 819e9  # B/s
ICI_BW = 50e9  # B/s per link


def _mesh(shape, axes):
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def _serving_devices(need: int, what: str) -> list:
    """The first ``need`` devices, or fewer on the CPU backend only.

    Tests run the multi-pod tiers on one CPU device, where the pod axis
    clamps to what exists and stages share it. On an accelerator that
    clamp would stack stages or replicas onto one chip without a word,
    so asking for more chips than there are raises instead.
    """
    avail = jax.devices()
    if need <= len(avail):
        return avail[:need]
    if jax.default_backend() == "cpu":
        return avail
    raise ValueError(
        f"{what} needs {need} {avail[0].platform} devices; "
        f"{len(avail)} present"
    )


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _mesh(shape, axes)


def make_local_mesh():
    """Single-device mesh with the same axis names (CPU tests/examples)."""
    return _mesh((1, 1), ("data", "model"))


def make_serving_pod_mesh(npods=None):
    """('pod',)-axis serving mesh over the first ``npods`` devices.

    Defaults to 2 pods. On the CPU backend the default clamps to the
    devices present, down to the 1-pod degenerate mesh (every collective
    becomes an identity permute, so the full disaggregated tier still runs
    in single-device tests); on an accelerator it raises when fewer than
    two chips exist. An explicit ``npods`` beyond the devices present
    raises on every backend. Re-exported as ``repro.serving.make_pod_mesh``.
    """
    from jax.sharding import Mesh

    if npods is None:
        devs = _serving_devices(2, "the default 2-pod serving mesh")
    else:
        avail = jax.devices()
        if npods > len(avail):
            raise ValueError(f"npods {npods} > available devices {len(avail)}")
        devs = avail[:npods]
    return Mesh(np.asarray(devs), ("pod",))


def make_cluster_mesh(n_replicas: int, pods_per_replica: int = 1):
    """('pod',)-axis mesh for an ``n_replicas``-replica serving cluster.

    The pod axis spans ``n_replicas * pods_per_replica`` slots —
    ``pods_per_replica`` is 1 for fused-engine replicas and 2 for
    disaggregated (prefill pod + decode pod) replicas. When the CPU
    backend has fewer devices than slots, the axis clamps to what exists
    and the cluster's replica slices overlap modulo the axis (the
    degenerate single-device case runs every replica on one CPU, which is
    what lets the full cluster tier execute in tier-1 tests); on an
    accelerator too few chips raise. With enough devices every replica
    owns a disjoint slice.
    """
    from jax.sharding import Mesh

    if n_replicas < 1 or pods_per_replica < 1:
        raise ValueError(
            f"need n_replicas >= 1 and pods_per_replica >= 1: "
            f"({n_replicas}, {pods_per_replica})"
        )
    devs = _serving_devices(
        n_replicas * pods_per_replica,
        f"a {n_replicas}-replica cluster of {pods_per_replica} pod(s) each",
    )
    return Mesh(np.asarray(devs), ("pod",))
