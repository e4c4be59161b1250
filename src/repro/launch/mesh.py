"""Production mesh construction.

A FUNCTION (not a module-level constant) so importing this module never
touches jax device state; the dry-run sets
``XLA_FLAGS=--xla_force_host_platform_device_count=512`` before calling.

Multi-host (DESIGN.md §16): :func:`init_distributed` brings up
``jax.distributed`` (gloo CPU collectives when running multi-process on
CPU), :func:`make_camr_mesh` builds the 1-D CAMR device axis over the
GLOBAL device list in the class-major host-block order the two-level
lowering assumes (host of device ``s`` = ``s // (K/hosts)``), and
:func:`detect_topology` derives a :class:`~repro.core.schedule.Topology`
from the process layout.
"""

from __future__ import annotations

import jax

from repro.compat import make_mesh
from repro.core.schedule import Topology

__all__ = ["make_production_mesh", "data_axes", "mesh_devices",
           "init_distributed", "make_camr_mesh", "device_shortfall",
           "detect_topology", "host_membership"]


def make_production_mesh(*, multi_pod: bool = False):
    """Single pod: (16, 16) ('data', 'model') = 256 chips.
    Multi-pod: (2, 16, 16) ('pod', 'data', 'model') = 512 chips; the
    'pod' axis carries only data parallelism + cross-pod gradient
    reduction (DCN-friendly), never layer-internal collectives."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def data_axes(mesh) -> tuple[str, ...]:
    """Mesh axes carrying the batch dimension."""
    return ("pod", "data") if "pod" in mesh.axis_names else ("data",)


def mesh_devices(mesh) -> int:
    return mesh.devices.size


# --------------------------------------------------------------------- #
# multi-host execution (DESIGN.md §16)
# --------------------------------------------------------------------- #
def init_distributed(*, coordinator: str | None = None,
                     num_processes: int | None = None,
                     process_id: int | None = None) -> bool:
    """Bring up ``jax.distributed`` for multi-process execution.

    On a CPU backend, multi-process collectives need a cross-host
    implementation — request gloo before initialize. Returns True when
    the distributed runtime is (now) initialized, False when this
    build/environment cannot (single-process fallback) — callers
    degrade to the flat single-process lane rather than crash, and the
    subprocess smoke test (tests/test_distributed.py) skips on False.

    MUST run before anything touches a backend: ``initialize`` rejects
    an already-materialized XLA client, so this function deliberately
    avoids ``jax.default_backend()`` / ``jax.process_count()`` on the
    init path (both instantiate the backend) and gates purely on
    exceptions.
    """
    # only meaningful for CPU backends; setting it is side-effect free
    # elsewhere and must NOT query the backend to find out
    jax.config.update("jax_cpu_collectives_implementation", "gloo")
    try:
        jax.distributed.initialize(coordinator_address=coordinator,
                                   num_processes=num_processes,
                                   process_id=process_id)
        return True
    except RuntimeError:
        # already initialized (e.g. by the launcher): report what is
        return jax.process_count() > 1
    except Exception:
        return False


def make_camr_mesh(K: int, *, axis_name: str = "camr"):
    """The 1-D CAMR mesh over the GLOBAL device list (all processes).

    ``jax.devices()`` orders devices process-major, which IS the
    class-major host-block order the two-level lowering assumes: with
    ``dph`` local devices per process, device ``s`` lives on host
    ``s // dph`` — exactly ``Topology.host_of``. Built through the
    ``compat`` shim like every other mesh in the repo.
    """
    devs = jax.devices()
    if len(devs) < K:
        raise ValueError(device_shortfall(K, len(devs)))
    return make_mesh((K,), (axis_name,), devices=devs[:K])


def device_shortfall(K: int, have: int) -> str:
    """Why a ``K = q*k``-worker shuffle cannot run on ``have`` devices,
    and what to do about it on this platform."""
    platform = jax.devices()[0].platform
    msg = (f"a {K}-worker (q*k) CAMR shuffle needs {K} devices, one "
           f"worker per device; this process sees {have} {platform} "
           "device(s).")
    if platform == "cpu":
        return msg + (" On CPU set XLA_FLAGS=--xla_force_host_platform_"
                      f"device_count={K} before importing jax.")
    return msg + (" Folding several workers onto one chip is not built "
                  "yet (ROADMAP Reach item 1).")


def detect_topology(k: int, *, alpha: float = 4.0) -> Topology:
    """Topology implied by the process layout: ``jax.process_count()``
    hosts when that divides ``k`` (two-level, class-major blocks),
    otherwise flat. ``alpha`` is the modeled inter/intra cost ratio for
    the per-edge accounting — it never changes the executed values.
    """
    hosts = jax.process_count()
    if hosts > 1 and k % hosts == 0:
        return Topology.two_level(hosts, alpha=alpha)
    return Topology.flat()


def host_membership(q: int, k: int, *, alpha: float = 4.0,
                    max_failed_hosts: int | None = None):
    """The launch-time fault-domain tracker for this process layout
    (DESIGN.md §17), or ``None`` when the layout is flat (no host
    blocks to lose). Feed ``kill_host``/``current_topology`` into
    ``ShuffleStream.set_topology`` on the recovery path; pre-pay the
    survivor lowerings with ``ShuffleStream.warm_host_survivors``.
    """
    from repro.runtime.fault import HostMembership
    topo = detect_topology(k, alpha=alpha)
    if topo.is_flat:
        return None
    return HostMembership(q, k, topo,
                          max_failed_hosts=max_failed_hosts)
