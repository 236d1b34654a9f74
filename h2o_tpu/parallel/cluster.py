"""Multi-host clustering — the deployment-layer analog of the reference's
cloud formation (`water/init/NetworkInit.java` multicast/flatfile discovery,
`h2o-k8s` headless-service DNS clouding, `h2o-hadoop-*` drivers).

On TPU, membership and transport are the JAX distributed runtime's job: every
host process calls :func:`init_cluster` with the same coordinator address
(K8s: the headless service DNS of pod 0 — exactly the `h2o-k8s` lookup
pattern), `jax.distributed.initialize` forms the "cloud", and the global mesh
then spans every chip on every host; collectives ride ICI within a slice and
DCN across slices. There is no Paxos, no heartbeat thread, no flatfile — the
coordination service owns membership, and a lost host fails the job (the
reference's frozen-membership semantics; recover via the checkpoint layer,
`backend/persist.py`)."""

from __future__ import annotations

import os

import jax

from . import mesh as meshmod


def init_cluster(coordinator_address: str | None = None,
                 num_processes: int | None = None,
                 process_id: int | None = None) -> "jax.sharding.Mesh":
    """Join (or form) the multi-host cloud, then build the global row mesh.

    With no arguments, reads the standard JAX env vars / TPU metadata (on
    Cloud TPU pods `jax.distributed.initialize()` autodetects everything —
    the analog of `h2o.init()` joining the local cloud). Returns the global
    mesh over ALL devices in the cloud; pass it to `use_mesh` or rely on it
    being installed as the default.
    """
    from ..utils import compile_cache

    if num_processes is None or num_processes > 1 or coordinator_address:
        jax.distributed.initialize(
            coordinator_address=coordinator_address,
            num_processes=num_processes,
            process_id=process_id)
    # every SPMD worker places the persistent compile cache at cloud
    # formation — a preempted-and-restarted pod replays its programs from
    # disk instead of re-paying the cold-start compile wall
    compile_cache.ensure()
    m = meshmod.make_mesh()  # all devices across all processes
    meshmod.set_mesh(m)
    return m


def cloud_size() -> int:
    """Number of host processes in the cloud (`/3/Cloud` cloud_size role)."""
    return jax.process_count()


class CloudsizeTimeoutError(RuntimeError):
    """Typed cloud-formation failure: the barrier gave up with ``seen`` of
    ``expected`` processes after ``waited_s`` — the numbers an operator
    needs to tell a mis-sized deployment from a slow-joining straggler,
    without parsing message text."""

    def __init__(self, seen: int, expected: int, waited_s: float):
        self.seen = seen
        self.expected = expected
        self.waited_s = waited_s
        super().__init__(
            f"cloud has {seen} of {expected} expected processes after "
            f"{waited_s:.1f}s — jax.distributed.initialize must be called "
            f"on every host (check the coordinator address and that all "
            f"{expected} pods are scheduled)")


def stall_till_cloudsize(n: int, timeout_s: float = 300.0) -> None:
    """Check the cloud reached ``n`` processes — the test-harness primitive
    from the reference (`TestUtil.stall_till_cloudsize`,
    `water/TestUtil.java:87-117`). There is nothing to poll for here:
    `jax.distributed.initialize()` blocks until every process joins, and
    reading ``jax.process_count()`` initializes the backend, after which
    membership is fixed for the life of the process. So a mis-sized cloud
    fails at once with the TYPED give-up (seen-vs-expected attached), not
    after sleeping out ``timeout_s`` (kept for the reference signature)."""
    seen = jax.process_count()
    if seen < n:
        raise CloudsizeTimeoutError(seen, n, 0.0)
