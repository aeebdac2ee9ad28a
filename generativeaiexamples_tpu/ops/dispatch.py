"""Where a kernel gate looks for its device, and what it chose.

Every Pallas kernel in ``ops/`` has an XLA twin and a predicate that
picks between them while a step is being traced.  The predicates ask
two things of the arrays' placement — which platform, and whether one
device holds them — and both answers come from the mesh the scheduler
built, never from the process-wide device count: a one-device replica
on a four-chip host takes the same kernels as on a one-chip host.

``TAKEN`` records each choice (the ``ops.qmm.BLOCK_EVENTS`` idiom) so
the engine's ``/health`` and ``chip_smoke.py`` can say which path every
compiled step took rather than which one was asked for.  It is one
record for the whole process, keyed by site and shape, and the last
trace to reach a key wins: schedulers that share a process (the
replicas of an ``EnginePool``) write into the same entries, so it says
which paths were taken in this process, not by which replica.  Whoever
needs that drives one replica at a time between ``TAKEN.clear()`` calls,
as ``chip_smoke.py --four-chips`` does.
"""

from __future__ import annotations

import jax

# site -> "pallas" | "xla", written at trace time, process-wide, e.g.
# {"decode_attention b=32 w=2048": "pallas", "q_matmul wqkv m=32": "xla"}.
TAKEN: dict[str, str] = {}


def platform_of(mesh) -> str:
    """Platform of the devices a step's arrays live on: the mesh's when
    one is given, else the default device's."""
    if mesh is not None:
        return mesh.devices.flat[0].platform
    return jax.default_backend()


def one_device(mesh) -> bool:
    """True when the step's arrays live on a single device (no mesh
    means the default device)."""
    return mesh is None or mesh.size == 1


def record(site: str, pallas: bool) -> bool:
    """Note the path ``site`` takes in the step being traced."""
    TAKEN[site] = "pallas" if pallas else "xla"
    return pallas
