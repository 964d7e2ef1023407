"""AcceleratorMesh — multi-chip sharding of the crypto data plane.

SURVEY.md §2.14/§5.8: the reference's only intra-validator parallelism is a
worker thread pool; the TPU-native axis is *batch data parallelism* of the
signature-verify plane.  A verify batch is embarrassingly parallel over items,
so the sharding story is one mesh axis ("batch"): inputs sharded over chips,
no collective anywhere — the (N,) verdicts stay sharded and the host reads
one piece a chip (the four-chip v5e host's trace, PR 45: PERF.md §5).

The byzantine inter-validator plane stays on the overlay's TCP sockets —
ICI/DCN collectives cannot replace signed flooding (SURVEY.md §5.8); this
module is strictly the *inside-one-validator* scale-out.
"""

from __future__ import annotations

from typing import Optional, Sequence, Union

import numpy as np


def make_mesh(devices: Optional[Sequence] = None, axis: str = "batch"):
    """1-D device mesh over all (or given) ADDRESSABLE devices.

    The default is ``jax.local_devices()``, not ``jax.devices()``: in a
    multi-host process group the global device list includes chips this
    process cannot feed (device_put to a non-addressable device raises),
    and the verify plane's per-shard staging uploads from host memory.
    An explicit ``devices=`` still wins — callers that know their slice
    (the dryrun harness, tests) pass it directly."""
    import jax
    from jax.sharding import Mesh

    if devices is None:
        devices = jax.local_devices()
    return Mesh(np.asarray(devices), (axis,))


def mesh_from_spec(spec: Union[int, str, None], axis: str = "batch"):
    """``Config.SIG_MESH`` -> Mesh or None (the production wiring seam).

    - ``0`` / ``False`` / ``None``: off — unsharded single-queue dispatch.
    - ``"auto"``: shard over every addressable device; a single-device
      host gets None (the unsharded path IS the one-chip configuration,
      and it keeps the lane-tree batched inversion).
    - int ``n >= 1``: exactly the first n addressable devices; fewer than
      n on the host is a config error, not a silent narrower mesh — a
      validator told to run 8-wide must not quietly run 2-wide.  ``1``
      normalizes to None for the same reason "auto" does on a one-chip
      host: a 1-device mesh would trade the batched inversion for
      sharding machinery with nothing to parallelize."""
    if not spec:
        return None
    import jax

    devices = jax.local_devices()
    if spec == "auto":
        return make_mesh(devices, axis) if len(devices) > 1 else None
    n = int(spec)
    if n > len(devices):
        raise ValueError(
            f"SIG_MESH={n} but only {len(devices)} addressable "
            f"device(s); use SIG_MESH=\"auto\" to take what is there"
        )
    if n == 1:
        return None
    return make_mesh(devices[:n], axis)


def make_sharded_verifier(mesh=None, max_batch: int = 8192, **kw):
    """BatchVerifier whose kernel is sharded over the mesh's batch axis.

    On real TPU the Pallas kernel runs PER SHARD under jax.shard_map
    (each chip grids its local batch slice; the program holds no
    collective), keeping the 4x-faster kernel at multi-chip scale;
    on CPU meshes the XLA kernel (or interpreter-mode Pallas with
    backend="pallas") provides the same bit-exact semantics."""
    from ..ops.verifier import BatchVerifier

    if mesh is None:
        mesh = make_mesh()
    return BatchVerifier(max_batch=max_batch, mesh=mesh, **kw)
