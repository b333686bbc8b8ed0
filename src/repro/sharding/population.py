"""Population-axis sharding for stacked ScoreGraph scoring.

The jitted batched scorer (``proxies.make_scorer``) is elementwise over
its leading population axis — every row is one placement's ScoreGraph plus
its per-row normalizer/weight vectors.  That makes device parallelism a
pure data partition: :func:`shard_scorer` wraps a compiled scorer with
``shard_map`` over a 1-D ``"pop"`` mesh so each device scores its slice of
the stacked batch, with no cross-device collectives at all.

Rows are padded (by repeating row 0) to a multiple of the device count
and the padding is sliced off on the way out, so any batch size works.
On a single device the wrapper runs the *same* per-row computation on the
same data — bit-for-bit identical to the unwrapped scorer (pinned by
``tests/test_design_service.py``) — which is the safe fallback
``run_sweep(shard=True)`` and the design service rely on when no
multi-device mesh exists.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P


def population_mesh(devices=None) -> Mesh:
    """1-D mesh over ``devices`` (default: all) with axis name ``"pop"``."""
    devs = list(jax.devices()) if devices is None else list(devices)
    return Mesh(np.array(devs), ("pop",))


def n_pop_devices(mesh: Mesh | None = None) -> int:
    return int((mesh or population_mesh()).devices.size)


def _per_row(v, rows: int) -> np.ndarray:
    """Broadcast a [D] runtime vector to per-row [rows, D] (already-2-D
    vectors pass through) so it shards along ``"pop"`` like the batch."""
    v = np.asarray(v, np.float32)
    if v.ndim == 1:
        v = np.broadcast_to(v, (rows,) + v.shape)
    return np.ascontiguousarray(v)


def shard_scorer(scorer, mesh: Mesh | None = None):
    """Wrap a jitted batched scorer so the population axis is split across
    ``mesh``'s devices with ``shard_map``.

    Returns ``call(batch, norms, weights) -> metrics`` with the scorer's
    signature; ``norms``/``weights`` may be single vectors or per-row
    matrices (they are always broadcast per-row before sharding, which is
    value-identical to the scorer's own internal broadcast).
    """
    mesh = mesh or population_mesh()
    n = n_pop_devices(mesh)

    # Jitted, so each padded batch shape compiles once (an eager
    # shard_map would trace and compile again on every call).
    sharded = jax.jit(jax.shard_map(
        lambda b, no, w: scorer(b, no, w), mesh=mesh,
        in_specs=(P("pop"), P("pop"), P("pop")), out_specs=P("pop"),
        check_vma=False))

    def call(batch, norms, weights):
        rows = int(np.asarray(batch["W"]).shape[0])
        norms = _per_row(norms, rows)
        weights = _per_row(weights, rows)
        pad = (-rows) % n
        if pad:
            def padrow(v):
                v = jnp.asarray(v)
                return jnp.concatenate([v, jnp.repeat(v[:1], pad, axis=0)])
            batch = {k: padrow(v) for k, v in batch.items()}
            norms = np.concatenate(
                [norms, np.repeat(norms[:1], pad, axis=0)])
            weights = np.concatenate(
                [weights, np.repeat(weights[:1], pad, axis=0)])
        out = sharded(batch, jnp.asarray(norms), jnp.asarray(weights))
        return {k: v[:rows] for k, v in out.items()}

    call.mesh = mesh
    call.n_devices = n
    return call
