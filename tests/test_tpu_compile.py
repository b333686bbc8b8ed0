"""Compile-only checks of the Pallas FW kernels for a described TPU v5e.

Interpret mode cannot see what Mosaic refuses (unaligned dynamic slices,
scoped-VMEM overruns), so the main path's kernels are compiled here, at
real padded V, for a ``v5e:2x2`` topology that is described and not
attached.  Nothing runs.  The topology is described inside a fixture:
only the worker that runs this file loads the TPU compiler.
"""
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import ops
from repro.kernels.minplus import fw_counts_pallas, fw_counts_tiled_pallas

HOMOG64_V = 432             # 272 PHYs + 2 x 80 virtual nodes
HOMOG64_VP = 512            # its padded V
MOSAIC = 'custom_call_target="tpu_custom_call"'


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # A described chip's executables can be written to the persistent
    # cache but never read back; keep them out of it.
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _compile(fn, one_chip, *shapes):
    args = [jax.ShapeDtypeStruct(s, jnp.float32, sharding=one_chip)
            for s in shapes]
    return jax.jit(fn).lower(*args).compile().as_text()


# 432 and 700 pad to 512 and 768: the pivot loop and row strips stop
# inside the padding.
@pytest.mark.parametrize("V", [HOMOG64_V, HOMOG64_VP, 700,
                               ops.FW_TILED_AUTO_V])
def test_fw_counts_vmem_compiles(one_chip, V):
    txt = _compile(lambda W: fw_counts_pallas(W, interpret=False),
                   one_chip, (8, V, V))
    assert txt.count(MOSAIC) == 1


def test_fw_counts_tiled_compiles_at_homog256(one_chip):
    txt = _compile(lambda W: fw_counts_tiled_pallas(W, interpret=False),
                   one_chip, (4, 1536, 1536))
    # 12 pivot blocks x (diagonal, row panel, column panel, outer tiles)
    assert txt.count(MOSAIC) == 4 * 1536 // 128


def test_homog64_scorer_kernels_are_fw(one_chip, monkeypatch):
    """The only Mosaic kernel in the homog64 ``fw-tiled`` scorer is the
    VMEM FW kernel compiled above."""
    from repro.core.api import make_rep
    from repro.core.chiplets import resolve_arch
    from repro.core.objective import NORM_DIM, Objective, weights_vec
    from repro.core.proxies import make_scorer
    from repro.core.topology import stack_graphs
    # This process's backend is the CPU, where ops would interpret.
    monkeypatch.setattr(ops, "_interp", lambda: False)
    arch = resolve_arch("homog64", "baseline")
    rep = make_rep(arch, "homog64")
    g = rep.score_graph(rep.random(np.random.default_rng(0)))
    batch = stack_graphs([g] * 16)
    scorer = make_scorer(rep.layout, fw_impl=ops.fw_impl_tiled, chunk=16,
                         objective=Objective())

    def spec(a):
        a = np.asarray(a)
        return jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip)

    w = weights_vec(Objective())
    txt = scorer.lower({k: spec(v) for k, v in batch.items()},
                       spec(np.ones(NORM_DIM, np.float32)),
                       spec(w)).compile().as_text()
    names = [ln.split("=", 1)[0] for ln in txt.splitlines() if MOSAIC in ln]
    assert names and all(re.search(r"fw_counts_vmem", n) for n in names)
