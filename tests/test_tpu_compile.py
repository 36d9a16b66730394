"""Compile the served path for a described TPU v5e chip, no chip attached.

The masked scan kernels go through Mosaic (the TPU kernel compiler) and
the graph phase through XLA:TPU at the shapes ``chip_smoke.py`` serves,
so a kernel the chip's compiler refuses fails here and not on the chip.
Interpret-mode tests (tests/test_kernels.py) cannot see such refusals.
"""
import functools
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core.graph_search import greedy_search
from repro.kernels import l2_topk as l2_kernels
from repro.kernels import pq_adc as pq_kernels

# rows of the PAG graph arena (aggregation points + promotions + slack)
# that chip_smoke.py's build produces at its default n
GRAPH_ROWS = 160_000
BIGANN_D = 128      # SIFT-1B's width (uint8)


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no libtpu here: nothing to compile for
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


def _pool_width(smoke, pq: bool) -> int:
    """Widest candidate pool the smoke's SearchConfig can produce: every
    probed partition full (cap = lam / p), plus the beam on the exact
    pass, rounded up to the scan block like ``ScanStage``."""
    cap = int(smoke.BUILD["lam"] / smoke.BUILD["p"])
    c = smoke.SEARCH["n_probe_max"] * cap + (0 if pq else smoke.SEARCH["L"])
    return -(-c // 256) * 256


def test_l2_topk_masked_compiles(smoke, one_chip):
    c = _pool_width(smoke, pq=False)
    s = functools.partial(jax.ShapeDtypeStruct, sharding=one_chip)
    fn = jax.jit(functools.partial(l2_kernels.l2_topk_masked, k=smoke.K,
                                   block_c=256, interpret=False))
    compiled = fn.lower(s((smoke.BATCH, smoke.D), jnp.float32),
                        s((smoke.BATCH, c, smoke.D), jnp.float32),
                        s((smoke.BATCH, c), jnp.int32)).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_pq_adc_masked_compiles(smoke, one_chip):
    c = _pool_width(smoke, pq=True)
    s = functools.partial(jax.ShapeDtypeStruct, sharding=one_chip)
    fn = jax.jit(functools.partial(pq_kernels.pq_adc_masked,
                                   k=smoke.RERANK_K, block_c=256,
                                   interpret=False))
    compiled = fn.lower(s((smoke.BATCH, smoke.PQ_M, 256), jnp.float32),
                        s((smoke.BATCH, c, smoke.PQ_M), jnp.uint8),
                        s((smoke.BATCH, c), jnp.int32)).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("dtype", [jnp.uint8, jnp.int8])
def test_l2_topk_masked_compiles_on_bigann_vectors(smoke, one_chip, dtype):
    """1-byte pools and queries at BIGANN's width reach Mosaic in their
    own type (the kernel widens them inside)."""
    c = _pool_width(smoke, pq=False)
    s = functools.partial(jax.ShapeDtypeStruct, sharding=one_chip)
    fn = jax.jit(functools.partial(l2_kernels.l2_topk_masked, k=smoke.K,
                                   block_c=256, interpret=False))
    compiled = fn.lower(s((smoke.BATCH, BIGANN_D), dtype),
                        s((smoke.BATCH, c, BIGANN_D), dtype),
                        s((smoke.BATCH, c), jnp.int32)).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_greedy_search_compiles_on_a_uint8_graph(smoke, one_chip):
    s = functools.partial(jax.ShapeDtypeStruct, sharding=one_chip)
    L = smoke.SEARCH["L"]
    greedy_search.lower(
        s((GRAPH_ROWS, BIGANN_D), jnp.uint8),
        s((GRAPH_ROWS, 18), jnp.int32),
        s((), jnp.int32), s((), jnp.int32),
        s((smoke.BATCH, BIGANN_D), jnp.uint8), L=L, K=L).compile()


def test_greedy_search_compiles(smoke, one_chip):
    s = functools.partial(jax.ShapeDtypeStruct, sharding=one_chip)
    L = smoke.SEARCH["L"]
    lowered = greedy_search.lower(
        s((GRAPH_ROWS, smoke.D), jnp.float32),
        s((GRAPH_ROWS, 18), jnp.int32),
        s((), jnp.int32), s((), jnp.int32),
        s((smoke.BATCH, smoke.D), jnp.float32), L=L, K=L)
    # the beam is merged by counting ranks: no sort in any hop
    assert "stablehlo.sort" not in lowered.as_text()
    compiled = lowered.compile()
    # the whole graph phase fits one v5e's 16 GB of HBM with room to spare
    assert compiled.memory_analysis().temp_size_in_bytes < 4 * 2 ** 30
