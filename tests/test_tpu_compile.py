"""The SDC kernels compile for a TPU v5e chip, at serving widths.

No chip is needed: the TPU compiler is installed with JAX and compiles
for a described topology. Each test compiles one kernel variant of the
main serving path (scan, IVF/HNSW gather, bi-granular rerank) at code
dim 128 over a 2**20-document corpus or 1024 lists of 1024, and checks
that the compiled program holds the Pallas kernel (``tpu_custom_call``)
— what interpret-mode tests cannot see: block shapes that break the
(8, 128) rule, ops Mosaic cannot lower, VMEM overruns, and XLA copies
of the corpus in front of the kernel.

The topology is described inside a module fixture (never at import),
so only the worker that runs this file loads the TPU library.
"""

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.sdc.gather import sdc_gather_topk
from repro.kernels.sdc.ops import sdc_search
from repro.kernels.sdc.rerank import sdc_rerank

Q, D, N = 128, 128, 2**20
NLIST, L, NPROBE = 1024, 1024, 32
LEVELS, K = 4, 10


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")
        # A compile for a described chip is written to the persistent
        # cache but cannot be read back without one: keep it off.
        was_on = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        compilation_cache.reset_cache()
        try:
            try:
                topo = topologies.get_topology_desc(
                    platform="tpu", topology_name="v5e:2x2"
                )
            except Exception as e:  # noqa: BLE001 — no TPU compiler here
                pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
            yield SingleDeviceSharding(topo.devices[0])
        finally:
            jax.config.update("jax_enable_compilation_cache", was_on)


def _shape(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _codes(sharding, rows, packed):
    if packed:
        return _shape(sharding, rows + (D // 2,), jnp.uint8)
    return _shape(sharding, rows + (D,), jnp.int8)


def _assert_kernel(fn, *args):
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


@pytest.mark.parametrize("q_rows", [8, Q])
@pytest.mark.parametrize("packed", [False, True], ids=["unpacked", "packed"])
def test_flat_scan_compiles(one_chip, packed, q_rows):
    """The served flat scan compiles with no corpus-sized temporary: the
    packed corpus, [D//2, N] with documents along lanes, reaches the
    kernel as it is stored (a corpus stored a document a row, half a
    vreg wide, was relaid out to 128 lanes in front of every call)."""
    corpus = (_shape(one_chip, (D // 2, N), jnp.uint8) if packed
              else _codes(one_chip, (N,), False))
    compiled = _assert_kernel(
        lambda q, d, inv: sdc_search(q, d, inv, n_levels=LEVELS, k=K,
                                     packed=packed),
        _shape(one_chip, (q_rows, D), jnp.int8),
        corpus,
        _shape(one_chip, (N,), jnp.float32),
    )
    assert compiled.memory_analysis().temp_size_in_bytes < N


@pytest.mark.parametrize("masked", [False, True], ids=["ivf", "masked"])
def test_gather_compiles(one_chip, masked):
    args = [
        _shape(one_chip, (Q, D), jnp.int8),
        _codes(one_chip, (NLIST, L), False),
        _shape(one_chip, (NLIST, L), jnp.float32),
        _shape(one_chip, (NLIST, L), jnp.int32),
        _shape(one_chip, (Q, NPROBE), jnp.int32),
    ]
    if masked:
        args.append(_shape(one_chip, (Q, NPROBE, L), jnp.float32))

    def gather(q, codes, inv, ids, probes, *mask):
        return sdc_gather_topk(q, codes, inv, ids, probes, n_levels=LEVELS,
                               k=K, cand_mask=mask[0] if mask else None)

    _assert_kernel(gather, *args)


def test_rerank_compiles(one_chip):
    _assert_kernel(
        lambda q, fine, inv, cand: sdc_rerank(q, fine, inv, cand,
                                              n_levels=LEVELS, k=K),
        _shape(one_chip, (Q, D), jnp.int8),
        _codes(one_chip, (N,), False),
        _shape(one_chip, (N,), jnp.float32),
        _shape(one_chip, (Q, 100), jnp.int32),
    )
