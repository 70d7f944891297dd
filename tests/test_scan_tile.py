"""The fused scan's query tile follows the request (``defaults.scan_block_q``).

The rule is the smallest multiple of 8 rows that holds the request, capped
at ``BLOCK_Q``. Every served flat scan (``FlatSDC``, the engine's leaves)
uses it unless an explicit ``block_q`` or a scan ``BlockPlan`` says
otherwise; the kernel is named by its tile (``sdc_topk_q<rows>``), which
is what these tests read from the traced program. Tiles only shape the
launch, so the derived tile returns the same bits as the 8-row one.
"""

import os
import re
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh

from repro.index.engine import make_distributed_search, make_failover_search
from repro.index.flat import FlatSDC
from repro.kernels.sdc.defaults import BLOCK_Q, BlockPlan, scan_block_q

SRC = os.path.join(os.path.dirname(__file__), "..", "src")
D, LEVELS, K = 64, 4, 10
TILE = re.compile(r"sdc_topk_q(\d+)")


@pytest.mark.parametrize(
    "q_rows,tile",
    [(1, 8), (8, 8), (9, 16), (100, 104), (128, 128), (129, 128), (256, 128)],
)
def test_scan_block_q_rule(q_rows, tile):
    assert scan_block_q(q_rows) == tile
    assert scan_block_q(q_rows) <= BLOCK_Q


def _codes(seed, rows):
    key = jax.random.PRNGKey(seed)
    return jax.random.randint(key, (rows, D), 0, 2**LEVELS).astype(jnp.int8)


def _tiles(fn, *args):
    """Query tiles of the fused scan kernels in ``fn``'s traced program."""
    text = str(jax.make_jaxpr(fn)(*args))
    return sorted({int(t) for t in TILE.findall(text)})


@pytest.mark.parametrize("q_rows", [8, 100, 128])
@pytest.mark.parametrize(
    "kwargs,want",
    [({}, None), ({"block_q": 16}, 16),
     ({"block_plan": BlockPlan("scan", 8, 512, "tuned")}, 8)],
    ids=["derived", "explicit", "plan"],
)
def test_flat_scan_tile(q_rows, kwargs, want):
    index = FlatSDC.build(_codes(1, 1024), LEVELS, packed=True,
                          backend="interpret")
    q = _codes(2, q_rows)
    got = _tiles(lambda x: index.search(x, K, **kwargs), q)
    assert got == [want or scan_block_q(q_rows)]


@pytest.mark.parametrize("make", [make_distributed_search,
                                  make_failover_search],
                         ids=["plain", "failover"])
@pytest.mark.parametrize(
    "kwargs,want",
    [({}, 8), ({"block_q": 32}, 32),
     ({"block_plan": BlockPlan("scan", 128, 512, "tuned")}, 128)],
    ids=["derived", "explicit", "plan"],
)
def test_engine_leaf_tile(make, kwargs, want):
    mesh = Mesh(np.array(jax.devices()[:1]).reshape(1, 1), ("data", "model"))
    search = make(mesh, n_levels=LEVELS, k=K, backend="interpret",
                  **kwargs)
    codes = _codes(1, 1024)
    args = [_codes(2, 8), codes, jnp.ones((1024,), jnp.float32)]
    if make is make_failover_search:
        args.append(jnp.ones((1,), bool))
    assert _tiles(search, *args) == [want]


def test_flat_derived_tile_bit_identical_to_8_rows():
    index = FlatSDC.build(_codes(3, 2048), LEVELS, packed=True,
                          backend="interpret")
    q = _codes(4, 128)
    v_rule, i_rule = index.search(q, K)
    v_8, i_8 = index.search(q, K, block_q=8)
    np.testing.assert_array_equal(np.asarray(i_rule), np.asarray(i_8))
    np.testing.assert_array_equal(np.asarray(v_rule), np.asarray(v_8))


def test_engine_derived_tile_bit_identical_to_8_rows():
    code = """
        import jax, jax.numpy as jnp, numpy as np
        from repro.core.binarize_lib import pack_codes_nibbles
        from repro.index.engine import (engine_input_shardings,
                                        make_distributed_search)
        from repro.kernels.sdc import ref as R
        key = jax.random.PRNGKey(5)
        codes = jax.random.randint(key, (4096, 64), 0, 16).astype(jnp.int8)
        q = jax.random.randint(jax.random.fold_in(key, 1), (128, 64), 0,
                               16).astype(jnp.int8)
        mesh = jax.make_mesh((4, 2), ("data", "model"))
        qs, ds, vs = engine_input_shardings(mesh)
        args = (jax.device_put(q, qs),
                jax.device_put(pack_codes_nibbles(codes), ds),
                jax.device_put(R.doc_inv_norms(codes, 4), vs))
        outs = [make_distributed_search(mesh, n_levels=4, k=10,
                                        backend="interpret", packed=True,
                                        **kw)(*args)
                for kw in ({}, {"block_q": 8})]
        (v_rule, i_rule), (v_8, i_8) = [tuple(map(np.asarray, o))
                                        for o in outs]
        np.testing.assert_array_equal(i_rule, i_8)
        np.testing.assert_array_equal(v_rule, v_8)
        assert (i_rule >= 0).all()
        print("OK")
    """
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = SRC
    out = subprocess.run(
        [sys.executable, "-c", textwrap.dedent(code)],
        capture_output=True, text=True, env=env, timeout=500,
    )
    assert out.returncode == 0, out.stderr[-3000:]
    assert "OK" in out.stdout
