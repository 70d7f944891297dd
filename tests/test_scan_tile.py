"""The fused scan's tiles, and the packed corpus it streams.

The query tile follows the request (``defaults.scan_block_q``): the
smallest multiple of 8 rows that holds it, capped at ``BLOCK_Q``. The
document tile is ``defaults.BLOCK_N``. Every
served flat scan (``FlatSDC``, the engine's leaves) uses both rules
unless an explicit tile or a scan ``BlockPlan`` says otherwise; the
kernel is named by its query tile (``sdc_topk_q<rows>``), which is what
these tests read from the traced program. Tiles only shape the launch,
so every tile returns the same bits.

The packed corpus lies documents along lanes ([D//2, N] uint8), so the
served program passes it to the kernel as it is stored: no equation but
the kernel touches a corpus-sized array when N is a whole number of
tiles (a relayout copy or a pad of the corpus would).
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
from jax.extend import core as jex_core
from jax.sharding import Mesh

from repro.core.binarize_lib import coarse_codes
from repro.index.engine import make_distributed_search, make_failover_search
from repro.index.flat import BiGranularFlat, FlatSDC
from repro.kernels.sdc.defaults import (
    BLOCK_N,
    BLOCK_Q,
    BlockPlan,
    scan_block_q,
)

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


@pytest.mark.parametrize("q_rows", [1, 8, 128])
@pytest.mark.parametrize("block_n", [None, 512, 2048],
                         ids=["default-n", "n512", "n2048"])
def test_flat_derived_tile_bit_identical_to_8_rows(q_rows, block_n):
    """The derived tiles return the bits of an 8-row, 512-document tile,
    and of the unpacked int8 scan, over the documents-along-lanes
    corpus."""
    codes = _codes(3, 2048 + 300)  # a padded tail at either tile
    index = FlatSDC.build(codes, LEVELS, packed=True, backend="interpret")
    assert index.codes.shape == (D // 2, codes.shape[0])
    q = _codes(4, q_rows)
    tile = {} if block_n is None else {"block_n": block_n}
    v_rule, i_rule = index.search(q, K, **tile)
    int8 = FlatSDC.build(codes, LEVELS, backend="interpret")
    for v, i in (index.search(q, K, block_q=8, block_n=512),
                 int8.search(q, K, block_q=8)):
        np.testing.assert_array_equal(np.asarray(i_rule), np.asarray(i))
        np.testing.assert_array_equal(np.asarray(v_rule), np.asarray(v))


def test_bigranular_coarse_scan_packed_bit_identical():
    """``BiGranularFlat``'s coarse tier is a packed ``FlatSDC``: it stores
    documents along lanes and returns the unpacked coarse scan's bits,
    survivors and reranked answers alike."""
    codes = _codes(6, 2048 + 77)
    q = _codes(7, 8)
    kw = dict(coarse_levels=2, k_coarse=40, backend="interpret")
    packed = BiGranularFlat.build(codes, LEVELS, packed=True, **kw)
    plain = BiGranularFlat.build(codes, LEVELS, packed=False, **kw)
    assert packed.coarse.codes.shape == (D // 2, codes.shape[0])
    assert packed.coarse.packed and not plain.coarse.packed
    qc = coarse_codes(q, LEVELS, 2)
    for a, b in ((packed.coarse.search(qc, 40), plain.coarse.search(qc, 40)),
                 (packed.search(q, K), plain.search(q, K))):
        np.testing.assert_array_equal(np.asarray(a[1]), np.asarray(b[1]))
        np.testing.assert_array_equal(np.asarray(a[0]), np.asarray(b[0]))


def _eqns(jaxpr):
    """Every equation of a jaxpr and of the jaxprs nested in it, the
    kernels' bodies aside."""
    for eqn in jaxpr.eqns:
        yield eqn
        if eqn.primitive.name == "pallas_call":
            continue
        for v in eqn.params.values():
            for sub in v if isinstance(v, (tuple, list)) else (v,):
                if isinstance(sub, jex_core.ClosedJaxpr):
                    sub = sub.jaxpr
                if isinstance(sub, jex_core.Jaxpr):
                    yield from _eqns(sub)


def _corpus_passes(fn, args, n_docs: int):
    """Equations other than the kernel whose output holds the corpus's
    N * D/2 bytes, or one element a document (a reshape aside: it moves
    nothing)."""
    corpus_bytes = n_docs * D // 2
    found = []
    for eqn in _eqns(jax.make_jaxpr(fn)(*args).jaxpr):
        if eqn.primitive.name == "pallas_call":
            continue
        for v in eqn.outvars:
            size = int(np.prod(v.aval.shape))
            nbytes = size * v.aval.dtype.itemsize
            if nbytes >= corpus_bytes or (
                    size >= n_docs and eqn.primitive.name != "reshape"):
                found.append(f"{eqn.primitive.name} -> {v.aval}")
    return found


@pytest.mark.parametrize("q_rows", [8, 128])
def test_served_flat_scan_passes_the_corpus_as_stored(q_rows):
    n = 64 * BLOCK_N  # far more documents than query codes
    codes = _codes(8, n)
    index = FlatSDC.build(codes, LEVELS, packed=True, backend="interpret")
    assert index.codes.shape == (D // 2, n)
    assert index.codes.dtype == jnp.uint8
    assert _corpus_passes(lambda q: index.search(q, K),
                          [_codes(9, q_rows)], n) == []


@pytest.mark.parametrize("make", [make_distributed_search,
                                  make_failover_search],
                         ids=["plain", "failover"])
def test_engine_leaf_passes_the_corpus_as_stored(make):
    n = 64 * BLOCK_N  # far more documents than query codes
    mesh = Mesh(np.array(jax.devices()[:1]).reshape(1, 1), ("data", "model"))
    search = make(mesh, n_levels=LEVELS, k=K, backend="interpret",
                  packed=True)
    args = [_codes(2, 8), jnp.zeros((D // 2, n), jnp.uint8),
            jnp.ones((n,), jnp.float32)]
    if make is make_failover_search:
        args.append(jnp.ones((1,), bool))
    assert _corpus_passes(search, args, n) == []


def test_corpus_pass_guard_sees_a_relayout():
    """The guard itself: a packed corpus stored a document a row, as
    before, needs a transpose in front of the kernel, which it names."""
    n = 64 * BLOCK_N  # far more documents than query codes
    index = FlatSDC.build(_codes(8, n), LEVELS, packed=True,
                          backend="interpret")
    rows = index.codes.T  # [N, D/2], a document a row
    found = _corpus_passes(
        lambda q, c: FlatSDC(c.T, index.inv_norm, LEVELS, packed=True,
                             backend="interpret").search(q, K),
        [_codes(9, 8), rows], n)
    assert [f for f in found if f.startswith("transpose")], found


def test_engine_derived_tile_bit_identical_to_8_rows():
    code = """
        import jax, jax.numpy as jnp, numpy as np
        from repro.index.engine import (engine_input_shardings,
                                        make_distributed_search)
        from repro.kernels.sdc import ref as R
        from repro.kernels.sdc.ops import pack_scan_corpus
        key = jax.random.PRNGKey(5)
        codes = jax.random.randint(key, (4096, 64), 0, 16).astype(jnp.int8)
        q = jax.random.randint(jax.random.fold_in(key, 1), (128, 64), 0,
                               16).astype(jnp.int8)
        mesh = jax.make_mesh((4, 2), ("data", "model"))
        qs, ds, vs = engine_input_shardings(mesh, packed=True)
        args = (jax.device_put(q, qs),
                jax.device_put(pack_scan_corpus(codes), ds),
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


@pytest.mark.parametrize(
    "text,want",
    [("128:8,128 8:8", [(128, 8, None), (128, 128, None), (8, 8, None)]),
     ("8:8/512,4096 128:128/2048",
      [(8, 8, 512), (8, 8, 4096), (128, 128, 2048)])],
    ids=["query-tiles", "document-tiles"],
)
def test_tile_sweep_grammar(text, want):
    """``benchmarks/scan_tile_sweep.py --sweep`` names request sizes,
    query tiles and, after a slash, document tiles (None: the program's
    own, ``defaults.BLOCK_N``)."""
    root = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))
    if root not in sys.path:  # bare `pytest` does not add the cwd
        sys.path.insert(0, root)
    from benchmarks.scan_tile_sweep import parse_sweep

    assert parse_sweep(text) == want
