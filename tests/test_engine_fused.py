"""Distributed engine on the unified kernel substrate, run on a CPU mesh in
a subprocess with 8 forced host devices (same pattern as
test_engine_distributed.py): the fused Pallas leaf path must match the jnp
leaf path bit-for-bit, packed streaming must not change results, and the
failover mask must still exclude dead leaves under the kernel path."""

import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest

SRC = os.path.join(os.path.dirname(__file__), "..", "src")


def _run(code: str) -> str:
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = SRC
    out = subprocess.run(
        [sys.executable, "-c", textwrap.dedent(code)],
        capture_output=True, text=True, env=env, timeout=500,
    )
    assert out.returncode == 0, out.stderr[-3000:]
    return out.stdout


@pytest.mark.parametrize(
    "mesh_shape,n_docs,excluded",
    [((4, 2), 4096, False),
     # four leaves, as the four-chip cell: 1,100 documents a leaf pads
     # each leaf's tail, and drained documents (inv 0) stay out
     ((2, 2), 4400, True)],
    ids=["8-leaves", "4-leaves-tail-excluded"],
)
def test_fused_leaf_matches_xla_leaf_and_exact(mesh_shape, n_docs, excluded):
    stdout = _run(f"""
        import jax, jax.numpy as jnp, numpy as np
        from repro.core.binarize_lib import SDC_NEG_INF
        from repro.index.engine import make_distributed_search, engine_input_shardings
        from repro.kernels.sdc import ref as R
        from repro.kernels.sdc.ops import pack_scan_corpus
        key = jax.random.PRNGKey(0)
        codes = jax.random.randint(key, ({n_docs}, 64), 0, 16).astype(jnp.int8)
        q = jax.random.randint(jax.random.fold_in(key,1), (8, 64), 0, 16).astype(jnp.int8)
        inv = R.doc_inv_norms(codes, 4)
        if {excluded}:
            inv = inv.at[::3].set(0.0)
        mesh = jax.make_mesh({mesh_shape}, ("data", "model"),
                             devices=jax.devices()[:{np.prod(mesh_shape)}])
        outs = {{}}
        with mesh:
            for name, backend, packed in [
                ("xla", "xla", False),
                ("fused", "interpret", False),       # Pallas kernel leaf
                ("fused_packed", "interpret", True), # int4 streaming leaf
                ("xla_packed", "xla", True),
            ]:
                qs, ds, vs = engine_input_shardings(mesh, packed=packed)
                d = pack_scan_corpus(codes) if packed else codes
                assert d.shape == ((32, {n_docs}) if packed else ({n_docs}, 64))
                search = make_distributed_search(
                    mesh, n_levels=4, k=10, backend=backend, packed=packed,
                    block_q=8)
                outs[name] = search(jax.device_put(q, qs),
                                    jax.device_put(d, ds),
                                    jax.device_put(inv, vs))
        base_v, base_i = map(np.asarray, outs["xla"])
        for name in ("fused", "fused_packed", "xla_packed"):
            v, i = map(np.asarray, outs[name])
            np.testing.assert_array_equal(base_v, v)
            np.testing.assert_array_equal(base_i, i)
        # ref.py: the affine identity's scores, excluded documents out,
        # ties to the lower id
        s = R.sdc_ref_affine(q, codes, 4, inv)
        ev, ei = jax.lax.top_k(jnp.where(inv[None, :] > 0, s, SDC_NEG_INF), 10)
        np.testing.assert_array_equal(base_v, np.asarray(ev))
        np.testing.assert_array_equal(base_i, np.asarray(ei))
        print("AGREE", 1.0)
    """)
    assert "AGREE 1.0" in stdout


def test_hnsw_engine_backends_agree_and_recall():
    """HNSW as a first-class engine index: one NSW graph per leaf searched
    by the batched-frontier walker; gather-kernel (interpret) and jnp
    leaves must agree bit-for-bit, packed included, with global ids and
    near-exact recall at generous ef."""
    stdout = _run("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.index.engine import (make_hnsw_search,
            hnsw_engine_shardings, hnsw_engine_inputs)
        from repro.index.hnsw_lite import build_hnsw_sharded
        from repro.kernels.sdc import ref as R
        key = jax.random.PRNGKey(0)
        codes = np.asarray(jax.random.randint(key, (2048, 64), 0, 16), np.int8)
        q = jax.random.randint(jax.random.fold_in(key,1), (8, 64), 0, 16).astype(jnp.int8)
        inv = np.asarray(R.doc_inv_norms(jnp.asarray(codes), 4))
        mesh = jax.make_mesh((4, 2), ("data", "model"))
        outs = {}
        with mesh:
            shards = hnsw_engine_shardings(mesh)
            qd = jax.device_put(q, shards[0])
            for packed in (False, True):
                sh = build_hnsw_sharded(codes, inv, n_leaves=8, n_levels=4,
                                        M=8, ef_construction=32, seed=0,
                                        packed=packed)
                ins = [jax.device_put(a, s)
                       for a, s in zip(hnsw_engine_inputs(sh), shards[1:])]
                for backend in ("xla", "interpret"):
                    search = make_hnsw_search(mesh, n_levels=4, k=10, ef=64,
                                              beam=16, backend=backend,
                                              packed=packed)
                    outs[(packed, backend)] = search(qd, *ins)
        bv, bi = map(np.asarray, outs[(False, "xla")])
        for key_ in outs:
            v, i = map(np.asarray, outs[key_])
            np.testing.assert_array_equal(bv, v)
            np.testing.assert_array_equal(bi, i)
        ev, ei = jax.lax.top_k(R.sdc_ref(q, jnp.asarray(codes), 4), 10)
        agree = np.mean([len(set(bi[i]) & set(np.asarray(ei[i])))/10
                         for i in range(8)])
        assert (bi >= 0).all() and (bi < 2048).all()
        print("AGREE", agree)
    """)
    agree = float(stdout.split("AGREE")[1].strip())
    assert agree >= 0.9, stdout


@pytest.mark.parametrize("packed", [False, True],
                         ids=["unpacked", "packed"])
def test_failover_excludes_dead_leaf_under_kernel_path(packed):
    stdout = _run(f"""
        import jax, jax.numpy as jnp, numpy as np
        from repro.index.engine import make_failover_search, engine_input_shardings
        from repro.kernels.sdc import ref as R
        from repro.kernels.sdc.ops import pack_scan_corpus
        key = jax.random.PRNGKey(0)
        codes = jax.random.randint(key, (4096, 64), 0, 16).astype(jnp.int8)
        q = jax.random.randint(jax.random.fold_in(key,1), (8, 64), 0, 16).astype(jnp.int8)
        inv = R.doc_inv_norms(codes, 4)
        mesh = jax.make_mesh((4, 2), ("data", "model"))
        search = make_failover_search(mesh, n_levels=4, k=10,
                                      backend="interpret", block_q=8,
                                      packed={packed})
        qs, ds, vs = engine_input_shardings(mesh, packed={packed})
        d = pack_scan_corpus(codes) if {packed} else codes
        with mesh:
            qd = jax.device_put(q, qs); dd = jax.device_put(d, ds)
            ivd = jax.device_put(inv, vs)
            alive = jnp.ones((8,), bool)
            v_all, i_all = search(qd, dd, ivd, alive)
            alive = alive.at[3].set(False)
            v_deg, i_deg = search(qd, dd, ivd, alive)
        ev, ei = jax.lax.top_k(R.sdc_ref(q, codes, 4), 10)
        full = np.mean([len(set(np.asarray(i_all[i]))&set(np.asarray(ei[i])))/10 for i in range(8)])
        dead_lo, dead_hi = 3*512, 4*512
        leaked = int(((np.asarray(i_deg) >= dead_lo) & (np.asarray(i_deg) < dead_hi)).sum())
        deg = np.mean([len(set(np.asarray(i_deg[i]))&set(np.asarray(ei[i])))/10 for i in range(8)])
        print("FULL", full, "DEG", deg, "LEAKED", leaked)
        assert full == 1.0 and leaked == 0 and deg >= 0.8
    """)
    assert "FULL 1.0" in stdout
