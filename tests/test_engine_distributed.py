"""Distributed engine + sharded training, run in a subprocess with 8 forced
host devices (device count locks at first jax init, so the main pytest
process must stay single-device)."""

import os
import subprocess
import sys
import textwrap

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


def test_distributed_engine_matches_exact():
    stdout = _run("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.index.engine import make_distributed_search, engine_input_shardings
        from repro.kernels.sdc import ref as R
        key = jax.random.PRNGKey(0)
        codes = jax.random.randint(key, (4096, 64), 0, 16).astype(jnp.int8)
        q = jax.random.randint(jax.random.fold_in(key,1), (8, 64), 0, 16).astype(jnp.int8)
        inv = R.doc_inv_norms(codes, 4)
        mesh = jax.make_mesh((4, 2), ("data", "model"))
        search = make_distributed_search(mesh, n_levels=4, k=10)
        with mesh:
            qs, ds, vs = engine_input_shardings(mesh)
            mv, mi = search(jax.device_put(q, qs), jax.device_put(codes, ds),
                            jax.device_put(inv, vs))
        ev, ei = jax.lax.top_k(R.sdc_ref(q, codes, 4), 10)
        agree = np.mean([len(set(np.asarray(mi[i])) & set(np.asarray(ei[i])))/10
                         for i in range(8)])
        print("AGREE", agree)
    """)
    assert "AGREE 1.0" in stdout


def test_sharded_lm_train_step_runs():
    stdout = _run("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.configs.registry import get_arch
        from repro.configs.cells import lm_cell
        from repro.launch.mesh import make_host_mesh
        from repro.models import transformer as tf
        from repro.train import optim, steps
        from repro.parallel import sharding as shd
        from repro.data import synthetic

        mesh = make_host_mesh((4, 2), ("data", "model"))
        cfg = get_arch("llama3.2-1b").smoke_config
        params = tf.init_params(jax.random.PRNGKey(0), cfg)
        psh = shd.lm_param_sharding(mesh, cfg)
        params = jax.device_put(params, psh)
        opt = optim.adam_init(params)
        batch = synthetic.lm_batch(0, 8, 16, cfg.vocab)
        batch = jax.device_put(batch, {k: shd.lm_batch_sharding(mesh) for k in batch})
        step = jax.jit(steps.lm_train_step(cfg, optim.AdamConfig(lr=1e-3)))
        with mesh:
            params, opt, metrics = step(params, opt, batch)
            params, opt, metrics = step(params, opt, batch)
        loss = float(metrics["loss"])
        assert np.isfinite(loss)
        print("LOSS_OK", loss)
    """)
    assert "LOSS_OK" in stdout


def test_compressed_psum_inside_shard_map():
    stdout = _run("""
        import jax, jax.numpy as jnp, numpy as np
        from jax import shard_map
        from jax.sharding import PartitionSpec as P
        from repro.train import compression as comp

        mesh = jax.make_mesh((8,), ("data",))
        grads = jax.random.normal(jax.random.PRNGKey(0), (8, 64))
        err = jnp.zeros((8, 64))

        def sync(g, e):
            mean, new_e = comp.compressed_psum({"g": g}, {"g": e}, "data")
            return mean["g"], new_e["g"]

        f = shard_map(sync, mesh=mesh, in_specs=(P("data"), P("data")),
                      out_specs=(P(), P("data")), check_vma=False)
        with mesh:
            mean, new_e = f(grads, err)
        true_mean = jnp.mean(grads, axis=0)
        err_norm = float(jnp.max(jnp.abs(mean[0] - true_mean)))
        scale = float(jnp.max(jnp.abs(grads)) / 127.0)
        assert err_norm <= scale + 1e-5, (err_norm, scale)
        print("COMPRESSED_PSUM_OK", err_norm)
    """)
    assert "COMPRESSED_PSUM_OK" in stdout
