"""Perf hillclimbing driver (§Perf): lower named variants of the three
target cells, compare roofline terms against the baseline, log every
hypothesis -> change -> measure iteration to perf_results.json.

    PYTHONPATH=src python -m repro.launch.hillclimb --cell tt_retrieval \
        --variant bebr_sdc [--multi-pod]

Cells and variants are defined in VARIANTS below; the baselines are the
same builders launch/dryrun.py uses, so deltas are apples-to-apples.
"""

import os

os.environ["XLA_FLAGS"] = (
    "--xla_force_host_platform_device_count=512 "
    + os.environ.get("XLA_FLAGS", "")
)

# ruff: noqa: E402
import argparse
import dataclasses
import json
import time

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

# Roofline constants shared with the block-plan autotuner: one table
# (kernels/sdc/defaults.py) prices kernels for both the cost model here
# and the launch-shape sweeps.
from repro.kernels.sdc.defaults import HBM_BW, LINK_BW, N_LINKS, PEAK_INT8_OPS


def _measure(fn, in_shardings, args, mesh, n_dev):
    from repro.launch.hlo_cost import hlo_costs

    t0 = time.time()
    with mesh:
        compiled = jax.jit(fn, in_shardings=in_shardings).lower(*args).compile()
    dt = time.time() - t0
    ma = compiled.memory_analysis()
    costs = hlo_costs(compiled.as_text(), n_dev)
    wire = sum(costs["collectives"].values())
    return {
        "compile_s": round(dt, 1),
        "flops": costs["flops"],
        "bytes": costs["bytes"],
        "wire_bytes": wire,
        "collectives": costs["collectives"],
        "compute_ms": 1e3 * costs["flops"] / PEAK_INT8_OPS,
        "memory_ms": 1e3 * costs["bytes"] / HBM_BW,
        "collective_ms": 1e3 * wire / (N_LINKS * LINK_BW),
        "peak_gib": (ma.argument_size_in_bytes + ma.output_size_in_bytes
                     + ma.temp_size_in_bytes - ma.alias_size_in_bytes) / 2**30,
    }


# ---------------------------------------------------------------------------
# Cell: two-tower retrieval_cand (paper-representative).
# ---------------------------------------------------------------------------


def tt_retrieval_baseline(mesh):
    from repro.configs.registry import build_cell

    cell = build_cell("two-tower-retrieval", "retrieval_cand", mesh)
    return cell.fn, cell.in_shardings, cell.abstract_args


def tt_retrieval_float_index(mesh):
    """Production float baseline: candidates as a precomputed f32 embedding
    index (no per-query tower recompute) — the paper's 'float flat' row."""
    from repro.configs.registry import get_arch
    from repro.models.recsys import two_tower as tt
    from repro.parallel import sharding as shd
    from repro.train import steps as _steps

    cfg = get_arch("two-tower-retrieval").config
    dp = shd.dp_axes(mesh)
    params_s = jax.eval_shape(lambda: tt.init_params(jax.random.PRNGKey(0), cfg))
    param_sh = shd.fill_param_sharding(mesh, params_s,
                                       ("user_table", "item_table"))
    Nc, D = 1_000_000, cfg.tower_mlp[-1]
    batch_s = {
        "hist_ids": jax.ShapeDtypeStruct((1, cfg.hist_len), jnp.int32),
        "hist_mask": jax.ShapeDtypeStruct((1, cfg.hist_len), jnp.float32),
        "cand_emb": jax.ShapeDtypeStruct((Nc, D), jnp.float32),
    }
    batch_sh = {
        "hist_ids": NamedSharding(mesh, P(None, None)),
        "hist_mask": NamedSharding(mesh, P(None, None)),
        "cand_emb": NamedSharding(mesh, P(dp, None)),
    }

    def step(params, batch):
        from repro.models.recsys.two_tower import query_embed

        q = query_embed(params, batch["hist_ids"], batch["hist_mask"], cfg)
        scores = (batch["cand_emb"] @ q[0])[None, :]
        return jax.lax.top_k(scores, 100)

    return step, (param_sh, batch_sh), (params_s, batch_s)


def tt_retrieval_bebr(mesh, code_dim=64, n_levels=4):
    """The paper's technique AS the optimisation: int8 SDC index scan."""
    from repro.configs.registry import get_arch
    from repro.models.recsys import two_tower as tt
    from repro.parallel import sharding as shd
    from repro.train import steps

    cfg = get_arch("two-tower-retrieval").config
    dp = shd.dp_axes(mesh)
    params_s = jax.eval_shape(lambda: tt.init_params(jax.random.PRNGKey(0), cfg))
    emb_out = cfg.tower_mlp[-1]
    params_s = dict(params_s)
    params_s["binarizer"] = {
        "W": [jax.ShapeDtypeStruct((emb_out, code_dim), jnp.float32)
              for _ in range(n_levels)],
        "R": [jax.ShapeDtypeStruct((code_dim, emb_out), jnp.float32)
              for _ in range(n_levels - 1)],
    }
    param_sh = shd.fill_param_sharding(mesh, params_s,
                                       ("user_table", "item_table"))
    Nc = 1_000_000
    batch_s = {
        "hist_ids": jax.ShapeDtypeStruct((1, cfg.hist_len), jnp.int32),
        "hist_mask": jax.ShapeDtypeStruct((1, cfg.hist_len), jnp.float32),
        "cand_codes": jax.ShapeDtypeStruct((Nc, code_dim), jnp.int8),
        "cand_inv": jax.ShapeDtypeStruct((Nc,), jnp.float32),
    }
    batch_sh = {
        "hist_ids": NamedSharding(mesh, P(None, None)),
        "hist_mask": NamedSharding(mesh, P(None, None)),
        "cand_codes": NamedSharding(mesh, P(dp, None)),
        "cand_inv": NamedSharding(mesh, P(dp)),
    }
    fn = steps.tt_retrieval_bebr_step(cfg, k=100, code_dim=code_dim,
                                      n_levels=n_levels)
    return fn, (param_sh, batch_sh), (params_s, batch_s)


def tt_retrieval_bebr_full(mesh):
    """BEBR + candidates sharded over the full mesh (dp x model)."""
    from repro.configs.registry import get_arch
    from repro.models.recsys import two_tower as tt
    from repro.parallel import sharding as shd
    from repro.train import steps

    fn, (param_sh, batch_sh), (params_s, batch_s) = tt_retrieval_bebr(mesh)
    dp = shd.dp_axes(mesh)
    # 1e6 doesn't divide dp*model; pad to the next multiple
    n_all = mesh.devices.size
    Nc = 1_000_000 + (-1_000_000) % n_all
    batch_s = dict(batch_s)
    batch_s["cand_codes"] = jax.ShapeDtypeStruct((Nc, 64), jnp.int8)
    batch_s["cand_inv"] = jax.ShapeDtypeStruct((Nc,), jnp.float32)
    batch_sh = dict(batch_sh)
    batch_sh["cand_codes"] = NamedSharding(mesh, P(dp + ("model",), None))
    batch_sh["cand_inv"] = NamedSharding(mesh, P(dp + ("model",)))
    return fn, (param_sh, batch_sh), (params_s, batch_s)


def tt_retrieval_bebr_merge(mesh, code_dim=64, n_levels=4):
    """BEBR + the paper's selection merge: per-leaf top-k under shard_map,
    all-gather only k results (wire: scores array -> k entries/leaf)."""
    from jax import shard_map

    from repro.core.binarize_lib import code_affine_constants
    from repro.configs.registry import get_arch
    from repro.models.recsys import two_tower as tt
    from repro.parallel import sharding as shd

    cfg = get_arch("two-tower-retrieval").config
    fn_base, (param_sh, batch_sh), (params_s, batch_s) = tt_retrieval_bebr(mesh)
    dp = shd.dp_axes(mesh)
    a, beta = code_affine_constants(n_levels)
    k = 100

    def leaf(q_code8, cand_codes, cand_inv):
        dot = jax.lax.dot_general(
            cand_codes, q_code8[0],
            dimension_numbers=(((1,), (0,)), ((), ())),
            preferred_element_type=jnp.int32)
        sq = jnp.sum(q_code8.astype(jnp.int32))
        sd = jax.lax.dot_general(
            cand_codes, jnp.ones((cand_codes.shape[1],), jnp.int8),
            dimension_numbers=(((1,), (0,)), ((), ())),
            preferred_element_type=jnp.int32)
        scores = ((a * a) * dot.astype(jnp.float32)
                  + (a * beta) * (sq + sd).astype(jnp.float32)
                  + code_dim * beta * beta) * cand_inv
        vals, idx = jax.lax.top_k(scores, k)
        rank = jax.lax.axis_index(dp[0]) if len(dp) == 1 else (
            jax.lax.axis_index(dp[0]) * mesh.shape[dp[1]]
            + jax.lax.axis_index(dp[1]))
        gidx = idx + rank * cand_codes.shape[0]
        av = jax.lax.all_gather(vals, dp, axis=0, tiled=True)
        ai = jax.lax.all_gather(gidx, dp, axis=0, tiled=True)
        bv, pos = jax.lax.top_k(av, k)
        return bv[None], jnp.take(ai, pos)[None]

    leaf_sharded = shard_map(
        leaf, mesh=mesh,
        in_specs=(P(None, None), P(dp, None), P(dp)),
        out_specs=(P(), P()), check_vma=False)

    def step(params, batch):
        q = tt.query_embed(params, batch["hist_ids"], batch["hist_mask"], cfg)

        def sign(x):
            return jnp.where(x > 0, 1.0, -1.0)

        bp = params["binarizer"]
        f = q * jax.lax.rsqrt(jnp.sum(q * q, -1, keepdims=True) + 1e-12)
        b = sign(f @ bp["W"][0])
        acc = b
        code = (b + 1.0) * 0.5 * (2 ** (n_levels - 1))
        for t in range(n_levels - 1):
            recon = acc @ bp["R"][t]
            recon = recon * jax.lax.rsqrt(
                jnp.sum(recon * recon, -1, keepdims=True) + 1e-12)
            r = sign((f - recon) @ bp["W"][t + 1])
            acc = acc + (2.0 ** -(t + 1)) * r
            code = code + (r + 1.0) * 0.5 * (2 ** (n_levels - 2 - t))
        return leaf_sharded(code.astype(jnp.int8), batch["cand_codes"],
                            batch["cand_inv"])

    return step, (param_sh, batch_sh), (params_s, batch_s)


# ---------------------------------------------------------------------------
# Cell: meshgraphnet ogb_products (most collective-bound).
# ---------------------------------------------------------------------------


def gnn_ogb_baseline(mesh):
    from repro.configs.registry import build_cell

    cell = build_cell("meshgraphnet", "ogb_products", mesh)
    return cell.fn, cell.in_shardings, cell.abstract_args


def gnn_ogb_node_constrained(mesh):
    """Constrain aggregates/states to the node partition: all-reduce ->
    reduce-scatter + all-gather, node MLP runs sharded."""
    from repro.configs import cells as cells_mod
    from repro.configs.registry import get_arch
    from repro.train import steps

    cell = cells_mod.gnn_cell(get_arch("meshgraphnet").config, "ogb_products",
                              mesh)

    def node_constrain(x):
        return jax.lax.with_sharding_constraint(
            x, NamedSharding(mesh, P("model", None)))

    import repro.models.gnn as gnn_lib

    # rebuild the step with the constraint (same cfg the cell used)
    cfg = dataclasses.replace(
        get_arch("meshgraphnet").config,
        d_node_in=cells_mod.GNN_SHAPES["ogb_products"]["d_feat"], d_edge_in=8)
    fn = steps.gnn_train_step(cfg, cells_mod.ADAM,
                              node_constrain=node_constrain)
    return fn, cell.in_shardings, cell.abstract_args


def gnn_ogb_bf16_edges(mesh):
    """node constraint + bf16 message/aggregate arithmetic (halves both
    the HBM and wire bytes of the edge pipeline)."""
    from repro.configs import cells as cells_mod
    from repro.configs.registry import get_arch
    from repro.train import steps

    cell = cells_mod.gnn_cell(get_arch("meshgraphnet").config, "ogb_products",
                              mesh)

    def node_constrain(x):
        return jax.lax.with_sharding_constraint(
            x, NamedSharding(mesh, P("model", None)))

    cfg = dataclasses.replace(
        get_arch("meshgraphnet").config, dtype=jnp.bfloat16,
        d_node_in=cells_mod.GNN_SHAPES["ogb_products"]["d_feat"], d_edge_in=8)
    fn = steps.gnn_train_step(cfg, cells_mod.ADAM,
                              node_constrain=node_constrain)

    # params/opt in bf16-aware shapes
    import repro.models.gnn as gnn_lib
    from repro.train import optim

    params_s = jax.eval_shape(lambda: gnn_lib.init_params(jax.random.PRNGKey(0), cfg))
    opt_s = jax.eval_shape(lambda: optim.adam_init(params_s))
    batch_s = cell.abstract_args[2]
    rep = jax.tree_util.tree_map(lambda _: NamedSharding(mesh, P()), params_s)
    opt_sh = optim.AdamState(step=NamedSharding(mesh, P()),
                             mu=jax.tree_util.tree_map(lambda _: NamedSharding(mesh, P()), params_s),
                             nu=jax.tree_util.tree_map(lambda _: NamedSharding(mesh, P()), params_s))
    return fn, (rep, opt_sh, cell.in_shardings[2]), (params_s, opt_s, batch_s)


def gnn_ogb_partitioned(mesh, gather_dtype=None):
    """Receiver-partitioned message passing (shard_map).

    Data contract: the host pipeline sorts edges so edge e lives on the
    device owning receiver[e] (standard partition-aware graph loading).
    Then: one all-gather of node states per layer (senders may be remote),
    segment_sum is fully local (NO all-reduce), node MLP runs on the local
    node shard. Baseline: ~3 all-gathers + 2 all-reduces of the full
    [2.45M, 128] array per layer; here: 1 all-gather (+ its reduce-scatter
    transpose in backward).
    """
    from jax import shard_map

    import repro.models.gnn as gnn_lib
    from repro.configs import cells as cells_mod
    from repro.configs.registry import get_arch
    from repro.train import optim as optim_mod

    info = cells_mod.GNN_SHAPES["ogb_products"]
    cfg = dataclasses.replace(get_arch("meshgraphnet").config,
                              d_node_in=info["d_feat"], d_edge_in=8)
    n_all = mesh.devices.size
    N = info["nodes"] + (-info["nodes"]) % n_all
    E = info["edges"] + (-info["edges"]) % n_all
    axes = tuple(mesh.axis_names)  # shard over the whole mesh

    params_s = jax.eval_shape(lambda: gnn_lib.init_params(jax.random.PRNGKey(0), cfg))
    opt_s = jax.eval_shape(lambda: optim_mod.adam_init(params_s))
    rep = jax.tree_util.tree_map(lambda _: NamedSharding(mesh, P()), params_s)
    opt_sh = optim_mod.AdamState(
        step=NamedSharding(mesh, P()),
        mu=jax.tree_util.tree_map(lambda _: NamedSharding(mesh, P()), params_s),
        nu=jax.tree_util.tree_map(lambda _: NamedSharding(mesh, P()), params_s))
    batch_s = {
        "node_feat": jax.ShapeDtypeStruct((N, info["d_feat"]), jnp.float32),
        "edge_feat": jax.ShapeDtypeStruct((E, 8), jnp.float32),
        "senders": jax.ShapeDtypeStruct((E,), jnp.int32),
        "receivers": jax.ShapeDtypeStruct((E,), jnp.int32),
        "edge_mask": jax.ShapeDtypeStruct((E,), jnp.bool_),
        "targets": jax.ShapeDtypeStruct((N, cfg.d_out), jnp.float32),
    }
    batch_sh = {
        "node_feat": NamedSharding(mesh, P(axes, None)),
        "edge_feat": NamedSharding(mesh, P(axes, None)),
        "senders": NamedSharding(mesh, P(axes)),
        "receivers": NamedSharding(mesh, P(axes)),
        "edge_mask": NamedSharding(mesh, P(axes)),
        "targets": NamedSharding(mesh, P(axes, None)),
    }
    n_loc = N // n_all

    def local_loss(params, nf, ef, snd, rcv, msk, tgt):
        rank = jax.lax.axis_index(axes[0])
        for ax in axes[1:]:
            rank = rank * mesh.shape[ax] + jax.lax.axis_index(ax)
        base = rank * n_loc
        v = gnn_lib._mlp(params["node_enc"], nf)  # [n_loc, h]
        e = gnn_lib._mlp(params["edge_enc"], ef) * msk[:, None]

        def layer_fn(lp, v, e):
            vg = v.astype(gather_dtype) if gather_dtype else v
            v_full = jax.lax.all_gather(vg, axes, axis=0, tiled=True)  # [N, h]
            vs = jnp.take(v_full, snd, axis=0).astype(v.dtype)
            vr = jnp.take(v_full, rcv, axis=0).astype(v.dtype)
            e_new = gnn_lib._mlp(lp["edge_mlp"],
                                 jnp.concatenate([e, vs, vr], -1))
            e = e + e_new * msk[:, None]
            # receivers are LOCAL by the partitioning contract
            agg = jax.ops.segment_sum(e, rcv - base, num_segments=n_loc)
            v = v + gnn_lib._mlp(lp["node_mlp"], jnp.concatenate([v, agg], -1))
            return v, e

        layer_fn = jax.checkpoint(layer_fn)
        for lp in params["layers"]:
            v, e = layer_fn(lp, v, e)
        out = gnn_lib._mlp(params["decoder"], v)
        sq = jnp.sum(jnp.square(out - tgt))
        return jax.lax.psum(sq, axes) / (N * cfg.d_out)

    def sharded_grads(params, nf, ef, snd, rcv, msk, tgt):
        loss, grads = jax.value_and_grad(local_loss)(params, nf, ef, snd,
                                                     rcv, msk, tgt)
        grads = jax.lax.pmean(grads, axes)  # params replicated
        return loss, grads

    gfn = shard_map(
        sharded_grads, mesh=mesh,
        in_specs=(P(), P(axes, None), P(axes, None), P(axes), P(axes),
                  P(axes), P(axes, None)),
        out_specs=(P(), P()), check_vma=False)

    from repro.configs.cells import ADAM as _ADAM

    def step(params, opt_state, batch):
        loss, grads = gfn(params, batch["node_feat"], batch["edge_feat"],
                          batch["senders"], batch["receivers"],
                          batch["edge_mask"], batch["targets"])
        new_params, new_opt = optim_mod.adam_update(grads, opt_state, params,
                                                    _ADAM)
        return new_params, new_opt, {"loss": loss}

    return step, (rep, opt_sh, batch_sh), (params_s, opt_s, batch_s)




def gnn_ogb_halo(mesh, slack: float = 2.0):
    """Halo exchange: instead of all-gathering the full node array, each
    device requests exactly the sender rows its local edges touch via a
    request/response all-to-all pair. Wire per layer ~ 2 * E_loc * h * 4B
    (~250 MB) vs the 1.25 GB all-gather — and it improves further with
    partition quality (METIS cut), unlike all-gather.

    Static shapes: per-destination request buckets are padded to
    slack * E_loc / n_shards (uniform senders => Poisson tails; slack=2
    bounds overflow far beyond 6 sigma at these sizes).
    """
    from jax import shard_map

    import repro.models.gnn as gnn_lib
    from repro.configs import cells as cells_mod
    from repro.configs.registry import get_arch
    from repro.train import optim as optim_mod
    from repro.configs.cells import ADAM as _ADAM

    info = cells_mod.GNN_SHAPES["ogb_products"]
    cfg = dataclasses.replace(get_arch("meshgraphnet").config,
                              d_node_in=info["d_feat"], d_edge_in=8)
    n_all = mesh.devices.size
    N = info["nodes"] + (-info["nodes"]) % n_all
    E = info["edges"] + (-info["edges"]) % n_all
    axes = tuple(mesh.axis_names)
    n_loc = N // n_all
    e_loc = E // n_all
    bucket = int(slack * e_loc / n_all) + 1  # per-peer request capacity

    params_s = jax.eval_shape(lambda: gnn_lib.init_params(jax.random.PRNGKey(0), cfg))
    opt_s = jax.eval_shape(lambda: optim_mod.adam_init(params_s))
    rep = jax.tree_util.tree_map(lambda _: NamedSharding(mesh, P()), params_s)
    opt_sh = optim_mod.AdamState(
        step=NamedSharding(mesh, P()),
        mu=jax.tree_util.tree_map(lambda _: NamedSharding(mesh, P()), params_s),
        nu=jax.tree_util.tree_map(lambda _: NamedSharding(mesh, P()), params_s))
    batch_s = {
        "node_feat": jax.ShapeDtypeStruct((N, info["d_feat"]), jnp.float32),
        "edge_feat": jax.ShapeDtypeStruct((E, 8), jnp.float32),
        "senders": jax.ShapeDtypeStruct((E,), jnp.int32),
        "receivers": jax.ShapeDtypeStruct((E,), jnp.int32),
        "edge_mask": jax.ShapeDtypeStruct((E,), jnp.bool_),
        "targets": jax.ShapeDtypeStruct((N, cfg.d_out), jnp.float32),
    }
    batch_sh = {
        "node_feat": NamedSharding(mesh, P(axes, None)),
        "edge_feat": NamedSharding(mesh, P(axes, None)),
        "senders": NamedSharding(mesh, P(axes)),
        "receivers": NamedSharding(mesh, P(axes)),
        "edge_mask": NamedSharding(mesh, P(axes)),
        "targets": NamedSharding(mesh, P(axes, None)),
    }

    def local_loss(params, nf, ef, snd, rcv, msk, tgt):
        rank = jax.lax.axis_index(axes[0])
        for ax in axes[1:]:
            rank = rank * mesh.shape[ax] + jax.lax.axis_index(ax)
        base = rank * n_loc
        v = gnn_lib._mlp(params["node_enc"], nf)
        e = gnn_lib._mlp(params["edge_enc"], ef) * msk[:, None]

        # --- static routing plan (independent of layer, computed once) ---
        owner = snd // n_loc  # [e_loc]
        order = jnp.argsort(owner)  # edges grouped by owner
        snd_sorted = snd[order]
        own_sorted = owner[order]
        # owners are sorted: position within the owner's group is
        # index - group_start (searchsorted: no [e_loc, n_all] one-hot)
        group_start = jnp.searchsorted(own_sorted, jnp.arange(n_all),
                                       side="left")
        pos_in_bucket = jnp.arange(e_loc) - group_start[own_sorted]
        keep = pos_in_bucket < bucket
        slot = jnp.clip(pos_in_bucket, 0, bucket - 1)
        req = jnp.full((n_all, bucket), -1, jnp.int32)
        req = req.at[own_sorted, slot].set(
            jnp.where(keep, snd_sorted % n_loc, -1))
        req_recv = jax.lax.all_to_all(
            req.reshape(n_all, 1, bucket), axes, split_axis=0,
            concat_axis=1, tiled=False).reshape(n_all, bucket)

        def fetch(v):
            rows = jnp.take(v, jnp.maximum(req_recv, 0).reshape(-1), axis=0)
            rows = jnp.where((req_recv >= 0).reshape(-1, 1), rows, 0.0)
            rows = rows.reshape(n_all, bucket, -1)
            resp = jax.lax.all_to_all(
                rows.reshape(n_all, 1, bucket, rows.shape[-1]), axes,
                split_axis=0, concat_axis=1, tiled=False
            ).reshape(n_all * bucket, rows.shape[-1])
            return resp  # row for request (owner o, slot s) at o*bucket+s

        def layer_fn(lp, v, e):
            resp = fetch(v)
            flat_idx = own_sorted * bucket + slot
            vs_sorted = jnp.take(resp, flat_idx, axis=0)
            vs_sorted = jnp.where(keep[:, None], vs_sorted, 0.0)
            vs = jnp.zeros_like(vs_sorted).at[order].set(vs_sorted)
            vr = jnp.take(v, rcv - base, axis=0)  # receivers are local
            e_new = gnn_lib._mlp(lp["edge_mlp"],
                                 jnp.concatenate([e, vs, vr], -1))
            e = e + e_new * msk[:, None]
            agg = jax.ops.segment_sum(e, rcv - base, num_segments=n_loc)
            v = v + gnn_lib._mlp(lp["node_mlp"], jnp.concatenate([v, agg], -1))
            return v, e

        layer_fn = jax.checkpoint(layer_fn)
        for lp in params["layers"]:
            v, e = layer_fn(lp, v, e)
        out = gnn_lib._mlp(params["decoder"], v)
        sq = jnp.sum(jnp.square(out - tgt))
        return jax.lax.psum(sq, axes) / (N * cfg.d_out)

    def sharded_grads(params, nf, ef, snd, rcv, msk, tgt):
        loss, grads = jax.value_and_grad(local_loss)(params, nf, ef, snd,
                                                     rcv, msk, tgt)
        grads = jax.lax.pmean(grads, axes)
        return loss, grads

    gfn = shard_map(
        sharded_grads, mesh=mesh,
        in_specs=(P(), P(axes, None), P(axes, None), P(axes), P(axes),
                  P(axes), P(axes, None)),
        out_specs=(P(), P()), check_vma=False)

    def step(params, opt_state, batch):
        loss, grads = gfn(params, batch["node_feat"], batch["edge_feat"],
                          batch["senders"], batch["receivers"],
                          batch["edge_mask"], batch["targets"])
        new_params, new_opt = optim_mod.adam_update(grads, opt_state, params,
                                                    _ADAM)
        return new_params, new_opt, {"loss": loss}

    return step, (rep, opt_sh, batch_sh), (params_s, opt_s, batch_s)




def gnn_ogb_halo_hostplan(mesh, slack: float = 2.0):
    """Halo exchange with the routing plan precomputed by the data
    pipeline (it is static per graph, exactly like the receiver
    partitioning): the device step receives request tables and unsort
    indices as inputs, so the in-graph work is just the two all-to-alls
    plus gathers — no sorting/scattering on the accelerator.
    """
    from jax import shard_map

    import repro.models.gnn as gnn_lib
    from repro.configs import cells as cells_mod
    from repro.configs.registry import get_arch
    from repro.train import optim as optim_mod
    from repro.configs.cells import ADAM as _ADAM

    info = cells_mod.GNN_SHAPES["ogb_products"]
    cfg = dataclasses.replace(get_arch("meshgraphnet").config,
                              d_node_in=info["d_feat"], d_edge_in=8)
    n_all = mesh.devices.size
    N = info["nodes"] + (-info["nodes"]) % n_all
    E = info["edges"] + (-info["edges"]) % n_all
    axes = tuple(mesh.axis_names)
    n_loc = N // n_all
    e_loc = E // n_all
    bucket = int(slack * e_loc / n_all) + 1

    params_s = jax.eval_shape(lambda: gnn_lib.init_params(jax.random.PRNGKey(0), cfg))
    opt_s = jax.eval_shape(lambda: optim_mod.adam_init(params_s))
    rep = jax.tree_util.tree_map(lambda _: NamedSharding(mesh, P()), params_s)
    opt_sh = optim_mod.AdamState(
        step=NamedSharding(mesh, P()),
        mu=jax.tree_util.tree_map(lambda _: NamedSharding(mesh, P()), params_s),
        nu=jax.tree_util.tree_map(lambda _: NamedSharding(mesh, P()), params_s))
    batch_s = {
        "node_feat": jax.ShapeDtypeStruct((N, info["d_feat"]), jnp.float32),
        "edge_feat": jax.ShapeDtypeStruct((E, 8), jnp.float32),
        "receivers": jax.ShapeDtypeStruct((E,), jnp.int32),
        "edge_mask": jax.ShapeDtypeStruct((E,), jnp.bool_),
        "targets": jax.ShapeDtypeStruct((N, cfg.d_out), jnp.float32),
        # host-prepared halo routing plan (per-device tables, see below)
        "fetch_idx": jax.ShapeDtypeStruct((E,), jnp.int32),
        "fetch_valid": jax.ShapeDtypeStruct((E,), jnp.bool_),
    }
    batch_sh = {
        "node_feat": NamedSharding(mesh, P(axes, None)),
        "edge_feat": NamedSharding(mesh, P(axes, None)),
        "receivers": NamedSharding(mesh, P(axes)),
        "edge_mask": NamedSharding(mesh, P(axes)),
        "targets": NamedSharding(mesh, P(axes, None)),
        "fetch_idx": NamedSharding(mesh, P(axes)),
        "fetch_valid": NamedSharding(mesh, P(axes)),
    }
    # req is per-device data: leading device axis, sharded over the mesh.
    batch_s["req"] = jax.ShapeDtypeStruct((n_all, n_all * bucket), jnp.int32)
    batch_sh["req"] = NamedSharding(mesh, P(axes, None))

    def local_loss(params, nf, ef, rcv, msk, tgt, req, fidx, fvalid):
        rank = jax.lax.axis_index(axes[0])
        for ax in axes[1:]:
            rank = rank * mesh.shape[ax] + jax.lax.axis_index(ax)
        base = rank * n_loc
        v = gnn_lib._mlp(params["node_enc"], nf)
        e = gnn_lib._mlp(params["edge_enc"], ef) * msk[:, None]
        req = req.reshape(n_all, bucket)  # [peer, slot] local node ids, -1 pad
        req_recv = jax.lax.all_to_all(
            req.reshape(n_all, 1, bucket), axes, split_axis=0,
            concat_axis=1, tiled=False).reshape(n_all, bucket)

        def fetch(v):
            rows = jnp.take(v, jnp.maximum(req_recv, 0).reshape(-1), axis=0)
            rows = rows * (req_recv >= 0).reshape(-1, 1)
            rows = rows.reshape(n_all, bucket, -1)
            resp = jax.lax.all_to_all(
                rows.reshape(n_all, 1, bucket, rows.shape[-1]), axes,
                split_axis=0, concat_axis=1, tiled=False
            ).reshape(n_all * bucket, rows.shape[-1])
            return resp

        def layer_fn(lp, v, e):
            resp = fetch(v)
            vs = jnp.take(resp, fidx, axis=0) * fvalid[:, None]
            vr = jnp.take(v, rcv - base, axis=0)
            e_new = gnn_lib._mlp(lp["edge_mlp"],
                                 jnp.concatenate([e, vs, vr], -1))
            e = e + e_new * msk[:, None]
            agg = jax.ops.segment_sum(e, rcv - base, num_segments=n_loc)
            v = v + gnn_lib._mlp(lp["node_mlp"], jnp.concatenate([v, agg], -1))
            return v, e

        layer_fn = jax.checkpoint(layer_fn)
        for lp in params["layers"]:
            v, e = layer_fn(lp, v, e)
        out = gnn_lib._mlp(params["decoder"], v)
        sq = jnp.sum(jnp.square(out - tgt))
        return jax.lax.psum(sq, axes) / (N * cfg.d_out)

    def sharded_grads(params, nf, ef, rcv, msk, tgt, req, fidx, fvalid):
        loss, grads = jax.value_and_grad(local_loss)(
            params, nf, ef, rcv, msk, tgt, req, fidx, fvalid)
        grads = jax.lax.pmean(grads, axes)
        return loss, grads

    gfn = shard_map(
        sharded_grads, mesh=mesh,
        in_specs=(P(), P(axes, None), P(axes, None), P(axes), P(axes),
                  P(axes, None), P(axes, None), P(axes), P(axes)),
        out_specs=(P(), P()), check_vma=False)

    def step(params, opt_state, batch):
        fvalid = batch["fetch_valid"].astype(jnp.float32)
        loss, grads = gfn(params, batch["node_feat"], batch["edge_feat"],
                          batch["receivers"], batch["edge_mask"],
                          batch["targets"], batch["req"],
                          batch["fetch_idx"], fvalid)
        new_params, new_opt = optim_mod.adam_update(grads, opt_state, params,
                                                    _ADAM)
        return new_params, new_opt, {"loss": loss}

    return step, (rep, opt_sh, batch_sh), (params_s, opt_s, batch_s)


# ---------------------------------------------------------------------------
# Cell: llama3-405b train_4k (biggest model, memory+collective heavy).
# ---------------------------------------------------------------------------


def _llama_variant(mesh, **overrides):
    from repro.configs import cells as cells_mod
    from repro.configs.archs.llama3_405b import CONFIG

    cfg = dataclasses.replace(CONFIG, **overrides)
    cell = cells_mod.lm_cell(cfg, "train_4k", mesh)
    return cell.fn, cell.in_shardings, cell.abstract_args


def llama_baseline(mesh):
    return _llama_variant(mesh)


def llama_no_sp(mesh):
    return _llama_variant(mesh, activation_sharding=None)


def llama_mb16(mesh):
    return _llama_variant(mesh, microbatches=16)


def llama_mb4(mesh):
    return _llama_variant(mesh, microbatches=4)


def llama_mb4_no_sp(mesh):
    return _llama_variant(mesh, microbatches=4, activation_sharding=None)


def llama_mb2_no_sp(mesh):
    return _llama_variant(mesh, microbatches=2, activation_sharding=None)


def llama_sp_residual(mesh):
    return _llama_variant(mesh, activation_sharding="seq_residual")


def llama_sp_residual_mb4(mesh):
    return _llama_variant(mesh, activation_sharding="seq_residual",
                          microbatches=4)


def llama_mb4_chunk1024(mesh):
    return _llama_variant(mesh, microbatches=4, attn_chunk=1024)


def llama_mb2_chunk1024(mesh):
    return _llama_variant(mesh, microbatches=2, attn_chunk=1024)


def llama_chunk256(mesh):
    return _llama_variant(mesh, attn_chunk=256)


def llama_chunk1024(mesh):
    return _llama_variant(mesh, attn_chunk=1024)


def grok_prefill_baseline(mesh):
    from repro.configs import cells as cells_mod
    from repro.configs.archs.grok_1_314b import CONFIG

    cell = cells_mod.lm_cell(CONFIG, "prefill_32k", mesh)
    return cell.fn, cell.in_shardings, cell.abstract_args


def grok_prefill_grouped(mesh):
    """Bonus iteration: fixed-size MoE routing groups bound the GShard
    dispatch one-hot linearly in S (654 GiB cell -> expected ~1/16)."""
    from repro.configs import cells as cells_mod
    from repro.configs.archs.grok_1_314b import CONFIG

    cfg = dataclasses.replace(CONFIG, moe_group=2048)
    cell = cells_mod.lm_cell(cfg, "prefill_32k", mesh)
    return cell.fn, cell.in_shardings, cell.abstract_args


VARIANTS = {
    "tt_retrieval": {
        "baseline": tt_retrieval_baseline,
        "float_index": tt_retrieval_float_index,
        "bebr_sdc": tt_retrieval_bebr,
        "bebr_sdc_fullmesh": tt_retrieval_bebr_full,
        "bebr_sdc_merge": tt_retrieval_bebr_merge,
    },
    "gnn_ogb": {
        "baseline": gnn_ogb_baseline,
        "node_constrained": gnn_ogb_node_constrained,
        "node_constrained_bf16": gnn_ogb_bf16_edges,
        "partitioned": gnn_ogb_partitioned,
        "partitioned_bf16gather": lambda mesh: gnn_ogb_partitioned(
            mesh, gather_dtype=jnp.bfloat16),
        "halo_exchange": gnn_ogb_halo,
        "halo_hostplan": gnn_ogb_halo_hostplan,
    },
    "grok_prefill": {
        "baseline": grok_prefill_baseline,
        "routing_groups": grok_prefill_grouped,
    },
    "llama405b_train": {
        "baseline": llama_baseline,
        "no_seq_sharding": llama_no_sp,
        "microbatch16": llama_mb16,
        "microbatch4": llama_mb4,
        "mb4_no_sp": llama_mb4_no_sp,
        "mb2_no_sp": llama_mb2_no_sp,
        "sp_residual": llama_sp_residual,
        "sp_residual_mb4": llama_sp_residual_mb4,
        "mb4_chunk1024": llama_mb4_chunk1024,
        "mb2_chunk1024": llama_mb2_chunk1024,
        "attn_chunk256": llama_chunk256,
        "attn_chunk1024": llama_chunk1024,
    },
}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--cell", required=True, choices=sorted(VARIANTS))
    ap.add_argument("--variant", required=True)
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--out", default="perf_results.json")
    args = ap.parse_args()

    from repro.launch.mesh import make_production_mesh

    mesh = make_production_mesh(multi_pod=args.multi_pod)
    build = VARIANTS[args.cell][args.variant]
    fn, shardings, abstract = build(mesh)
    res = _measure(fn, shardings, abstract, mesh, mesh.devices.size)

    key = f"{args.cell}|{args.variant}|{'2x16x16' if args.multi_pod else '16x16'}"
    print(f"{key}: compute={res['compute_ms']:.2f}ms "
          f"memory={res['memory_ms']:.2f}ms coll={res['collective_ms']:.2f}ms "
          f"peak={res['peak_gib']:.2f}GiB compile={res['compile_s']}s")

    log = {}
    if os.path.exists(args.out):
        with open(args.out) as f:
            log = json.load(f)
    log[key] = res
    with open(args.out, "w") as f:
        json.dump(log, f, indent=1)


if __name__ == "__main__":
    main()
