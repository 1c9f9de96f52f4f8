import os
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=512")
# ^ must precede jax import (512 placeholder devices, dry-run contract).

# §Perf hillclimb variants. Each variant rebuilds one of the three chosen
# cells with an optimization applied, lowers+compiles it on the production
# mesh, and reports the three roofline terms from the while-aware HLO walk.
#
#   PYTHONPATH=src python -m benchmarks.perf_variants --cell llama3-405b
#   PYTHONPATH=src python -m benchmarks.perf_variants --cell deepfm
#   PYTHONPATH=src python -m benchmarks.perf_variants --cell clax-ubm
#
# Results are recorded in EXPERIMENTS.md §Perf.
import argparse
import dataclasses
import json
import time

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro import optim as optim_lib
from repro.configs import llama3_405b
from repro.configs.common import named, sds
from repro.configs.lm_common import build_lm_cell
from repro.distrib import masked_psum_lookup
from repro.launch.hlo_cost import analyze_hlo
from repro.launch.mesh import make_production_mesh
from repro.optim.optimizers import ScaleByAdamState
from repro.optim.sparse import (init_sparse_table_state, sparse_adamw_update,
                                sparse_row_grads)

PEAK_FLOPS, HBM_BW, LINK_BW = 197e12, 819e9, 50e9


def measure(name, fn, args, in_sh, out_sh, donate=(), mesh=None):
    t0 = time.time()
    with jax.set_mesh(mesh):
        compiled = jax.jit(fn, in_shardings=in_sh, out_shardings=out_sh,
                           donate_argnums=donate).lower(*args).compile()
    walk = analyze_hlo(compiled.as_text())
    mem = compiled.memory_analysis()
    peak = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes - mem.alias_size_in_bytes)
    terms = {
        "compute_s": walk["flops"] / PEAK_FLOPS,
        "memory_s": walk["bytes"] / HBM_BW,
        "collective_s": walk["collective_wire_bytes"] / LINK_BW,
    }
    dom = max(terms, key=terms.get)
    per_op = {k: f"{v / 2**20:.0f}MiB" for k, v in
              sorted(walk["collective_ops"].items(), key=lambda kv: -kv[1])}
    print(f"{name:44s} compute={terms['compute_s']:.3e}s "
          f"memory={terms['memory_s']:.3e}s "
          f"collective={terms['collective_s']:.3e}s  dom={dom:12s} "
          f"peak={peak / 2**30:.2f}GiB (compile {time.time() - t0:.0f}s)")
    print(f"{'':44s} wire breakdown: {per_op}")
    return terms


# ---------------------------------------------------------------------------
# Cell 1: llama3-405b x train_4k — collective-bound (FSDP regathers x micro)
# ---------------------------------------------------------------------------

def run_llama(mesh):
    for mb, chunks, erp in ((16, 9, False), (8, 9, False), (4, 9, False),
                            (4, 9, True), (8, 9, True)):
        cfg = dataclasses.replace(llama3_405b.FULL, microbatches=mb,
                                  scan_chunks=chunks,
                                  explicit_row_parallel=erp)
        cell = build_lm_cell(cfg, "train_4k", mesh)
        measure(f"llama3-405b/train_4k mb={mb} erp={erp}", cell.fn,
                cell.args, cell.in_shardings, cell.out_shardings,
                donate=cell.donate, mesh=mesh)


# ---------------------------------------------------------------------------
# Cell 2: deepfm x train_batch — table lookup + optimizer variants
# ---------------------------------------------------------------------------

def _deepfm_pieces(mesh):
    from repro.configs.deepfm import FULL
    from repro.models.recsys import DeepFM

    model = DeepFM(FULL)
    B, F, D = 65536, FULL.n_sparse, FULL.embed_dim
    R = FULL.table_rows
    dp = ("data",) if "pod" not in mesh.axis_names else ("pod", "data")
    batch = {"field_ids": sds((B, F), jnp.int32),
             "labels": sds((B,), jnp.float32)}
    bspecs = {"field_ids": P(dp, None), "labels": P(dp)}
    return model, FULL, batch, bspecs, dp, (B, F, D, R)


def run_deepfm(mesh):
    model, cfg, batch, bspecs, dp, (B, F, D, R) = _deepfm_pieces(mesh)
    params = jax.eval_shape(lambda: model.init(jax.random.PRNGKey(0)))
    pspecs = model.param_specs(mesh)

    # --- baseline: XLA-auto lookup + dense AdamW ---------------------------------
    optimizer = optim_lib.adamw(1e-3)
    opt_state = jax.eval_shape(optimizer.init, params)
    ospecs = (ScaleByAdamState(count=P(), mu=pspecs, nu=pspecs), (), ())
    step = model.make_train_step(optimizer)
    measure("deepfm/train baseline (auto lookup, dense adamw)", step,
            (params, opt_state, batch),
            (named(mesh, pspecs), named(mesh, ospecs), named(mesh, bspecs)),
            (named(mesh, pspecs), named(mesh, ospecs), named(mesh, P())),
            donate=(0, 1), mesh=mesh)

    # --- v1: shard_map masked-psum lookup (activations cross the wire, the
    # table-grad scatter stays shard-local) --------------------------------------
    lookup = masked_psum_lookup(mesh, batch_dims=2)

    def forward_v1(p, batch):
        ids = batch["field_ids"]
        v = lookup(p["embedding"]["table"], ids)
        first = lookup(p["first_order"]["table"], ids)[..., 0]
        from repro.kernels import fm_interaction
        fm = fm_interaction(v)
        deep = model.mlp(p["mlp"], v.reshape(v.shape[0], -1))[..., 0]
        return p["bias"] + jnp.sum(first, -1) + fm + deep

    def loss_v1(p, batch):
        from repro.stable import log_bce, log_sigmoid
        return jnp.mean(log_bce(log_sigmoid(forward_v1(p, batch)),
                                batch["labels"]))

    def step_v1(p, opt_state, batch):
        loss, grads = jax.value_and_grad(loss_v1)(p, batch)
        updates, opt_state = optimizer.update(grads, opt_state, p)
        return optim_lib.apply_updates(p, updates), opt_state, loss

    measure("deepfm/train v1 (+shard_map psum lookup)", step_v1,
            (params, opt_state, batch),
            (named(mesh, pspecs), named(mesh, ospecs), named(mesh, bspecs)),
            (named(mesh, pspecs), named(mesh, ospecs), named(mesh, P())),
            donate=(0, 1), mesh=mesh)

    # --- v2: v1 + sparse-row AdamW on both tables --------------------------------
    max_unique = B * F  # static bound on unique rows per batch

    def step_v2(tables, sparse_states, dense, dense_opt, batch):
        ids = batch["field_ids"]

        def loss_fn(emb_rows, first_rows, dense_in):
            from repro.kernels import fm_interaction
            from repro.stable import log_bce, log_sigmoid
            v = emb_rows
            fm = fm_interaction(v)
            deep = model.mlp(dense_in["mlp"],
                             v.reshape(v.shape[0], -1))[..., 0]
            logit = (dense_in["bias"] + jnp.sum(first_rows[..., 0], -1)
                     + fm + deep)
            return jnp.mean(log_bce(log_sigmoid(logit), batch["labels"]))

        emb_rows = lookup(tables["embedding"], ids)
        first_rows = lookup(tables["first_order"], ids)
        loss, (d_emb, d_first, d_dense) = jax.value_and_grad(
            loss_fn, argnums=(0, 1, 2))(emb_rows, first_rows, dense)
        new_tables, new_states = {}, {}
        for key, d_rows in (("embedding", d_emb), ("first_order", d_first)):
            uids, ugrads = sparse_row_grads(d_rows, ids, R,
                                            max_unique=max_unique)
            new_tables[key], new_states[key] = sparse_adamw_update(
                tables[key], sparse_states[key], uids, ugrads, lr=1e-3)
        updates, dense_opt = dense_optimizer.update(d_dense, dense_opt, dense)
        dense = optim_lib.apply_updates(dense, updates)
        return new_tables, new_states, dense, dense_opt, loss

    dense_optimizer = optim_lib.adamw(1e-3)
    tables = {"embedding": sds((R, D), jnp.float32),
              "first_order": sds((R, 1), jnp.float32)}
    tspecs = {"embedding": P("model", None), "first_order": P("model", None)}
    sstate = {k: jax.eval_shape(init_sparse_table_state, tables[k])
              for k in tables}
    sspecs = {k: type(sstate[k])(count=P(), mu=tspecs[k], nu=tspecs[k])
              for k in tables}
    dense = {"mlp": jax.eval_shape(
        lambda: model.mlp.init(jax.random.PRNGKey(0))),
        "bias": sds((), jnp.float32)}
    dspecs = jax.tree_util.tree_map(lambda _: P(), dense)
    dense_opt = jax.eval_shape(dense_optimizer.init, dense)
    dopt_specs = (ScaleByAdamState(count=P(), mu=dspecs, nu=dspecs), (), ())
    measure("deepfm/train v2 (+sparse-row adamw)", step_v2,
            (tables, sstate, dense, dense_opt, batch),
            (named(mesh, tspecs), named(mesh, sspecs), named(mesh, dspecs),
             named(mesh, dopt_specs), named(mesh, bspecs)),
            (named(mesh, tspecs), named(mesh, sspecs), named(mesh, dspecs),
             named(mesh, dopt_specs), named(mesh, P())),
            donate=(0, 1, 2, 3), mesh=mesh)


# ---------------------------------------------------------------------------
# Cell 3: clax-ubm-baidu x train_batch — the paper's own workload
# ---------------------------------------------------------------------------

def run_clax(mesh):
    from repro.configs import clax_baidu
    from repro.core.parameterization import hash_ids
    from repro.stable import log_bce, log_sigmoid
    from repro.core.base import last_click_positions, masked_mean

    # baseline (paper-faithful: auto lookup, dense AdamW)
    cell = clax_baidu.build_cell("train_batch", mesh, kind="ubm")
    measure("clax-ubm/train baseline (paper-faithful)", cell.fn, cell.args,
            cell.in_shardings, cell.out_shardings, donate=cell.donate,
            mesh=mesh)

    B, K = 65536, clax_baidu.POSITIONS
    model = clax_baidu.make_model("ubm")
    attr = model.parts["attraction"]
    R = attr.table_rows
    dp = ("data",) if "pod" not in mesh.axis_names else ("pod", "data")
    lookup = masked_psum_lookup(mesh, batch_dims=2)

    batch = {
        "positions": sds((B, K), jnp.int32),
        "query_doc_ids": sds((B, K), jnp.int32),
        "clicks": sds((B, K), jnp.float32),
        "mask": sds((B, K), jnp.bool_),
    }
    bspecs = {k: P(dp, None) for k in batch}

    def cond_loss_from_rows(rows, dense, batch):
        """UBM conditional NLL with attraction logits given as inputs."""
        la = log_sigmoid(rows[..., 0] + dense["baseline"][0])
        k_prime = last_click_positions(batch["clicks"], batch["positions"])
        k_idx = jnp.clip(batch["positions"] - 1, 0, K - 1)
        kp_idx = jnp.clip(k_prime, 0, K - 1)
        le = log_sigmoid(dense["exam_table"][k_idx, kp_idx])
        nll = log_bce(la + le, batch["clicks"])
        return masked_mean(nll, batch["mask"])

    # v1: psum lookup, dense AdamW
    def step_v1(table, opt_state, dense, dense_opt, batch):
        hashed = hash_ids(batch["query_doc_ids"], R)

        def loss_fn(t, d):
            rows = lookup(t, hashed)
            return cond_loss_from_rows(rows, d, batch)

        loss, (gt, gd) = jax.value_and_grad(loss_fn, argnums=(0, 1))(
            table, dense)
        upd, opt_state = table_opt.update(gt, opt_state, table)
        table = optim_lib.apply_updates(table, upd)
        dupd, dense_opt = dense_optimizer.update(gd, dense_opt, dense)
        dense = optim_lib.apply_updates(dense, dupd)
        return table, opt_state, dense, dense_opt, loss

    table_opt = optim_lib.adamw(3e-3)
    dense_optimizer = optim_lib.adamw(3e-3)
    table = sds((R, 1), jnp.float32)
    tspec = P("model", None)
    topt = jax.eval_shape(table_opt.init, table)
    topt_specs = (ScaleByAdamState(count=P(), mu=tspec, nu=tspec), (), ())
    dense = {"baseline": sds((1,), jnp.float32),
             "exam_table": sds((K, K), jnp.float32)}
    dspecs = jax.tree_util.tree_map(lambda _: P(), dense)
    dopt = jax.eval_shape(dense_optimizer.init, dense)
    dopt_specs = (ScaleByAdamState(count=P(), mu=dspecs, nu=dspecs), (), ())
    measure("clax-ubm/train v1 (+shard_map psum lookup)", step_v1,
            (table, topt, dense, dopt, batch),
            (named(mesh, tspec), named(mesh, topt_specs), named(mesh, dspecs),
             named(mesh, dopt_specs), named(mesh, bspecs)),
            (named(mesh, tspec), named(mesh, topt_specs), named(mesh, dspecs),
             named(mesh, dopt_specs), named(mesh, P())),
            donate=(0, 1, 2, 3), mesh=mesh)

    # v2: psum lookup + sparse-row AdamW on the table
    def step_v2(table, sstate, dense, dense_opt, batch):
        hashed = hash_ids(batch["query_doc_ids"], R)
        rows = lookup(table, hashed)
        loss, (d_rows, gd) = jax.value_and_grad(
            cond_loss_from_rows, argnums=(0, 1))(rows, dense, batch)
        uids, ugrads = sparse_row_grads(d_rows, hashed, R,
                                        max_unique=B * K)
        table, sstate = sparse_adamw_update(table, sstate, uids, ugrads,
                                            lr=3e-3, weight_decay=1e-4)
        dupd, dense_opt = dense_optimizer.update(gd, dense_opt, dense)
        dense = optim_lib.apply_updates(dense, dupd)
        return table, sstate, dense, dense_opt, loss

    sstate = jax.eval_shape(init_sparse_table_state, table)
    sspecs = type(sstate)(count=P(), mu=tspec, nu=tspec)
    measure("clax-ubm/train v2 (+sparse-row adamw)", step_v2,
            (table, sstate, dense, dopt, batch),
            (named(mesh, tspec), named(mesh, sspecs), named(mesh, dspecs),
             named(mesh, dopt_specs), named(mesh, bspecs)),
            (named(mesh, tspec), named(mesh, sspecs), named(mesh, dspecs),
             named(mesh, dopt_specs), named(mesh, P())),
            donate=(0, 1, 2, 3), mesh=mesh)


def run_llama_decode(mesh):
    """Cell D: llama3-405b x long_500k — decode over a 524288-token KV cache
    sharded over ('data','model'). Baseline: XLA-auto softmax over the
    sharded seq axis. Optimized: flash-decoding (shard-local partial softmax
    + O(B*H*Dh) psum), repro/models/lm/transformer.py."""
    for flash in (False, True):
        cfg = dataclasses.replace(llama3_405b.FULL, flash_decode=flash)
        import repro.configs.llama3_405b as mod
        orig = mod.FULL
        mod.FULL = cfg
        try:
            cell = build_lm_cell(cfg, "long_500k", mesh)
        finally:
            mod.FULL = orig
        measure(f"llama3-405b/long_500k flash_decode={flash}", cell.fn,
                cell.args, cell.in_shardings, cell.out_shardings,
                donate=cell.donate, mesh=mesh)
        # decode_32k too (batch-sharded variant)
        cell = build_lm_cell(cfg, "decode_32k", mesh)
        measure(f"llama3-405b/decode_32k flash_decode={flash}", cell.fn,
                cell.args, cell.in_shardings, cell.out_shardings,
                donate=cell.donate, mesh=mesh)


def run_deepfm_v3(mesh):
    """v3: shard tables over BOTH axes — table grads reduce only to the
    owning 1/256 shard instead of an all-reduce over 'data' of each 1/16
    model shard. Napkin: baseline table-grad all-reduce = 2*(R/16)*D*4*(15/16)
    = ~400MiB/dev; 2D-sharded, the reduction payload is bounded by the
    activation-sized contributions (~102MiB) scattered to owners."""
    model, cfg, batch, bspecs, dp, (B, F, D, R) = _deepfm_pieces(mesh)
    params = jax.eval_shape(lambda: model.init(jax.random.PRNGKey(0)))
    pspecs = model.param_specs(mesh)
    both = (dp + ("model",))
    pspecs["embedding"] = {"table": P(both, None)}
    pspecs["first_order"] = {"table": P(both, None)}
    optimizer = optim_lib.adamw(1e-3)
    opt_state = jax.eval_shape(optimizer.init, params)
    ospecs = (ScaleByAdamState(count=P(), mu=pspecs, nu=pspecs), (), ())
    step = model.make_train_step(optimizer)
    measure("deepfm/train v3 (2D-sharded tables)", step,
            (params, opt_state, batch),
            (named(mesh, pspecs), named(mesh, ospecs), named(mesh, bspecs)),
            (named(mesh, pspecs), named(mesh, ospecs), named(mesh, P())),
            donate=(0, 1), mesh=mesh)


def run_deepfm_v4(mesh):
    """v4 = v3 + bf16 tables (DLRM-style): halves both the lookup-result
    resharding and the table-grad reduction payloads."""
    import dataclasses as dc
    from repro.configs.deepfm import FULL
    from repro.models.recsys import DeepFM
    from repro.models.recsys.embedding import TableConfig

    cfg = dc.replace(FULL)
    model = DeepFM(cfg)
    B, F, D, R = 65536, cfg.n_sparse, cfg.embed_dim, cfg.table_rows
    dp = ("data",) if "pod" not in mesh.axis_names else ("pod", "data")
    batch = {"field_ids": sds((B, F), jnp.int32),
             "labels": sds((B,), jnp.float32)}
    bspecs = {"field_ids": P(dp, None), "labels": P(dp)}
    both = dp + ("model",)
    params = jax.eval_shape(lambda: model.init(jax.random.PRNGKey(0)))
    # rebuild table leaves in bf16
    params = dict(params)
    params["embedding"] = {"table": sds((R, D), jnp.bfloat16)}
    params["first_order"] = {"table": sds((R, 1), jnp.bfloat16)}
    pspecs = model.param_specs(mesh)
    pspecs["embedding"] = {"table": P(both, None)}
    pspecs["first_order"] = {"table": P(both, None)}
    optimizer = optim_lib.adamw(1e-3, moment_dtype=jnp.bfloat16)
    opt_state = jax.eval_shape(optimizer.init, params)
    ospecs = (ScaleByAdamState(count=P(), mu=pspecs, nu=pspecs), (), ())
    step = model.make_train_step(optimizer)
    measure("deepfm/train v4 (2D shard + bf16 tables/moments)", step,
            (params, opt_state, batch),
            (named(mesh, pspecs), named(mesh, ospecs), named(mesh, bspecs)),
            (named(mesh, pspecs), named(mesh, ospecs), named(mesh, P())),
            donate=(0, 1), mesh=mesh)


def run_deepfm_v5(mesh):
    """v5 = v3 + batch sharded over BOTH axes (256-way DP): lookup results
    live on 1/256 batch shards, dense-tower compute also 256-way."""
    from repro.configs.deepfm import FULL
    from repro.models.recsys import DeepFM

    model = DeepFM(FULL)
    B, F, D, R = 65536, FULL.n_sparse, FULL.embed_dim, FULL.table_rows
    dp = ("data",) if "pod" not in mesh.axis_names else ("pod", "data")
    both = dp + ("model",)
    batch = {"field_ids": sds((B, F), jnp.int32),
             "labels": sds((B,), jnp.float32)}
    bspecs = {"field_ids": P(both, None), "labels": P(both)}
    params = jax.eval_shape(lambda: model.init(jax.random.PRNGKey(0)))
    pspecs = model.param_specs(mesh)
    pspecs["embedding"] = {"table": P(both, None)}
    pspecs["first_order"] = {"table": P(both, None)}
    optimizer = optim_lib.adamw(1e-3)
    opt_state = jax.eval_shape(optimizer.init, params)
    ospecs = (ScaleByAdamState(count=P(), mu=pspecs, nu=pspecs), (), ())
    step = model.make_train_step(optimizer)
    measure("deepfm/train v5 (2D tables + 2D batch)", step,
            (params, opt_state, batch),
            (named(mesh, pspecs), named(mesh, ospecs), named(mesh, bspecs)),
            (named(mesh, pspecs), named(mesh, ospecs), named(mesh, P())),
            donate=(0, 1), mesh=mesh)


def run_clax_v3(mesh):
    from repro.configs import clax_baidu
    cell = clax_baidu.build_cell("train_batch", mesh, kind="ubm")
    pspecs, params = clax_baidu._param_specs(clax_baidu.make_model("ubm"))
    dp = ("data",) if "pod" not in mesh.axis_names else ("pod", "data")
    pspecs["attraction"]["table"] = P(dp + ("model",), None)
    optimizer = optim_lib.adamw(3e-3, weight_decay=1e-4)
    opt_state = jax.eval_shape(optimizer.init, params)
    ospecs = (ScaleByAdamState(count=P(), mu=pspecs, nu=pspecs), (), ())
    bspecs = {k: P(dp, None) for k in
              ("positions", "query_doc_ids", "clicks", "mask")}
    measure("clax-ubm/train v3 (2D-sharded table)", cell.fn,
            (params, opt_state, cell.args[2]),
            (named(mesh, pspecs), named(mesh, ospecs), named(mesh, bspecs)),
            (named(mesh, pspecs), named(mesh, ospecs), named(mesh, P())),
            donate=(0, 1), mesh=mesh)


def run_graphsage(mesh):
    """Cell E: graphsage x ogb_products — collective-bound full-graph
    training. Baseline: edges random-sharded, nodes replicated, psum per
    layer. Optimized: dst-partitioned edges (see graphsage.py)."""
    import dataclasses as dc
    from repro.configs import graphsage_reddit

    cell = graphsage_reddit.build_cell("ogb_products", mesh)
    measure("graphsage/ogb_products baseline (psum)", cell.fn, cell.args,
            cell.in_shardings, cell.out_shardings, donate=cell.donate,
            mesh=mesh)
    # partitioned variant: same shapes, flag flipped inside a rebuilt step
    from repro import optim as ol
    from repro.models.gnn import SAGEConfig, make_full_graph_train_step
    info = graphsage_reddit.SHAPES["ogb_products"]
    n_nodes = info["n_nodes"] - (info["n_nodes"] % 256)  # divisible contract
    cfg = SAGEConfig(name="graphsage", n_layers=2, d_in=info["d_feat"],
                     d_hidden=128, n_classes=info["n_classes"],
                     partitioned_edges=True)
    optimizer = ol.adam(1e-2)
    fn = make_full_graph_train_step(cfg, optimizer, mesh)
    # rebuild args with the truncated-to-divisible node count
    n_edges = graphsage_reddit._pad_edges(info["n_edges"], mesh)
    graph = {
        "features": sds((n_nodes, info["d_feat"]), jnp.float32),
        "src": sds((n_edges,), jnp.int32), "dst": sds((n_edges,), jnp.int32),
        "edge_weight": sds((n_edges,), jnp.float32),
        "degree_inv": sds((n_nodes,), jnp.float32),
        "labels": sds((n_nodes,), jnp.int32),
    }
    axes = tuple(mesh.axis_names)
    gspecs = {"features": P(None, None), "src": P(axes), "dst": P(axes),
              "edge_weight": P(axes), "degree_inv": P(axes),
              "labels": P(None)}
    params, opt_state, pspecs, ospecs = graphsage_reddit._params_opt(
        cfg, optimizer)
    measure("graphsage/ogb_products v1 (dst-partitioned)", fn,
            (params, opt_state, graph),
            (named(mesh, pspecs), named(mesh, ospecs), named(mesh, gspecs)),
            (named(mesh, pspecs), named(mesh, ospecs), named(mesh, P())),
            donate=(0, 1), mesh=mesh)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--cell", required=True,
                    choices=["llama3-405b", "llama-decode", "deepfm", "clax-ubm",
                             "graphsage", "deepfm-v3",
                             "deepfm-v4", "deepfm-v5", "clax-ubm-v3"])
    ap.add_argument("--multi-pod", action="store_true")
    args = ap.parse_args()
    mesh = make_production_mesh(multi_pod=args.multi_pod)
    {"llama3-405b": run_llama, "llama-decode": run_llama_decode,
     "deepfm": run_deepfm, "graphsage": run_graphsage,
     "clax-ubm": run_clax, "deepfm-v3": run_deepfm_v3,
     "deepfm-v4": run_deepfm_v4, "deepfm-v5": run_deepfm_v5,
     "clax-ubm-v3": run_clax_v3}[args.cell](mesh)


if __name__ == "__main__":
    main()
