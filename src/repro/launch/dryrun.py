"""Multi-pod dry-run: lower + compile every (arch × shape × mesh) cell.

Run it as a module (``python -m repro.launch.dryrun``): its ``__main__``
block forces 512 CPU host devices before the backend starts, so
``jax.make_mesh`` finds the production mesh. Importing the module sets
nothing — tests and benches keep their own devices.

Per cell it jit-lowers the step with explicit in/out shardings resolved from
the logical-axis rules, compiles, and records memory_analysis(),
cost_analysis() and the collective-bytes breakdown parsed from the HLO —
everything §Roofline consumes. Results accumulate in a JSON file so the
(slow, single-CPU) compiles are resumable.

Usage:
  python -m repro.launch.dryrun --arch qwen3-8b --shape train_4k --mesh single
  python -m repro.launch.dryrun --all [--mesh single|multi|both] [--out f.json]
"""

import argparse
import json
import os
import time
import traceback
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.config import SHAPES, get_arch, shape_applicable
from repro.config.base import ArchFamily, ModelConfig, OptimizerConfig, ShapeConfig, TrainConfig
from repro.configs import ASSIGNED_ARCHS
from repro.launch.mesh import make_production_mesh
from repro.launch.roofline import collective_bytes_from_hlo, roofline_terms
from repro.launch.sharding import axis_rules, current_rules, tree_shardings
from repro.launch.steps import (
    batch_axes,
    input_specs,
    make_prefill_step,
    make_serve_step,
    make_train_step,
    opt_state_axes,
)
from repro.models.transformer import lm_init

DEFAULT_OUT = "dryrun_results.json"


def _shapes_tree(tree):
    return jax.tree_util.tree_map(lambda s: tuple(s.shape), tree)


def _train_cfg(cfg: ModelConfig, shape: ShapeConfig,
               microbatches: Optional[int] = None) -> TrainConfig:
    # Big models need grad accumulation to bound live activations; the 1T MoE
    # runs Adafactor (factored second moments) per DESIGN.md §4. Microbatch
    # counts are the memory/collective trade: every microbatch re-gathers the
    # FSDP weights (§Perf H5) — use the fewest that fit HBM.
    if microbatches is None:
        big = cfg.param_count() > 3e10
        microbatches = 8 if big else (2 if cfg.param_count() > 5e9 else 1)
    opt_name = "adafactor" if cfg.param_count() > 3e11 else "adamw"
    return TrainConfig(optimizer=OptimizerConfig(name=opt_name),
                       microbatches=microbatches)


def lower_cell(arch: str, shape_name: str, multi_pod: bool,
               microbatches: Optional[int] = None) -> Dict[str, Any]:
    """Lower + compile one cell; return the §Dry-run / §Roofline record."""
    cfg = get_arch(arch)
    if cfg.family == ArchFamily.CNN:
        raise SystemExit(f"{arch} is a federated-plane CNN config; the dry-run "
                         "covers the assigned LM architectures")
    shape = SHAPES[shape_name]
    if not shape_applicable(cfg, shape):
        return {"status": "skipped",
                "reason": "long_500k requires sub-quadratic attention (DESIGN.md §5)"}

    mesh = make_production_mesh(multi_pod=multi_pod)
    t0 = time.time()

    from repro.models.layers import abstract_init
    with abstract_init():
        params_shapes, params_axes = lm_init(cfg, 0)

    with mesh:
        p_shard = tree_shardings(mesh, params_shapes, params_axes)
        specs = input_specs(cfg, shape)
        b_axes = batch_axes(cfg, shape)
        b_shard = tree_shardings(mesh, specs, b_axes)

        # Donation mirrors production: params/opt-state update in place for
        # train; the KV/recurrent cache updates in place for decode (without
        # it every step would copy the multi-GB cache — visible in the
        # memory roofline term).
        if shape.mode == "train":
            tc = _train_cfg(cfg, shape, microbatches)
            step, opt_init = make_train_step(cfg, tc)
            opt_shapes = jax.eval_shape(opt_init, params_shapes)
            o_axes = opt_state_axes(cfg, params_axes, tc.optimizer)
            o_shard = _opt_shardings(mesh, opt_shapes, o_axes, p_shard)
            jitted = jax.jit(step,
                             in_shardings=(p_shard, o_shard, b_shard),
                             out_shardings=(p_shard, o_shard, None),
                             donate_argnums=(0, 1))
            lowered = jitted.lower(params_shapes, opt_shapes, specs)
        elif shape.mode == "prefill":
            step = make_prefill_step(cfg)
            jitted = jax.jit(step, in_shardings=(p_shard, b_shard),
                             out_shardings=None)
            lowered = jitted.lower(params_shapes, specs)
        else:  # decode
            step = make_serve_step(cfg)
            jitted = jax.jit(step,
                             in_shardings=(p_shard, b_shard["state"],
                                           b_shard["tokens"], b_shard["length"]),
                             out_shardings=(None, b_shard["state"]),
                             donate_argnums=(1,))
            lowered = jitted.lower(params_shapes, specs["state"],
                                   specs["tokens"], specs["length"])

        t_lower = time.time() - t0
        compiled = lowered.compile()
        t_compile = time.time() - t0 - t_lower

        mem = compiled.memory_analysis()
        cost = compiled.cost_analysis()
        coll = collective_bytes_from_hlo(compiled.as_text())
        n_dev = mesh.devices.size

    # Whole-program cost_analysis undercounts scan bodies (counted once, not
    # × trip count) — use per-component analysis for the roofline terms.
    tc = _train_cfg(cfg, shape, microbatches) if shape.mode == "train" else None
    comp = component_cost_analysis(cfg, shape, mesh, tc)

    rec = {
        "status": "ok",
        "arch": arch,
        "shape": shape_name,
        "mesh": "multi" if multi_pod else "single",
        "num_devices": int(n_dev),
        "lower_s": round(t_lower, 1),
        "compile_s": round(t_compile, 1),
        "flops_total": comp["flops"],
        "bytes_total": comp["bytes"],
        "collective_bytes": {"total": comp["coll"], "wholeprog": coll},
        "wholeprog_flops": float(cost.get("flops", 0.0)),
        "wholeprog_bytes": float(cost.get("bytes accessed", 0.0)),
        "memory": _mem_dict(mem),
        "params": _actual_params(params_shapes),
        "active_params": _actual_active_params(cfg, params_shapes),
        "tokens": shape.tokens if shape.mode != "decode" else shape.global_batch,
        "mode": shape.mode,
    }
    rec["roofline"] = roofline_terms(rec)
    return rec


def component_cost_analysis(cfg: ModelConfig, shape: ShapeConfig, mesh,
                            tc: Optional[TrainConfig]) -> Dict[str, float]:
    """Whole-step FLOPs/bytes/collective-bytes via per-component analysis.

    XLA's cost_analysis counts a while/scan BODY exactly once regardless of
    trip count (verified on this backend), so whole-program numbers undercount
    layer-scanned models by ~L×. We therefore cost the scan body (one block)
    separately and scale: step = M_microbatches × (L × block + embed/head)
    [+ optimizer once, train only]. Remat is accounted exactly: a remat'd
    block executes fwd (forward scan) + fwd+bwd (backward scan).
    """
    import functools as ft
    from repro.models.layers import abstract_init
    from repro.models import transformer as T

    with abstract_init():
        params_shapes, params_axes = lm_init(cfg, 0)
    blocks_sds = params_shapes["blocks"]
    block_sds = jax.tree_util.tree_map(
        lambda s: jax.ShapeDtypeStruct(s.shape[1:], s.dtype), blocks_sds)
    block_axes = jax.tree_util.tree_map(lambda s, ax: tuple(ax[1:]),
                                        blocks_sds, params_axes["blocks"])
    L = block_sds and jax.tree_util.tree_leaves(blocks_sds)[0].shape[0]
    M = tc.microbatches if (tc and shape.mode == "train") else 1
    B = shape.global_batch // M
    S = shape.seq_len
    act_dt = jnp.dtype(cfg.dtype)

    def analyzed(fn, in_shardings, *sds, donate=()):
        lowered = jax.jit(fn, in_shardings=in_shardings,
                          donate_argnums=donate).lower(*sds)
        comp = lowered.compile()
        c = comp.cost_analysis()
        coll = collective_bytes_from_hlo(comp.as_text())
        return {"flops": float(c.get("flops", 0.0)),
                "bytes": float(c.get("bytes accessed", 0.0)),
                "coll": float(coll["total"])}

    with mesh:
        b_shard = tree_shardings(mesh, block_sds, block_axes)
        from repro.launch.sharding import named_sharding
        x_sds = jax.ShapeDtypeStruct((B, S, cfg.d_model), act_dt)
        x_sh = named_sharding(mesh, x_sds.shape, ("batch", None, None))
        pos_sds = jax.ShapeDtypeStruct((B, S), jnp.int32)
        pos_sh = named_sharding(mesh, pos_sds.shape, ("batch", None))

        if shape.mode in ("train", "prefill"):
            def blk_fwd(bp, x, pos):
                return T._block_apply(cfg, bp, x, pos)
            fwd = analyzed(blk_fwd, (b_shard, x_sh, pos_sh), block_sds, x_sds, pos_sds)

            if shape.mode == "train":
                def blk_grad(bp, x, pos):
                    def f(bp_, x_):
                        y = T._block_apply(cfg, bp_, x_, pos)
                        return jnp.sum(y.astype(jnp.float32) ** 2)
                    return jax.grad(f, argnums=(0, 1))(bp, x)
                grd = analyzed(blk_grad, (b_shard, x_sh, pos_sh), block_sds, x_sds, pos_sds)
                per_block = {k: (fwd[k] + grd[k]) if cfg.remat else grd[k]
                             for k in ("flops", "bytes", "coll")}
            else:
                per_block = fwd

            # embed + head (+ loss & their grads for train), once per microbatch
            specs = input_specs(cfg, ShapeConfig(shape.name, S, B, shape.mode))
            eh_axes = batch_axes(cfg, ShapeConfig(shape.name, S, B, shape.mode))
            eh_shard = tree_shardings(mesh, specs, eh_axes)
            emb_parts = {k: params_shapes[k] for k in ("embed", "head", "final_norm")}
            emb_axes = {k: params_axes[k] for k in ("embed", "head", "final_norm")}
            emb_shard = tree_shardings(mesh, emb_parts, emb_axes)

            def eh_fn(pp, batch):
                dt = act_dt
                if cfg.family == ArchFamily.AUDIO:
                    x = batch["frontend"].astype(dt)
                elif cfg.family == ArchFamily.VLM:
                    te = T.embed_apply(cfg, pp["embed"], batch["tokens"])
                    x = jnp.concatenate([batch["frontend"].astype(dt), te], axis=1)
                else:
                    x = T.embed_apply(cfg, pp["embed"], batch["tokens"])
                x = T.rmsnorm(pp["final_norm"], x, cfg.norm_eps)
                if shape.mode == "train":
                    labels = batch["labels"]
                    logits = T.unembed_apply(cfg, pp["embed"], pp["head"], x[:, :-1])
                    return T.cross_entropy(logits[:, -(labels.shape[1] - 1):],
                                           labels[:, 1:]).mean()
                return T.unembed_apply(cfg, pp["embed"], pp["head"], x)

            if shape.mode == "train":
                def eh_grad(pp, batch):
                    return jax.grad(eh_fn)(pp, batch)
                eh = analyzed(eh_grad, (emb_shard, eh_shard), emb_parts, specs)
            else:
                eh = analyzed(eh_fn, (emb_shard, eh_shard), emb_parts, specs)

            total = {k: M * (L * per_block[k] + eh[k]) for k in ("flops", "bytes", "coll")}

            if shape.mode == "train":
                opt_init_, opt_update_ = __import__("repro.optim", fromlist=["make_optimizer"]
                                                    ).make_optimizer(tc.optimizer)
                opt_shapes = jax.eval_shape(opt_init_, params_shapes)
                o_axes = opt_state_axes(cfg, params_axes, tc.optimizer)
                o_shard = _opt_shardings(mesh, opt_shapes, o_axes, None)
                p_shard = tree_shardings(mesh, params_shapes, params_axes)

                def opt_fn(g, st, p):
                    up, st2 = opt_update_(g, st, p)
                    p2 = jax.tree_util.tree_map(
                        lambda pp, uu: (pp.astype(jnp.float32)
                                        + uu.astype(jnp.float32)).astype(pp.dtype), p, up)
                    return p2, st2
                opt = analyzed(opt_fn, (p_shard, o_shard, p_shard),
                               params_shapes, opt_shapes, params_shapes,
                               donate=(1, 2))
                total = {k: total[k] + opt[k] for k in total}
            return total

        # decode: one block-decode × L + embed/head fwd
        state_sds = jax.eval_shape(lambda: T.init_decode_state(cfg, B, S))
        layer_state = jax.tree_util.tree_map(
            lambda s: jax.ShapeDtypeStruct(s.shape[1:], s.dtype), state_sds)
        st_axes_full = T.decode_state_axes(cfg)
        layer_state_axes = jax.tree_util.tree_map(lambda s, ax: tuple(ax[1:]),
                                                  state_sds, st_axes_full)
        st_shard = tree_shardings(mesh, layer_state, layer_state_axes)
        x1 = jax.ShapeDtypeStruct((B, 1, cfg.d_model), act_dt)
        x1_sh = named_sharding(mesh, x1.shape, ("cache_batch", None, None))
        len_sds = jax.ShapeDtypeStruct((B,), jnp.int32)
        len_sh = named_sharding(mesh, (B,), ("cache_batch",))

        def blk_dec(bp, x, st, ln):
            return T._block_decode(cfg, bp, x, st, ln)
        dec = analyzed(blk_dec, (b_shard, x1_sh, st_shard, len_sh),
                       block_sds, x1, layer_state, len_sds, donate=(2,))

        emb_parts = {k: params_shapes[k] for k in ("embed", "head", "final_norm")}
        emb_axes = {k: params_axes[k] for k in ("embed", "head", "final_norm")}
        emb_shard = tree_shardings(mesh, emb_parts, emb_axes)
        tok_sds = (jax.ShapeDtypeStruct((B, cfg.d_model), act_dt)
                   if cfg.family == ArchFamily.AUDIO
                   else jax.ShapeDtypeStruct((B,), jnp.int32))
        tok_sh = named_sharding(mesh, tok_sds.shape,
                                ("cache_batch", None) if cfg.family == ArchFamily.AUDIO
                                else ("cache_batch",))

        def eh_dec(pp, tok):
            if cfg.family == ArchFamily.AUDIO:
                x = tok.astype(act_dt)[:, None, :]
            else:
                x = T.embed_apply(cfg, pp["embed"], tok[:, None])
            x = T.rmsnorm(pp["final_norm"], x, cfg.norm_eps)
            return T.unembed_apply(cfg, pp["embed"], pp["head"], x)
        eh = analyzed(eh_dec, (emb_shard, tok_sh), emb_parts, tok_sds)

        return {k: L * dec[k] + eh[k] for k in ("flops", "bytes", "coll")}


def _actual_params(params_shapes) -> int:
    return int(sum(int(np.prod(l.shape)) for l in jax.tree_util.tree_leaves(params_shapes)))


def _actual_active_params(cfg: ModelConfig, params_shapes) -> int:
    """Total params minus the unactivated expert fraction (per token)."""
    total = _actual_params(params_shapes)
    if not cfg.is_moe:
        return total
    blocks = params_shapes["blocks"]
    moe = blocks.get("moe", {})
    expert_params = sum(int(np.prod(moe[k].shape))
                        for k in ("w_gate", "w_up", "w_down") if k in moe)
    inactive = expert_params * (cfg.num_experts - cfg.experts_per_token) / cfg.num_experts
    return int(total - inactive)


def _mem_dict(mem) -> Dict[str, float]:
    out = {}
    for k in ("generated_code_size_in_bytes", "argument_size_in_bytes",
              "output_size_in_bytes", "temp_size_in_bytes", "alias_size_in_bytes"):
        try:
            out[k] = float(getattr(mem, k))
        except Exception:
            pass
    return out


def _opt_shardings(mesh, opt_shapes, o_axes, p_shard):
    from repro.launch.sharding import named_sharding
    is_ax = lambda x: isinstance(x, tuple) and all(isinstance(e, (str, type(None))) for e in x)

    def resolve(shapes, axes):
        return jax.tree_util.tree_map(
            lambda s, a: named_sharding(mesh, s.shape, a if a is not None else
                                        (None,) * len(s.shape)),
            shapes, axes, is_leaf=lambda x: isinstance(x, jax.ShapeDtypeStruct))

    # OptState(step, inner): map manually to tolerate structural differences
    step_sh = named_sharding(mesh, (), ())
    inner = jax.tree_util.tree_map(
        lambda s, a: named_sharding(mesh, s.shape, a if a is not None else (None,) * len(s.shape)),
        opt_shapes.inner, o_axes["inner"], is_leaf=lambda x: isinstance(x, jax.ShapeDtypeStruct))
    import repro.optim.optimizers as O
    return O.OptState(step_sh, inner)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--mesh", default="both", choices=["single", "multi", "both"])
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--out", default=DEFAULT_OUT)
    ap.add_argument("--microbatches", type=int, default=None)
    args = ap.parse_args()

    results: Dict[str, Any] = {}
    if os.path.exists(args.out):
        with open(args.out) as f:
            results = json.load(f)

    archs = list(ASSIGNED_ARCHS) if args.all or not args.arch else [args.arch]
    shapes = list(SHAPES) if args.all or not args.shape else [args.shape]
    meshes = {"single": [False], "multi": [True], "both": [False, True]}[args.mesh]

    for arch in archs:
        for shape in shapes:
            for mp in meshes:
                key = f"{arch}|{shape}|{'multi' if mp else 'single'}"
                if results.get(key, {}).get("status") == "ok":
                    print(f"[skip cached] {key}")
                    continue
                print(f"[lower+compile] {key} ...", flush=True)
                try:
                    rec = lower_cell(arch, shape, mp, args.microbatches)
                except Exception as e:
                    rec = {"status": "error", "error": f"{type(e).__name__}: {e}",
                           "traceback": traceback.format_exc()[-2000:]}
                results[key] = rec
                with open(args.out, "w") as f:
                    json.dump(results, f, indent=1)
                if rec["status"] == "ok":
                    r = rec["roofline"]
                    print(f"  ok: compile={rec['compile_s']}s "
                          f"compute={r['compute_s']:.4f}s memory={r['memory_s']:.4f}s "
                          f"collective={r['collective_s']:.4f}s dominant={r['dominant']}")
                else:
                    print(f"  {rec['status']}: {rec.get('reason', rec.get('error'))}")

    n_ok = sum(1 for v in results.values() if v.get("status") == "ok")
    n_skip = sum(1 for v in results.values() if v.get("status") == "skipped")
    n_err = sum(1 for v in results.values() if v.get("status") == "error")
    print(f"\n=== dry-run summary: {n_ok} ok, {n_skip} skipped, {n_err} errors ===")


if __name__ == "__main__":
    # Read when the backend starts (first device query), not at import.
    os.environ["XLA_FLAGS"] = (
        os.environ.get("XLA_FLAGS", "")
        + " --xla_force_host_platform_device_count=512").strip()
    main()
