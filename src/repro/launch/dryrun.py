import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile every (architecture x input shape)
cell on the production mesh, prove it fits, and extract roofline terms.

MUST set XLA_FLAGS above before ANY other import — jax locks the device
count at first initialization. This is the only module that fabricates 512
host devices; smoke tests and benchmarks see the real single CPU device.

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch qwen3-32b \
      --shape train_4k [--multi-pod] [--rules baseline] [--out results/...]
  PYTHONPATH=src python -m repro.launch.dryrun --all [--multi-pod]
"""
import argparse
import json
import sys
import time
import traceback

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.configs.base import SHAPES, ArchConfig, ShapeConfig, get_arch, \
    list_archs
from repro.core.roofline import model_flops_estimate, report_from_hlo
from repro.data.specs import batch_specs
from repro.launch.mesh import make_production_mesh, mesh_desc, n_chips
from repro.parallel.jax_compat import set_mesh, use_compile_cache
from repro.models import model as M
from repro.models import registry
from repro.models.param import is_spec, tree_sds
from repro.optim.adamw import AdamWConfig
from repro.parallel.sharding import (RULE_VARIANTS, ShardingRules, act_pspec,
                                     param_pspec, use_rules)
from repro.train.steps import TrainState, make_prefill_step, \
    make_serve_step, make_train_step

# ---------------------------------------------------------------------------
# per-cell configuration (memory-driven; see EXPERIMENTS.md §Dry-run)
# ---------------------------------------------------------------------------

SERVE_DTYPE = jnp.bfloat16


# Microbatch counts size the per-layer scan residual (B_local x S x D bf16
# x n_layers must fit alongside params+moments in 16 GB HBM). Moment dtype
# bf16 where fp32 optimizer state alone would blow the budget.
_TRAIN_OVERRIDES = {
    "deepseek-v2-236b": dict(moment_dtype=jnp.bfloat16, microbatches=8,
                             accum_dtype=jnp.bfloat16),
    "qwen3-32b": dict(moment_dtype=jnp.float32, microbatches=8),
    "qwen2.5-14b": dict(moment_dtype=jnp.float32, microbatches=8),
    "qwen2.5-3b": dict(moment_dtype=jnp.float32, microbatches=4),
    "qwen1.5-4b": dict(moment_dtype=jnp.float32, microbatches=4),
    "hymba-1.5b": dict(moment_dtype=jnp.float32, microbatches=4),
    "hubert-xlarge": dict(moment_dtype=jnp.float32, microbatches=4),
    "mamba2-780m": dict(moment_dtype=jnp.float32, microbatches=4),
    "paligemma-3b": dict(moment_dtype=jnp.float32, microbatches=2),
    "granite-moe-1b-a400m": dict(moment_dtype=jnp.float32, microbatches=1),
}


def train_overrides(arch: str) -> dict:
    ov = dict(_TRAIN_OVERRIDES.get(
        arch, dict(moment_dtype=jnp.float32, microbatches=1)))
    ov.setdefault("remat", "full")
    ov.setdefault("accum_dtype", jnp.float32)
    return ov


def rules_for(cell_kind: str, rules_name: str) -> ShardingRules:
    if rules_name != "auto":
        return RULE_VARIANTS[rules_name]
    # decode cells shard the KV cache along kv_seq (flash-decoding);
    # train/prefill use the baseline FSDP x TP table
    return RULE_VARIANTS["kv_seq" if cell_kind == "decode"
                         else "baseline"]


# ---------------------------------------------------------------------------
# abstract inputs + shardings
# ---------------------------------------------------------------------------

BATCH_AXES = {
    "tokens": ("batch", "seq"),
    "labels": ("batch", "seq"),
    "frames": ("batch", "seq", None),
    "patches": ("batch", None, None),
    "cache_len": (),
}


def batch_pspecs(specs: dict, rules: ShardingRules, mesh) -> dict:
    mesh_shape = dict(zip(mesh.axis_names, mesh.devices.shape))
    out = {}
    for k, s in specs.items():
        axes = BATCH_AXES[k]
        out[k] = act_pspec(rules, axes, s.shape, mesh_shape)
    return out


def cache_axes(cfg: ArchConfig, entry) -> tuple:
    """Logical axes for one layer-cache entry (pre-stacking)."""
    if cfg.family == "ssm":
        conv_axes = (("batch", None, "ssm_inner"),
                     ("batch", None, None), ("batch", None, None))
        return (conv_axes, ("batch", "heads", None, None))
    if cfg.family == "hybrid":
        kv = (("batch", "kv_seq", "kv_heads", None),) * 2
        conv_axes = (("batch", None, "ssm_inner"),
                     ("batch", None, None), ("batch", None, None))
        return (kv, (conv_axes, ("batch", "heads", None, None)))
    if cfg.mla:
        return (("batch", "kv_seq", None), ("batch", "kv_seq", None))
    return (("batch", "kv_seq", "kv_heads", None),) * 2


def cache_sds(cfg: ArchConfig, B: int, Smax: int, dtype):
    L = registry.n_scanned_layers(cfg)
    entry = M.layer_cache_struct(cfg, B, Smax, dtype)
    stacked = jax.tree.map(
        lambda s: jax.ShapeDtypeStruct((L, *s.shape), s.dtype), entry)
    out = {"layers": stacked}
    if cfg.moe and cfg.moe.first_dense_layers:
        out["dense0"] = M.mla_cache_struct(cfg, B, Smax, dtype)
    return out


def cache_pspecs(cfg: ArchConfig, B: int, Smax: int, rules: ShardingRules,
                 mesh, dtype):
    mesh_shape = dict(zip(mesh.axis_names, mesh.devices.shape))
    entry_axes = cache_axes(cfg, None)
    entry = M.layer_cache_struct(cfg, B, Smax, dtype)

    def resolve(s, axes):
        return act_pspec(rules, (None, *axes), (0, *s.shape), mesh_shape)

    stacked = jax.tree.map(resolve, entry, entry_axes,
                           is_leaf=lambda x: isinstance(
                               x, jax.ShapeDtypeStruct))
    out = {"layers": stacked}
    if cfg.moe and cfg.moe.first_dense_layers:
        d0 = M.mla_cache_struct(cfg, B, Smax, dtype)
        d0_axes = (("batch", "kv_seq", None), ("batch", "kv_seq", None))
        out["dense0"] = jax.tree.map(
            lambda s, a: act_pspec(rules, a, s.shape, mesh_shape),
            d0, d0_axes,
            is_leaf=lambda x: isinstance(x, jax.ShapeDtypeStruct))
    return out


def param_pspecs(cfg: ArchConfig, rules: ShardingRules, mesh):
    specs = registry.param_specs(cfg)
    mesh_shape = dict(zip(mesh.axis_names, mesh.devices.shape))
    return jax.tree.map(
        lambda s: param_pspec(rules, s.axes, s.shape, mesh_shape),
        specs, is_leaf=is_spec)


def serve_param_sds(cfg: ArchConfig):
    specs = registry.param_specs(cfg)

    def cast(s):
        dt = SERVE_DTYPE if jnp.issubdtype(s.dtype, jnp.floating) \
            else s.dtype
        return jax.ShapeDtypeStruct(s.shape, dt)
    return jax.tree.map(cast, specs, is_leaf=is_spec)


# ---------------------------------------------------------------------------
# cell lowering
# ---------------------------------------------------------------------------

def lower_cell(cfg: ArchConfig, shape: ShapeConfig, mesh,
               rules: ShardingRules, *, grad_compression=None,
               remat_override=None, extra_note=""):
    """Returns (lowered, meta). Must be called inside jax.set_mesh(mesh)."""
    ov = train_overrides(cfg.name)
    if remat_override:
        ov["remat"] = remat_override
    kind = shape.kind
    note = extra_note

    if kind == "train":
        opt = AdamWConfig(moment_dtype=ov["moment_dtype"])
        step = make_train_step(cfg, opt, microbatches=ov["microbatches"],
                               remat=ov["remat"],
                               accum_dtype=ov["accum_dtype"],
                               grad_compression=grad_compression)
        p_ps = param_pspecs(cfg, rules, mesh)
        p_sds = tree_sds(registry.param_specs(cfg))
        mom = jax.tree.map(lambda s: jax.ShapeDtypeStruct(
            s.shape, ov["moment_dtype"]), p_sds)
        state_sds = TrainState(
            params=p_sds,
            opt_state={"m": mom, "v": mom,
                       "step": jax.ShapeDtypeStruct((), jnp.int32)},
            step=jax.ShapeDtypeStruct((), jnp.int32))
        state_ps = TrainState(
            params=p_ps,
            opt_state={"m": p_ps, "v": p_ps, "step": P()},
            step=P())
        b_sds = batch_specs(cfg, shape)
        b_ps = batch_pspecs(b_sds, rules, mesh)
        jitted = jax.jit(step, in_shardings=(state_ps, b_ps),
                         out_shardings=(state_ps, None),
                         donate_argnums=(0,))
        lowered = jitted.lower(state_sds, b_sds)
    elif kind == "prefill":
        if cfg.encoder_only:
            # encoder: inference forward (no cache/decode exists)
            def enc_step(params, batch):
                logits, _ = M.forward(params, batch, cfg, remat="none",
                                      dtype=SERVE_DTYPE)
                return logits
            p_ps = param_pspecs(cfg, rules, mesh)
            b_sds = batch_specs(cfg, shape)
            b_ps = batch_pspecs(b_sds, rules, mesh)
            jitted = jax.jit(enc_step, in_shardings=(p_ps, b_ps),
                             out_shardings=None)
            lowered = jitted.lower(serve_param_sds(cfg), b_sds)
            note += "encoder-only: prefill lowers the inference forward"
        else:
            step = make_prefill_step(cfg, remat="none", dtype=SERVE_DTYPE)
            p_ps = param_pspecs(cfg, rules, mesh)
            b_sds = batch_specs(cfg, shape)
            b_ps = batch_pspecs(b_sds, rules, mesh)
            jitted = jax.jit(step, in_shardings=(p_ps, b_ps),
                             out_shardings=None)
            lowered = jitted.lower(serve_param_sds(cfg), b_sds)
    else:  # decode
        B = shape.global_batch
        Smax = shape.seq_len
        step = make_serve_step(cfg, dtype=SERVE_DTYPE)
        p_ps = param_pspecs(cfg, rules, mesh)
        c_sds = cache_sds(cfg, B, Smax, SERVE_DTYPE)
        c_ps = cache_pspecs(cfg, B, Smax, rules, mesh, SERVE_DTYPE)
        b_sds = batch_specs(cfg, shape)
        b_ps = batch_pspecs(b_sds, rules, mesh)
        jitted = jax.jit(step, in_shardings=(p_ps, c_ps, b_ps),
                         out_shardings=(None, c_ps),
                         donate_argnums=(1,))
        lowered = jitted.lower(serve_param_sds(cfg), c_sds, b_sds)
    return lowered, {"note": note, "rules": rules.name}


def run_cell(arch: str, shape_name: str, *, multi_pod: bool = False,
             rules_name: str = "auto", out_dir: str = "results/dryrun",
             grad_compression=None, remat_override=None,
             attention: str = "baseline", segments: bool = False,
             moe: str = "gspmd", tag: str = "") -> dict:
    from repro.models.blocks import MOE_SHARD_MAP
    from repro.models.common import ATTENTION_VARIANT
    from repro.models.model import STATIC_WINDOW_SEGMENTS
    ATTENTION_VARIANT["impl"] = attention
    STATIC_WINDOW_SEGMENTS["enabled"] = segments
    MOE_SHARD_MAP["enabled"] = moe == "shard_map"
    cfg = get_arch(arch)
    shape = SHAPES[shape_name]
    support = cfg.supported_shapes()[shape_name]
    mesh = make_production_mesh(multi_pod=multi_pod)
    mdesc = mesh_desc(mesh)
    cell_id = f"{arch}-{shape_name}" + (f"-{tag}" if tag else "")
    result: dict = {"arch": arch, "shape": shape_name, "mesh": mdesc,
                    "chips": n_chips(mesh), "status": "ok", "tag": tag}
    if support != "ok":
        result["status"] = support
        _dump(result, out_dir, multi_pod, cell_id)
        print(f"[dryrun] {cell_id} on {mdesc}: {support}")
        return result

    rules = rules_for(shape.kind, rules_name)
    t0 = time.time()
    try:
        with set_mesh(mesh), use_rules(rules):
            lowered, meta = lower_cell(cfg, shape, mesh, rules,
                                       grad_compression=grad_compression,
                                       remat_override=remat_override)
            t_lower = time.time() - t0
            compiled = lowered.compile()
            t_compile = time.time() - t0 - t_lower
            mem = compiled.memory_analysis()
            cost = compiled.cost_analysis()
            txt = compiled.as_text()
        per_dev_bytes = (mem.argument_size_in_bytes
                         + mem.output_size_in_bytes
                         + mem.temp_size_in_bytes
                         - mem.alias_size_in_bytes)
        rep = report_from_hlo(
            txt, arch=arch, shape=shape_name, mesh=mdesc,
            n_chips=n_chips(mesh),
            model_flops=model_flops_estimate(cfg, shape),
            bytes_per_device=per_dev_bytes,
            xla_cost_flops=float(cost.get("flops", 0.0)),
            notes=meta["note"])
        result.update(rep.to_json())
        result.update(
            rules=meta["rules"],
            lower_s=round(t_lower, 1), compile_s=round(t_compile, 1),
            memory_analysis={
                "argument_size_in_bytes": mem.argument_size_in_bytes,
                "output_size_in_bytes": mem.output_size_in_bytes,
                "temp_size_in_bytes": mem.temp_size_in_bytes,
                "alias_size_in_bytes": mem.alias_size_in_bytes,
                "generated_code_size_in_bytes":
                    mem.generated_code_size_in_bytes,
            },
            hbm_gb_per_device=round(per_dev_bytes / 2 ** 30, 3))
        print(f"[dryrun] {cell_id} on {mdesc}: OK "
              f"{per_dev_bytes / 2**30:.2f} GiB/dev, "
              f"compute {rep.compute_s*1e3:.1f} ms, "
              f"memory {rep.memory_s*1e3:.1f} ms, "
              f"collective {rep.collective_s*1e3:.1f} ms, "
              f"dominant={rep.dominant}, RF={rep.roofline_fraction:.2f} "
              f"(lower {t_lower:.0f}s compile {t_compile:.0f}s)")
    except Exception as e:  # noqa
        result["status"] = f"FAIL: {type(e).__name__}: {e}"
        result["traceback"] = traceback.format_exc()[-4000:]
        print(f"[dryrun] {cell_id} on {mdesc}: FAILED {type(e).__name__}: "
              f"{str(e)[:200]}")
    _dump(result, out_dir, multi_pod, cell_id)
    return result


def _dump(result: dict, out_dir: str, multi_pod: bool, cell_id: str):
    d = os.path.join(out_dir, "multipod" if multi_pod else "singlepod")
    os.makedirs(d, exist_ok=True)
    with open(os.path.join(d, f"{cell_id}.json"), "w") as f:
        json.dump(result, f, indent=1, default=str)


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None, choices=[*SHAPES, None])
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--rules", default="auto")
    ap.add_argument("--grad-compression", default=None)
    ap.add_argument("--remat", default=None)
    ap.add_argument("--attention", default="baseline",
                    choices=["baseline", "triangle"])
    ap.add_argument("--segments", action="store_true",
                    help="static-window layer segments (hymba hillclimb)")
    ap.add_argument("--moe", default="gspmd",
                    choices=["gspmd", "shard_map"])
    ap.add_argument("--tag", default="")
    ap.add_argument("--out", default="results/dryrun")
    args = ap.parse_args(argv)
    use_compile_cache()

    cells: list[tuple[str, str]] = []
    archs = [args.arch] if args.arch else list_archs()
    shapes = [args.shape] if args.shape else list(SHAPES)
    for a in archs:
        for s in shapes:
            cells.append((a, s))
    failures = 0
    for a, s in cells:
        r = run_cell(a, s, multi_pod=args.multi_pod, rules_name=args.rules,
                     out_dir=args.out,
                     grad_compression=args.grad_compression,
                     remat_override=args.remat, attention=args.attention,
                     segments=args.segments, moe=args.moe, tag=args.tag)
        if str(r.get("status", "")).startswith("FAIL"):
            failures += 1
    print(f"[dryrun] done: {len(cells)} cells, {failures} failures")
    return 1 if failures else 0


if __name__ == "__main__":
    sys.exit(main())
