import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

The two lines above run before ANY other import (jax locks the device
count on first init). 512 host-platform placeholder devices let
jax.make_mesh build the production meshes; ``.lower().compile()`` proves
the sharding config is coherent; ``memory_analysis``/``cost_analysis``
feed EXPERIMENTS.md §Dry-run and §Roofline.

Usage:
  python -m repro.launch.dryrun --arch qwen2-1.5b --shape train_4k \
      --mesh pod --out benchmarks/artifacts/dryrun/
  python -m repro.launch.dryrun --all   # every cell, sequential
"""
import argparse
import dataclasses
import json
import re
import sys
import time
import traceback

import jax
import jax.numpy as jnp

from repro.configs import SHAPES, get_config, list_configs, shape_applicable
from repro.distributed.sharding import make_ctx, spec_tree, sharding_tree
from repro.launch.mesh import make_production_mesh
from repro.models import frontends
from repro.models import transformer as tfm
from repro.models.common import P, abstract_params
from repro.runtime.serve import make_decode_step, make_prefill_step
from repro.train.optimizer import adafactor, adamw, cosine_schedule
from repro.train.trainer import make_batch_spec, make_train_step

COLLECTIVE_RE = re.compile(
    r"(all-gather|all-reduce|reduce-scatter|all-to-all|collective-permute)")


def pick_optimizer(cfg):
    """Memory policy (DESIGN.md §5): Adafactor + FSDP for the 1T MoE;
    AdamW (+FSDP over `data` for >=10B) otherwise."""
    n = cfg.param_counts()["total"]
    if n > 100e9:
        return adafactor(cosine_schedule(1e-4, 100, 10000)), True
    return adamw(cosine_schedule(3e-4, 100, 10000)), n > 10e9


def input_specs(cfg, shape, ctx):
    """ShapeDtypeStruct stand-ins for every model input of this cell
    (weak-type-correct, shardable, no device allocation)."""
    B, S = shape.global_batch, shape.seq_len
    if shape.kind == "train":
        specs, shardings = make_batch_spec(cfg, ctx, B, S)
        return specs, shardings
    if shape.kind == "prefill":
        if frontends.uses_embeds(cfg):
            specs = dict(embeds=jax.ShapeDtypeStruct(
                (B, S, cfg.d_model), jnp.dtype(cfg.dtype)))
            shardings = dict(embeds=ctx.sharding(("batch", "seq", "act_embed")))
        else:
            specs = dict(tokens=jax.ShapeDtypeStruct((B, S), jnp.int32))
            shardings = dict(tokens=ctx.sharding(("batch", "seq")))
        return specs, shardings
    # decode: one new token against a seq_len KV cache
    if frontends.uses_embeds(cfg):
        tok = jax.ShapeDtypeStruct((B, 1, cfg.d_model), jnp.dtype(cfg.dtype))
        tok_sh = ctx.sharding(("batch", "seq", "act_embed"))
    else:
        tok = jax.ShapeDtypeStruct((B, 1), jnp.int32)
        tok_sh = ctx.sharding(("batch", "seq"))
    return dict(token=tok), dict(token=tok_sh)


_DT_BYTES = {"f32": 4, "bf16": 2, "f16": 2, "s32": 4, "u32": 4, "pred": 1,
             "s8": 1, "u8": 1, "f64": 8, "s64": 8, "u64": 8, "f8e4m3": 1,
             "f8e5m2": 1, "s16": 2, "u16": 2}

_SHAPE_RE = re.compile(r"([a-z0-9]+)\[([0-9,]*)\]")
_OP_RE = re.compile(
    r"=\s*(\(?[^=]*?)\s+"
    r"(all-gather|all-reduce|reduce-scatter|all-to-all|collective-permute)"
    r"(?:-start|-done)?\(")


def _collective_bytes(hlo_text: str) -> dict:
    """Sum result bytes of every collective op in the post-SPMD HLO
    (per-device view — the bytes each chip moves). Tuple-shaped results
    (grouped collectives) count every element."""
    out = {}
    for m in _OP_RE.finditer(hlo_text):
        shapes, kind = m.group(1), m.group(2)
        if "-done(" in m.group(0):
            continue  # avoid double counting async start/done pairs
        b = 0
        for dt, dims in _SHAPE_RE.findall(shapes):
            n = 1
            if dims:
                for d in dims.split(","):
                    n *= int(d)
            b += n * _DT_BYTES.get(dt, 4)
        out[kind] = out.get(kind, 0) + b
        out["total"] = out.get("total", 0) + b
    return out


def _lower_cell(cfg, shape, ctx, mesh):
    """Build + lower the cell's step function. Returns (lowered, kind)."""
    tmpl = tfm.model_template(cfg)
    params_abs = abstract_params(tmpl, jnp.dtype(cfg.param_dtype))
    params_sh = sharding_tree(tmpl, ctx)
    specs, input_sh = input_specs(cfg, shape, ctx)

    with mesh:
        if shape.kind == "train":
            opt, _ = pick_optimizer(cfg)
            opt_tmpl = opt.state_template(tmpl)
            opt_abs = abstract_params(opt_tmpl, jnp.float32)
            opt_abs = jax.tree.map(
                lambda t: (jax.ShapeDtypeStruct(t.shape, jnp.int32)
                           if t.shape == () else t), opt_abs)
            opt_sh = sharding_tree(opt_tmpl, ctx)
            step_fn = make_train_step(cfg, ctx, opt)
            jitted = jax.jit(step_fn,
                             in_shardings=(params_sh, opt_sh, input_sh),
                             out_shardings=(params_sh, opt_sh, None),
                             donate_argnums=(0, 1))
            return jitted.lower(params_abs, opt_abs, specs)
        if shape.kind == "prefill":
            cache_tmpl = tfm.cache_template(cfg, shape.global_batch,
                                            shape.seq_len)
            cache_abs = tfm.abstract_cache(cfg, shape.global_batch,
                                           shape.seq_len, jnp.dtype(cfg.dtype))
            cache_sh = sharding_tree(cache_tmpl, ctx)
            fn = make_prefill_step(cfg, ctx)
            jitted = jax.jit(fn, in_shardings=(params_sh, input_sh, cache_sh),
                             out_shardings=(None, cache_sh),
                             donate_argnums=(2,))
            return jitted.lower(params_abs, specs, cache_abs)
        cache_tmpl = tfm.cache_template(cfg, shape.global_batch,
                                        shape.seq_len)
        cache_abs = tfm.abstract_cache(cfg, shape.global_batch,
                                       shape.seq_len, jnp.dtype(cfg.dtype))
        cache_sh = sharding_tree(cache_tmpl, ctx)
        fn = make_decode_step(cfg, ctx)
        jitted = jax.jit(fn,
                         in_shardings=(params_sh, input_sh["token"],
                                       cache_sh, None),
                         out_shardings=(None, cache_sh),
                         donate_argnums=(2,))
        return jitted.lower(params_abs, specs["token"], cache_abs,
                            jax.ShapeDtypeStruct((), jnp.int32))


def _make_ctx_for(cfg, mesh, shape, fsdp_mode: str = "always",
                  seq_parallel: bool = False):
    dp_size = mesh.shape.get("data", 1) * mesh.shape.get("pod", 1)
    fsdp = pick_optimizer(cfg)[1]
    if fsdp_mode == "train-only" and shape.kind != "train":
        # §Perf iteration C1: serving keeps weights model-sharded — FSDP's
        # per-step weight re-gather is pure loss without optimizer state
        fsdp = False
    ctx = make_ctx(cfg, mesh, fsdp=fsdp, dp_over_pod=True,
                   seq_parallel=seq_parallel)
    if shape.global_batch < dp_size:
        rules = dict(ctx.rules)
        rules["batch"] = None        # B=1 long-decode: replicate batch
        ctx = type(ctx)(mesh=mesh, rules=rules)
    return ctx


def _rwkv_step_flops(cfg, batch_local: int, heads_local: int) -> float:
    """Per-time-step wkv flops (per device), measured from XLA itself."""
    hd = cfg.rwkv_head_dim
    B, H = batch_local, heads_local
    sh = jax.ShapeDtypeStruct

    def step(s, rt, kt, vt, lw, u):
        kv = jnp.einsum("bhk,bhv->bhkv", kt, vt)
        o = jnp.einsum("bhk,bhkv->bhv", rt, s + u[None, :, :, None] * kv)
        return jnp.exp(lw)[..., None] * s + kv, o

    args = (sh((B, H, hd, hd), jnp.float32),) + \
        tuple(sh((B, H, hd), jnp.float32) for _ in range(4)) + \
        (sh((H, hd), jnp.float32),)
    c = jax.jit(step).lower(*args).compile().cost_analysis()
    return float(c.get("flops", 0.0))


def measure_analysis(cfg, shape, mesh, fsdp_mode: str = "always",
                     seq_parallel: bool = False) -> dict:
    """Scan-aware roofline counts (§Roofline methodology):

    XLA cost_analysis counts a lax.scan body ONCE. We lower two unrolled
    reduced-depth variants (1 and 2 pattern-cycles, dense-attention
    analysis_mode) and extrapolate linearly in depth:
        total(L) = f(L1) + (f(L2)-f(L1))/cycle_len * (L - L1).
    Exact for identical scan bodies. The RWKV time scan gets an explicit
    per-step correction measured from XLA on the step function.
    """
    p = len(cfg.block_pattern)
    fk = cfg.first_k_dense
    L1, L2 = fk + p, fk + 2 * p

    def counts(L, analysis: bool):
        # analysis=True: dense attention / single-chunk CE — exact FLOPs,
        # but bytes inflated by materialized S^2 scores the real blocked
        # path never touches. analysis=False: the real code path — honest
        # bytes/collectives (its internal kv-chunk scans undercount some
        # re-reads; noted in EXPERIMENTS §Roofline methodology).
        c2 = dataclasses.replace(cfg, n_layers=L, scan_layers=False,
                                 analysis_mode=analysis)
        ctx = _make_ctx_for(c2, mesh, shape, fsdp_mode, seq_parallel)
        lowered = _lower_cell(c2, shape, ctx, mesh)
        compiled = lowered.compile()
        ca = compiled.cost_analysis()
        coll = _collective_bytes(compiled.as_text())
        return (float(ca.get("flops", 0.0)),
                float(ca.get("bytes accessed", 0.0)), coll)

    n_extra = cfg.n_layers - L1

    f1, _, _ = counts(L1, True)
    f2, _, _ = counts(L2, True)
    flops = f1 + (f2 - f1) / p * n_extra

    _, b1, c1 = counts(L1, False)
    _, b2, c2_ = counts(L2, False)
    bytes_acc = b1 + (b2 - b1) / p * n_extra
    coll = {}
    keys = set(c1) | set(c2_)
    for k in keys:
        v1, v2 = c1.get(k, 0), c2_.get(k, 0)
        coll[k] = v1 + (v2 - v1) / p * n_extra

    notes = ["flops: dense-attn variant; bytes/coll: real-path variant; "
             "depth-extrapolated from unrolled L=%d,%d" % (L1, L2)]
    if "rwkv" in cfg.block_pattern:
        dp = mesh.shape.get("data", 1) * mesh.shape.get("pod", 1)
        b_loc = max(shape.global_batch // dp, 1)
        h_loc = cfg.n_rwkv_heads
        if cfg.n_rwkv_heads % mesh.shape.get("model", 1) == 0:
            h_loc = cfg.n_rwkv_heads // mesh.shape.get("model", 1)
        steps = shape.seq_len if shape.kind != "decode" else 1
        if steps > 1:
            per = _rwkv_step_flops(cfg, b_loc, h_loc)
            # fwd counted once per layer; remat recompute + bwd for train
            mult = 4.0 if (shape.kind == "train" and cfg.remat) else \
                (3.0 if shape.kind == "train" else 1.0)
            corr = per * (steps - 1) * mult * cfg.n_layers
            flops += corr
            notes.append("rwkv wkv-scan correction +%.3e flops" % corr)
    return dict(flops=flops, bytes_accessed=bytes_acc, collectives=coll,
                notes=notes)


def parse_overrides(pairs):
    """--set key=value pairs -> typed ModelConfig overrides."""
    from repro.configs.base import ModelConfig
    types = {f.name: f.type for f in dataclasses.fields(ModelConfig)}
    out = {}
    for pair in pairs or []:
        k, v = pair.split("=", 1)
        t = str(types.get(k, "str"))
        if "bool" in t:
            out[k] = v.lower() in ("1", "true", "yes")
        elif "int" in t:
            out[k] = int(v)
        elif "float" in t:
            out[k] = float(v)
        else:
            out[k] = v
    return out


def run_cell(arch: str, shape_name: str, mesh_kind: str,
             skip_hlo_bytes: bool = False, overrides: dict = None,
             fsdp_mode: str = "always", seq_parallel: bool = False) -> dict:
    cfg = get_config(arch)
    if overrides:
        cfg = dataclasses.replace(cfg, **overrides)
    shape = SHAPES[shape_name]
    ok, why = shape_applicable(cfg, shape)
    if not ok:
        return dict(arch=arch, shape=shape_name, mesh=mesh_kind,
                    status="skipped", reason=why)

    mesh = make_production_mesh(multi_pod=(mesh_kind == "multipod"))
    n_chips = mesh.devices.size
    ctx = _make_ctx_for(cfg, mesh, shape, fsdp_mode, seq_parallel)

    t0 = time.time()
    lowered = _lower_cell(cfg, shape, ctx, mesh)
    t_lower = time.time() - t0
    with mesh:
        compiled = lowered.compile()
    t_compile = time.time() - t0 - t_lower

    mem = compiled.memory_analysis()
    cost = compiled.cost_analysis()
    # collectives only exist post-SPMD-partitioning -> compiled HLO.
    # NOTE: raw counts below see scan bodies once; the `analysis` block
    # holds the depth-extrapolated numbers §Roofline uses.
    coll = {} if skip_hlo_bytes else _collective_bytes(compiled.as_text())

    analysis = None
    if not skip_hlo_bytes:
        try:
            analysis = measure_analysis(cfg, shape, mesh, fsdp_mode,
                                        seq_parallel)
        except Exception as e:  # noqa: BLE001
            analysis = dict(error=f"{type(e).__name__}: {e}")

    flops = float(cost.get("flops", 0.0)) if cost else 0.0
    bytes_acc = float(cost.get("bytes accessed", 0.0)) if cost else 0.0
    result = dict(
        arch=arch, shape=shape_name, mesh=mesh_kind, status="ok",
        n_chips=int(n_chips),
        lower_s=round(t_lower, 1), compile_s=round(t_compile, 1),
        hlo_gflops=flops / 1e9,
        hlo_bytes_accessed=bytes_acc,
        collective_bytes=coll,
        analysis=analysis,
        memory=dict(
            argument_bytes=int(getattr(mem, "argument_size_in_bytes", 0)),
            output_bytes=int(getattr(mem, "output_size_in_bytes", 0)),
            temp_bytes=int(getattr(mem, "temp_size_in_bytes", 0)),
            peak_bytes=int(getattr(mem, "peak_memory_in_bytes", 0) or
                           getattr(mem, "temp_size_in_bytes", 0)),
        ),
        params_total=cfg.param_counts()["total"],
        params_active=cfg.param_counts()["active"],
    )
    return result


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None, choices=list(SHAPES) + [None])
    ap.add_argument("--mesh", default="pod", choices=["pod", "multipod"])
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--out", default="benchmarks/artifacts/dryrun")
    ap.add_argument("--skip-hlo-bytes", action="store_true")
    ap.add_argument("--set", action="append", default=[],
                    help="ModelConfig override key=value (repeatable)")
    ap.add_argument("--tag", default="",
                    help="artifact-name suffix for §Perf variants")
    ap.add_argument("--fsdp-mode", default="always",
                    choices=["always", "train-only"])
    ap.add_argument("--seq-parallel", action="store_true",
                    help="sequence-parallel residual stream (SP variant)")
    ap.add_argument("--refresh-analysis", action="store_true",
                    help="recompute only the `analysis` block of an "
                         "existing ok artifact (skips the full compile)")
    args = ap.parse_args(argv)
    overrides = parse_overrides(args.set)

    os.makedirs(args.out, exist_ok=True)
    cells = []
    if args.all:
        for arch in list_configs():
            for shape in SHAPES:
                for mesh in ("pod", "multipod"):
                    cells.append((arch, shape, mesh))
    else:
        cells = [(args.arch, args.shape, args.mesh)]

    failures = 0
    for arch, shape, mesh in cells:
        tag = f"{arch}__{shape}__{mesh}"
        if args.tag:
            tag += f"__{args.tag}"
        path = os.path.join(args.out, tag + ".json")
        try:
            if args.refresh_analysis and os.path.exists(path):
                res = json.load(open(path))
                if res.get("status") == "ok":
                    v = res.get("variant") or {}
                    cfg = get_config(arch)
                    ov = v.get("overrides") or overrides
                    if "variant" not in res and cfg.moe and not ov:
                        # pre-variant-era baseline artifacts were recorded
                        # with the then-default ragged dispatch
                        ov = {"moe_dispatch": "ragged"}
                    if ov:
                        import dataclasses as _dc
                        cfg = _dc.replace(cfg, **ov)
                    m = make_production_mesh(
                        multi_pod=(mesh == "multipod"))
                    res["analysis"] = measure_analysis(
                        cfg, SHAPES[shape], m,
                        v.get("fsdp_mode", args.fsdp_mode),
                        v.get("seq_parallel", False))
            else:
                res = run_cell(arch, shape, mesh, args.skip_hlo_bytes,
                               overrides=overrides, fsdp_mode=args.fsdp_mode,
                               seq_parallel=args.seq_parallel)
                res["variant"] = dict(tag=args.tag, overrides=overrides,
                                      fsdp_mode=args.fsdp_mode,
                                      seq_parallel=args.seq_parallel)
        except Exception as e:  # noqa: BLE001 — record the failure honestly
            res = dict(arch=arch, shape=shape, mesh=mesh, status="error",
                       error=f"{type(e).__name__}: {e}",
                       traceback=traceback.format_exc()[-4000:])
            failures += 1
        with open(path, "w") as f:
            json.dump(res, f, indent=1)
        status = res["status"]
        extra = ("" if status != "ok" else
                 f" gflops={res['hlo_gflops']:.1f}"
                 f" compile={res['compile_s']}s")
        print(f"[dryrun] {tag}: {status}{extra}", flush=True)
        if status == "error":
            print(res["error"], flush=True)
    return 1 if failures else 0


if __name__ == "__main__":
    sys.exit(main())
