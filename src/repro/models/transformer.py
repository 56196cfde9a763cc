"""Decoder-stack assembly for every architecture in the pool.

Layers are grouped into scan-able units (``layer_groups``): homogeneous
archs scan one stacked block; hybrid archs (RecurrentGemma) scan a stacked
*cycle* of blocks (rglru, rglru, local) plus explicit trailing blocks; MoE
archs with leading dense layers (Kimi K2) place them in their own group.

``forward`` covers train / prefill (S tokens, optional cache write) and
decode (S==1 against a cache). Caches and recurrent states are pytrees
mirroring the group structure so the whole bundle shards/scans uniformly.
"""
from __future__ import annotations

from typing import List, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as PS

from repro.models import attention as attn
from repro.models import mlp as mlpm
from repro.models import moe as moem
from repro.models import rglru as rglrum
from repro.models import rwkv6 as rwkvm
from repro.models.common import (
    P, apply_norm, init_params, norm_template, padded_vocab, stack_templates,
)

INT32_MAX = jnp.iinfo(jnp.int32).max


# ---------------------------------------------------------------------------
# structure
# ---------------------------------------------------------------------------


def layer_groups(cfg) -> List[Tuple[Tuple[str, ...], int]]:
    """[(kinds_in_cycle, repeats), ...] covering all n_layers in order."""
    kinds = list(cfg.layer_kinds())
    groups: List[Tuple[Tuple[str, ...], int]] = []
    i = 0
    if cfg.moe and cfg.first_k_dense:
        groups.append((("attn_dense",), cfg.first_k_dense))
        i = cfg.first_k_dense
    rest = kinds[i:]
    if not rest:
        return groups
    p = tuple(cfg.block_pattern) if len(set(rest)) > 1 else (rest[0],)
    n_cyc = len(rest) // len(p)
    if n_cyc:
        groups.append((p, n_cyc))
    for k in rest[n_cyc * len(p):]:
        groups.append(((k,), 1))
    return groups


def block_template(cfg, kind: str) -> dict:
    t = {"ln1": norm_template(cfg), "ln2": norm_template(cfg)}
    if kind in ("attn", "local", "attn_dense"):
        t["attn"] = attn.attn_template(cfg)
        if cfg.moe and kind == "attn":
            t["mlp"] = moem.moe_template(cfg)
        else:
            t["mlp"] = mlpm.mlp_template(cfg)
    elif kind == "rglru":
        t["lru"] = rglrum.rglru_template(cfg)
        t["mlp"] = mlpm.mlp_template(cfg)
    elif kind == "rwkv":
        t["mix"] = rwkvm.rwkv_template(cfg)
    else:
        raise ValueError(kind)
    return t


def model_template(cfg) -> dict:
    D = cfg.d_model
    Vp = padded_vocab(cfg)
    t = {
        "embed": P((Vp, D), ("vocab", "embed"), "embed", 0.02),
        "final_norm": norm_template(cfg),
        "groups": {},
    }
    if not cfg.tie_embeddings:
        t["unembed"] = P((D, Vp), ("embed", "vocab"))
    for gi, (kinds, reps) in enumerate(layer_groups(cfg)):
        cyc = {f"b{i}": block_template(cfg, k) for i, k in enumerate(kinds)}
        t["groups"][f"g{gi}"] = stack_templates(cyc, reps) if reps > 1 else cyc
    return t


def block_cache_template(cfg, kind: str, batch: int, max_seq: int) -> dict:
    if kind in ("attn", "local", "attn_dense"):
        C = max_seq
        if kind == "local" or (cfg.attn_type == "swa" and cfg.window):
            C = min(max_seq, cfg.window)
        Hkv, hd = cfg.n_kv_heads, cfg.hd
        return {
            "k": P((batch, C, Hkv, hd), ("batch", "kv_seq", "kv_heads", None), "zeros"),
            "v": P((batch, C, Hkv, hd), ("batch", "kv_seq", "kv_heads", None), "zeros"),
            "pos": P((batch, C), ("batch", "kv_seq"), "ones"),  # scaled below
        }
    if kind == "rglru":
        return rglrum.rglru_state_template(cfg, batch)
    if kind == "rwkv":
        return rwkvm.rwkv_state_template(cfg, batch)
    raise ValueError(kind)


def cache_template(cfg, batch: int, max_seq: int) -> dict:
    t = {"groups": {}}
    for gi, (kinds, reps) in enumerate(layer_groups(cfg)):
        cyc = {f"b{i}": block_cache_template(cfg, k, batch, max_seq)
               for i, k in enumerate(kinds)}
        t["groups"][f"g{gi}"] = stack_templates(cyc, reps) if reps > 1 else cyc
    return t


_F32_STATE_KEYS = ("h", "s", "conv", "x_prev_tm", "x_prev_cm")


def _cache_leaf_dtype(path, dtype):
    name = path[-1].key if hasattr(path[-1], "key") else str(path[-1])
    if name == "pos":
        return jnp.int32
    if name in _F32_STATE_KEYS:
        return jnp.float32   # recurrent states stay f32
    return dtype


def init_cache(cfg, batch: int, max_seq: int, dtype=jnp.bfloat16):
    """Empty cache: kv pos slots = INT32_MAX so masks exclude them."""
    tmpl = cache_template(cfg, batch, max_seq)

    def mk(path, p):
        dt = _cache_leaf_dtype(path, dtype)
        if dt == jnp.int32:
            return jnp.full(p.shape, INT32_MAX, jnp.int32)
        return jnp.zeros(p.shape, dt)

    return jax.tree_util.tree_map_with_path(
        mk, tmpl, is_leaf=lambda x: isinstance(x, P))


def abstract_cache(cfg, batch: int, max_seq: int, dtype=jnp.bfloat16):
    """ShapeDtypeStruct cache for the dry-run."""
    tmpl = cache_template(cfg, batch, max_seq)
    return jax.tree_util.tree_map_with_path(
        lambda path, p: jax.ShapeDtypeStruct(
            p.shape, _cache_leaf_dtype(path, dtype)),
        tmpl, is_leaf=lambda x: isinstance(x, P))


# ---------------------------------------------------------------------------
# block application
# ---------------------------------------------------------------------------


def _pad_group(cfg, ctx):
    """Padded-heads mode: extra query heads per kv group so the activation
    head count divides the model axis (params untouched; zero-padded at
    compute time — exact)."""
    if cfg.attn_sharding != "padded" or ctx is None:
        return 0
    m = ctx.axis_sizes.get("model", 1)
    if m <= 1 or cfg.n_heads % m == 0:
        return 0
    import math
    G = cfg.n_heads // cfg.n_kv_heads
    need = m // math.gcd(cfg.n_kv_heads, m)
    return -(-G // need) * need - G


def _attention_block(p, kind, x, cfg, ctx, positions, cache, t, mode):
    window = cfg.window if (kind == "local" or cfg.attn_type == "swa") else 0
    h = apply_norm(p["ln1"], x, cfg)
    q, k, v = attn.qkv_proj(p["attn"], h, cfg, positions)
    pad_g = _pad_group(cfg, ctx)
    if pad_g:
        B, S, Hq, hd = q.shape
        Hkv = cfg.n_kv_heads
        G = Hq // Hkv
        q = jnp.pad(q.reshape(B, S, Hkv, G, hd),
                    ((0, 0), (0, 0), (0, 0), (0, pad_g), (0, 0))
                    ).reshape(B, S, Hkv * (G + pad_g), hd)
    if ctx is not None:
        # attention internals run full-seq (SP gathers before qkv): the
        # seq dim here is explicitly unsharded, heads carry the model axis
        q = ctx.constrain(q, ("batch", None, "act_heads", None))

    new_cache = cache
    if mode == "decode":
        C = cache["k"].shape[1]
        slot = (t % C).astype(jnp.int32)
        ck = jax.lax.dynamic_update_slice_in_dim(
            cache["k"], k.astype(cache["k"].dtype), slot, axis=1)
        cv = jax.lax.dynamic_update_slice_in_dim(
            cache["v"], v.astype(cache["v"].dtype), slot, axis=1)
        cpos = jax.lax.dynamic_update_slice_in_dim(
            cache["pos"], positions.astype(jnp.int32), slot, axis=1)
        o = attn.decode_attention(q, ck, cv, cpos, positions, window)
        new_cache = {"k": ck, "v": cv, "pos": cpos}
    else:
        S = x.shape[1]
        if cfg.use_pallas_kernels and not cfg.analysis_mode:
            from repro.kernels.flash_attention import flash_attention
            o = flash_attention(q, k, v, causal=True, window=window,
                                bq=min(512, S), bk=min(512, S))
        elif S <= 1024 or cfg.analysis_mode:
            o = attn.naive_attention(q, k, v, positions, positions, window)
        else:
            o = attn.blocked_attention(q, k, v, positions, positions, window)
        if cache is not None:               # prefill: persist KV
            C = cache["k"].shape[1]
            kk, vv, pp = k, v, positions
            if S >= C:
                # ring convention: slot(p) = p % C. The last C tokens land
                # at slots ((S-C)%C + i) % C — a cyclic roll.
                kk, vv, pp = k[:, -C:], v[:, -C:], positions[:, -C:]
                sh = (S - C) % C
                ck = jnp.roll(kk, sh, axis=1).astype(cache["k"].dtype)
                cv = jnp.roll(vv, sh, axis=1).astype(cache["v"].dtype)
                cpos = jnp.roll(pp, sh, axis=1).astype(jnp.int32)
            else:
                ck = jax.lax.dynamic_update_slice_in_dim(
                    cache["k"], kk.astype(cache["k"].dtype), 0, axis=1)
                cv = jax.lax.dynamic_update_slice_in_dim(
                    cache["v"], vv.astype(cache["v"].dtype), 0, axis=1)
                cpos = jax.lax.dynamic_update_slice_in_dim(
                    cache["pos"], pp.astype(jnp.int32), 0, axis=1)
            new_cache = {"k": ck, "v": cv, "pos": cpos}

    wo = p["attn"]["wo"]
    if pad_g:
        Hq, hd, D = wo.shape
        Hkv = cfg.n_kv_heads
        wo = jnp.pad(wo.reshape(Hkv, Hq // Hkv, hd, D),
                     ((0, 0), (0, pad_g), (0, 0), (0, 0))
                     ).reshape(-1, hd, D)
    x = x + jnp.einsum("bshk,hkd->bsd", o, wo)
    if ctx is not None:
        x = ctx.constrain(x, ("batch", "seq", "act_embed"))

    h2 = apply_norm(p["ln2"], x, cfg)
    aux = jnp.zeros((), jnp.float32)
    if cfg.moe and kind == "attn":
        m, aux = moem.moe_apply(p["mlp"], h2, cfg, ctx)
    else:
        m = mlpm.mlp_apply(p["mlp"], h2, cfg)
    x = x + m
    if ctx is not None:
        x = ctx.constrain(x, ("batch", "seq", "act_embed"))
    return x, new_cache, aux


def _rglru_block(p, x, cfg, ctx, cache):
    h = apply_norm(p["ln1"], x, cfg)
    o, new_state = rglrum.rglru_apply(p["lru"], h, cfg, cache)
    x = x + o
    h2 = apply_norm(p["ln2"], x, cfg)
    x = x + mlpm.mlp_apply(p["mlp"], h2, cfg)
    if ctx is not None:
        x = ctx.constrain(x, ("batch", "seq", "act_embed"))
    return x, new_state, jnp.zeros((), jnp.float32)


def _rwkv_block(p, x, cfg, ctx, cache):
    st_tm = None if cache is None else {"s": cache["s"],
                                        "x_prev": cache["x_prev_tm"]}
    st_cm = None if cache is None else {"x_prev": cache["x_prev_cm"]}
    h = apply_norm(p["ln1"], x, cfg)
    o, tm_state = rwkvm.rwkv_time_mix(p["mix"], h, cfg, st_tm)
    x = x + o
    h2 = apply_norm(p["ln2"], x, cfg)
    o2, cm_state = rwkvm.rwkv_channel_mix(p["mix"], h2, cfg, st_cm)
    x = x + o2
    if ctx is not None:
        x = ctx.constrain(x, ("batch", "seq", "act_embed"))
    new_cache = None if cache is None else {
        "s": tm_state["s"], "x_prev_tm": tm_state["x_prev"],
        "x_prev_cm": cm_state["x_prev"]}
    return x, new_cache, jnp.zeros((), jnp.float32)


def apply_block(p, kind, x, cfg, ctx, positions, cache, t, mode):
    if kind in ("attn", "local", "attn_dense"):
        return _attention_block(p, kind, x, cfg, ctx, positions, cache, t, mode)
    if kind == "rglru":
        return _rglru_block(p, x, cfg, ctx, cache)
    if kind == "rwkv":
        return _rwkv_block(p, x, cfg, ctx, cache)
    raise ValueError(kind)


# ---------------------------------------------------------------------------
# embedding / logits
# ---------------------------------------------------------------------------


def embed_lookup(params, tokens, cfg, ctx):
    table = params["embed"]
    if (ctx is not None and ctx.rules.get("vocab") == "model"
            and ctx.axis_sizes.get("model", 1) > 1):
        mesh = ctx.mesh

        def f(tbl, ids):
            vloc = tbl.shape[0]
            lo = jax.lax.axis_index("model") * vloc
            loc = jnp.clip(ids - lo, 0, vloc - 1)
            ok = ((ids - lo) >= 0) & ((ids - lo) < vloc)
            out = jnp.where(ok[..., None], tbl[loc], 0).astype(tbl.dtype)
            return jax.lax.psum(out, "model")

        # ids must be replicated over `model` (the psum combines vocab
        # shards of the SAME positions); SP resharding happens after.
        ba = ctx.rules.get("batch")
        return jax.shard_map(
            f, mesh=mesh,
            in_specs=(PS(ctx.rules.get("vocab"), None), PS(ba, None)),
            out_specs=PS(ba, None, None),
            check_vma=False)(table, tokens)
    return jnp.take(table, tokens, axis=0)


def unembed_weight(params, cfg):
    if cfg.tie_embeddings:
        return params["embed"].T
    return params["unembed"]


def logits_fn(params, hidden, cfg, ctx):
    """Full logits (B,S,Vp) — only for decode (S==1) / tests."""
    w = unembed_weight(params, cfg)
    out = jnp.einsum("bsd,dv->bsv", hidden, w)
    if ctx is not None:
        out = ctx.constrain(out, ("batch", "seq", "vocab"))
    return out


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------


def forward(params, cfg, ctx, *, tokens=None, embeds=None, positions,
            cache=None, t=None, mode: str = "train"):
    """Returns (hidden (B,S,D), new_cache, aux_loss)."""
    if embeds is not None:
        x = embeds.astype(cfg.dtype)
    else:
        x = embed_lookup(params, tokens, cfg, ctx).astype(cfg.dtype)
    if ctx is not None:
        x = ctx.constrain(x, ("batch", "seq", "act_embed"))

    aux = jnp.zeros((), jnp.float32)
    groups = layer_groups(cfg)
    new_cache_groups = {}
    for gi, (kinds, reps) in enumerate(groups):
        gp = params["groups"][f"g{gi}"]
        gc = None if cache is None else cache["groups"][f"g{gi}"]

        if reps == 1 or not cfg.scan_layers:
            def one_cycle(lp, lc, x_in, aux_in):
                new_lc = {}
                for i, kind in enumerate(kinds):
                    bc = None if lc is None else lc[f"b{i}"]
                    x_in, nc, a = apply_block(lp[f"b{i}"], kind, x_in, cfg,
                                              ctx, positions, bc, t, mode)
                    new_lc[f"b{i}"] = nc
                    aux_in = aux_in + a
                return x_in, new_lc, aux_in

            if cfg.remat and reps > 1:
                one_cycle = jax.checkpoint(one_cycle)
            new_cycles = []
            for r in range(reps):
                lp = (gp if reps == 1
                      else jax.tree.map(lambda v_: v_[r], gp))
                lc = None if gc is None else (
                    gc if reps == 1
                    else jax.tree.map(lambda v_: v_[r], gc))
                x, new_lc, aux = one_cycle(lp, lc, x, aux)
                new_cycles.append(new_lc)
            if gc is None:
                new_cache_groups[f"g{gi}"] = None
            elif reps == 1:
                new_cache_groups[f"g{gi}"] = new_cycles[0]
            else:
                new_cache_groups[f"g{gi}"] = jax.tree.map(
                    lambda *xs: jnp.stack(xs), *new_cycles)
        else:
            def body(carry, xs):
                xc, auxc = carry
                if gc is None:
                    lp, lc = xs, None
                else:
                    lp, lc = xs
                new_lc = {}
                for i, kind in enumerate(kinds):
                    bc = None if lc is None else lc[f"b{i}"]
                    xc, nc, a = apply_block(lp[f"b{i}"], kind, xc, cfg, ctx,
                                            positions, bc, t, mode)
                    new_lc[f"b{i}"] = nc
                    auxc = auxc + a
                out = new_lc if gc is not None else None
                return (xc, auxc), out

            if cfg.remat:
                body = jax.checkpoint(body)
            xs = gp if gc is None else (gp, gc)
            (x, aux), stacked_cache = jax.lax.scan(body, (x, aux), xs)
            new_cache_groups[f"g{gi}"] = stacked_cache

    x = apply_norm(params["final_norm"], x, cfg)
    new_cache = None if cache is None else {"groups": new_cache_groups}
    return x, new_cache, aux


# ---------------------------------------------------------------------------
# init
# ---------------------------------------------------------------------------


def init_model(key, cfg):
    import numpy as np  # noqa: F401
    dt = jnp.dtype(cfg.param_dtype)
    return init_params(key, model_template(cfg), dt)


def abstract_model(cfg):
    dt = jnp.dtype(cfg.param_dtype)
    return jax.tree.map(
        lambda p: jax.ShapeDtypeStruct(p.shape, dt), model_template(cfg),
        is_leaf=lambda x: isinstance(x, P))
