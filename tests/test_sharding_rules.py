"""Static sharding-coherence tests: every parameter/cache/optimizer spec
for every arch must be divisibility-legal on the production meshes —
catches dry-run breakage without a 512-device compile."""
import os

import jax
import numpy as np
import pytest
from jax.sharding import Mesh

from repro.configs import SHAPES, get_config, list_configs
from repro.distributed.sharding import build_rules, ShardCtx, spec_tree
from repro.models import transformer as tfm
from repro.models.common import P
from repro.train.optimizer import adafactor, adamw, cosine_schedule


def _fake_mesh(shape, axes):
    """AbstractMesh-backed spec checks (no devices needed)."""
    return jax.sharding.AbstractMesh(tuple(shape), tuple(axes))


MESHES = [((16, 16), ("data", "model")),
          ((2, 16, 16), ("pod", "data", "model"))]


def _check_tree(tmpl, ctx, sizes, what, arch):
    def leafcheck(path, t):
        spec = ctx.spec(t.axes)
        for dim, ax in zip(t.shape, spec):
            if ax is None:
                continue
            axs = (ax,) if isinstance(ax, str) else tuple(ax)
            total = int(np.prod([sizes[a] for a in axs]))
            assert dim % total == 0, (
                f"{arch} {what} {jax.tree_util.keystr(path)}: dim {dim} "
                f"not divisible by {axs}={total}")
    jax.tree_util.tree_map_with_path(
        leafcheck, tmpl, is_leaf=lambda x: isinstance(x, P))


@pytest.mark.parametrize("arch", list_configs())
@pytest.mark.parametrize("mesh_shape,axes", MESHES)
@pytest.mark.parametrize("fsdp", [False, True])
def test_param_and_state_specs_divisible(arch, mesh_shape, axes, fsdp):
    cfg = get_config(arch)
    mesh = _fake_mesh(mesh_shape, axes)
    rules = build_rules(cfg, mesh, fsdp=fsdp)
    ctx = ShardCtx(mesh=mesh, rules=rules)
    sizes = dict(zip(axes, mesh_shape))

    tmpl = tfm.model_template(cfg)
    _check_tree(tmpl, ctx, sizes, "params", arch)

    for opt in (adamw(cosine_schedule(1e-3, 0, 10)),
                adafactor(cosine_schedule(1e-3, 0, 10))):
        _check_tree(opt.state_template(tmpl), ctx, sizes, "opt", arch)


@pytest.mark.parametrize("arch", list_configs())
@pytest.mark.parametrize("shape_name", ["decode_32k", "prefill_32k"])
def test_cache_specs_divisible(arch, shape_name):
    cfg = get_config(arch)
    shape = SHAPES[shape_name]
    mesh = _fake_mesh((16, 16), ("data", "model"))
    rules = build_rules(cfg, mesh)
    ctx = ShardCtx(mesh=mesh, rules=rules)
    sizes = dict(data=16, model=16)
    tmpl = tfm.cache_template(cfg, shape.global_batch, shape.seq_len)
    _check_tree(tmpl, ctx, sizes, "cache", arch)


@pytest.mark.parametrize("arch", list_configs())
def test_rules_consistent(arch):
    cfg = get_config(arch)
    mesh = _fake_mesh((16, 16), ("data", "model"))
    rules = build_rules(cfg, mesh)
    # padded vocab divisible by model
    from repro.models.common import padded_vocab
    assert padded_vocab(cfg) % 16 == 0
    # kv_seq sharded exactly when kv heads are not
    assert (rules["kv_heads"] == "model") == (rules["kv_seq"] is None)
