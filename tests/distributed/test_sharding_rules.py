"""Sharding-rule unit tests (no multi-device needed: specs are pure functions)."""

import jax
import numpy as np
import pytest
from jax.sharding import Mesh, PartitionSpec as P

from repro.configs import get_config, get_smoke_config
from repro.distributed import sharding
from repro.models import api


def fake_mesh(shape=(16, 16), axes=("data", "model")):
    # Spec computation needs no real devices: AbstractMesh takes the axis
    # sizes and names and exposes axis_names/axis_sizes/shape.
    from jax.sharding import AbstractMesh

    return AbstractMesh(tuple(shape), tuple(axes))


def test_param_specs_qwen_rules():
    mesh = fake_mesh()
    cfg = get_config("qwen2-72b")
    params = jax.eval_shape(lambda: api.init(cfg, jax.random.PRNGKey(0)))
    specs = sharding.param_specs(params, mesh)
    flat, _ = jax.tree_util.tree_flatten_with_path(specs)
    by_name = {}
    for path, spec in flat:
        name = [getattr(e, "key", None) for e in path if getattr(e, "key", None)][-1]
        by_name.setdefault(name, spec)
    # embed (V, D): vocab over model, d over data
    assert by_name["embed"] == P("model", "data")
    # wq stacked (L, D, H*hd)
    assert by_name["wq"] == P(None, "data", "model")
    assert by_name["wo"] == P(None, "model", "data")
    assert all(s is None for s in by_name["scale"])  # norms replicated


def test_param_specs_divisibility_fallback():
    """rg-2b: 10 heads not divisible by 16 -> head dim replicated, not crashed."""
    mesh = fake_mesh()
    cfg = get_config("recurrentgemma-2b")
    params = jax.eval_shape(lambda: api.init(cfg, jax.random.PRNGKey(0)))
    specs = sharding.param_specs(params, mesh)
    for path, spec in jax.tree_util.tree_flatten_with_path(specs)[0]:
        leaf = None  # just ensure all specs are valid PartitionSpecs
        assert isinstance(spec, P)
    flat_p, _ = jax.tree_util.tree_flatten_with_path(params)
    flat_s, _ = jax.tree_util.tree_flatten_with_path(specs)
    sizes = dict(zip(mesh.axis_names, mesh.axis_sizes))
    for (pp, leaf), (sp, spec) in zip(flat_p, flat_s):
        for dim, ax in enumerate(spec):
            if ax is None:
                continue
            axes = ax if isinstance(ax, tuple) else (ax,)
            div = int(np.prod([sizes[a] for a in axes]))
            assert leaf.shape[dim] % div == 0, (pp, leaf.shape, spec)


@pytest.mark.parametrize("arch", ["deepseek-v3-671b", "llama4-scout-17b-a16e"])
def test_expert_leaves_ep_sharded(arch):
    mesh = fake_mesh()
    cfg = get_config(arch)
    params = jax.eval_shape(lambda: api.init(cfg, jax.random.PRNGKey(0)))
    specs = sharding.param_specs(params, mesh)
    flat, _ = jax.tree_util.tree_flatten_with_path(specs)
    found = 0
    for path, spec in flat:
        keys = [getattr(e, "key", None) for e in path]
        if "moe" in keys and "shared" not in keys and keys[-1] in ("wi", "wg", "wo"):
            # (L, E, D, F) stacked or (E, D, F): expert dim sharded over model
            edim = len(spec) - 3
            assert spec[edim] == "model", (keys, spec)
            found += 1
    assert found >= 3


def test_all_archs_specs_valid():
    mesh = fake_mesh()
    mesh3 = fake_mesh((2, 16, 16), ("pod", "data", "model"))
    for arch in ("qwen2-72b", "starcoder2-15b", "minitron-4b", "phi3-mini-3.8b",
                 "internvl2-26b", "recurrentgemma-2b", "xlstm-350m",
                 "llama4-scout-17b-a16e", "deepseek-v3-671b", "seamless-m4t-large-v2"):
        cfg = get_config(arch)
        params = jax.eval_shape(lambda c=cfg: api.init(c, jax.random.PRNGKey(0)))
        for m in (mesh, mesh3):
            specs = sharding.param_specs(params, m)
            sizes = dict(zip(m.axis_names, m.axis_sizes))
            for (pp, leaf), (sp, spec) in zip(
                jax.tree_util.tree_flatten_with_path(params)[0],
                jax.tree_util.tree_flatten_with_path(specs)[0],
            ):
                for dim, ax in enumerate(spec):
                    if ax is None:
                        continue
                    axes = ax if isinstance(ax, tuple) else (ax,)
                    div = int(np.prod([sizes[a] for a in axes]))
                    assert leaf.shape[dim] % div == 0, (arch, pp, leaf.shape, spec)
