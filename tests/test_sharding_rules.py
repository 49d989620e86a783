"""Static validation of the sharding rules for every FULL config on an
abstract production mesh — catches divisibility / rule errors without
compiling anything."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import AbstractMesh, PartitionSpec as P

from repro import configs
from repro.launch import specs as specs_mod
from repro.models import LM
from repro.parallel import sharding as shd

# (axis name, size) layouts of the production meshes; the AbstractMesh
# objects are built inside each test, never at import
MESHES = {"pod1": (("data", 16), ("model", 16)),
          "pod2": (("pod", 2), ("data", 16), ("model", 16))}


def abstract_mesh(name):
    names, sizes = zip(*MESHES[name])
    return AbstractMesh(sizes, names)


def axis_size(mesh, axes):
    if axes is None:
        return 1
    if isinstance(axes, str):
        axes = (axes,)
    n = 1
    for a in axes:
        n *= dict(zip(mesh.axis_names, mesh.axis_sizes))[a]
    return n


def check_divisible(shapes, specs, mesh, where):
    def chk(path, leaf, spec):
        assert len(spec) <= len(leaf.shape), (where, path, leaf.shape, spec)
        for dim, ax in zip(leaf.shape, tuple(spec) + (None,) * 8):
            if ax is not None:
                assert dim % axis_size(mesh, ax) == 0, \
                    (where, jax.tree_util.keystr(path), leaf.shape, spec)
    jax.tree_util.tree_map_with_path(chk, shapes, specs,
                                     is_leaf_with_path=None)


@pytest.mark.parametrize("mesh", ["pod1", "pod2"])
@pytest.mark.parametrize("arch", configs.ARCH_NAMES)
def test_param_specs_divisible(arch, mesh):
    mesh = abstract_mesh(mesh)
    cfg = configs.get(arch)
    lm = LM(cfg)
    shapes = jax.eval_shape(lm.init, jax.random.PRNGKey(0))
    specs = shd.param_specs(cfg, shapes, mesh)
    flat_shapes = jax.tree.leaves(shapes)
    flat_specs = jax.tree.leaves(specs, is_leaf=lambda x: isinstance(x, P))
    assert len(flat_shapes) == len(flat_specs)
    for leaf, spec in zip(flat_shapes, flat_specs):
        for dim, ax in zip(leaf.shape, tuple(spec)):
            if ax is not None:
                assert dim % axis_size(mesh, ax) == 0, \
                    (arch, leaf.shape, spec)


@pytest.mark.parametrize("mesh", ["pod1", "pod2"])
@pytest.mark.parametrize("arch", configs.ARCH_NAMES)
def test_cell_specs_divisible(arch, mesh):
    mesh = abstract_mesh(mesh)
    cfg = configs.get(arch)
    for shape_name in cfg.shapes:
        args, in_specs = specs_mod.cell_specs(cfg, shape_name, mesh)
        flat_args = jax.tree.leaves(args)
        flat_specs = jax.tree.leaves(in_specs,
                                     is_leaf=lambda x: isinstance(x, P))
        assert len(flat_args) == len(flat_specs)
        for leaf, spec in zip(flat_args, flat_specs):
            for dim, ax in zip(leaf.shape, tuple(spec)):
                if ax is not None:
                    assert dim % axis_size(mesh, ax) == 0, \
                        (arch, shape_name, leaf.shape, spec)


def test_tp_weights_actually_sharded():
    """Big weights must not silently fall back to replication."""
    cfg = configs.get("yi_34b")
    lm = LM(cfg)
    shapes = jax.eval_shape(lm.init, jax.random.PRNGKey(0))
    specs = shd.param_specs(cfg, shapes, abstract_mesh("pod1"))
    flat = jax.tree_util.tree_leaves_with_path(shapes)
    specs_flat = jax.tree.leaves(specs, is_leaf=lambda x: isinstance(x, P))
    n_replicated_big = 0
    for (path, leaf), spec in zip(flat, specs_flat):
        if np.prod(leaf.shape) * 2 > 64e6:       # > 64 MB in bf16
            if all(ax is None for ax in tuple(spec)):
                n_replicated_big += 1
    assert n_replicated_big == 0
