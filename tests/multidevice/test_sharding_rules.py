"""`runtime.sharding` rule resolution against real 1/2/8-device meshes.

Every logical-axis entry of ``LOGICAL_RULES`` / ``LOGICAL_RULES_SERVE``
is resolved on meshes of 1, 2 and 8 devices (including a 3-axis
pod/data/model mesh, which only exists with 8 devices to carve up), the
divisibility fallback to replication is pinned, and the "a mesh axis is
never used twice in one spec" invariant is property-tested over random
axis/shape combinations via the hypothesis shim.
"""
import numpy as np
import pytest

import jax
from jax.sharding import Mesh, PartitionSpec as P

from _hypothesis_compat import given, settings, st
from repro.configs import SMOKES
from repro.launch.mesh import make_host_mesh
from repro.models import layers as L, lm
from repro.runtime import sharding as sh


def _mesh(*shape, names=("data", "model")):
    n = int(np.prod(shape))
    return Mesh(np.asarray(jax.devices()[:n]).reshape(shape), names)


#: built lazily inside tests -- this module is COLLECTED on single-device
#: runs too (where it only has to skip, not crash at import)
MESH_NAMES = ["1dev", "2dev-data", "2dev-model", "8dev", "8dev-pod"]


def _meshes():
    return {
        "1dev": _mesh(1, 1),
        "2dev-data": _mesh(2, 1),
        "2dev-model": _mesh(1, 2),
        "8dev": _mesh(2, 4),
        "8dev-pod": _mesh(2, 2, 2, names=("pod", "data", "model")),
    }


# --------------------------------------------------- every rule, every mesh
@pytest.mark.parametrize("mesh_name", MESH_NAMES)
@pytest.mark.parametrize("rules_name", ["LOGICAL_RULES",
                                        "LOGICAL_RULES_SERVE"])
def test_every_rule_resolves_on_every_mesh(mesh_name, rules_name):
    """A divisible dim lands on exactly the rule's mesh axes (those the
    mesh has); an indivisible (prime) dim falls back to replication."""
    mesh = _meshes()[mesh_name]
    rules = getattr(sh, rules_name)
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    def axes_of(entry):
        return (list(entry) if isinstance(entry, tuple)
                else [entry] if entry else [])

    for logical, preferred in rules.items():
        want = [a for a in preferred if a in mesh.axis_names]
        # 240 divides every axis size here (1/2/4) and every greedy
        # prefix product of them: the dim lands on exactly the rule's
        # axes that exist on this mesh
        spec = sh.spec_for((logical,), (240,), mesh, rules)
        assert axes_of(spec[0]) == want, (logical, mesh_name, spec)
        # prime dim: only size-1 axes can divide it -> effectively
        # replicated (a size-1 assignment shards nothing)
        spec_prime = sh.spec_for((logical,), (241,), mesh, rules)
        assert all(sizes[a] == 1 for a in axes_of(spec_prime[0])), \
            (logical, mesh_name, spec_prime)


def test_serve_rules_disable_fsdp_only():
    """LOGICAL_RULES_SERVE == LOGICAL_RULES except "embed" (the FSDP
    axis) resolves to nothing -- TP axes are untouched."""
    assert set(sh.LOGICAL_RULES_SERVE) == set(sh.LOGICAL_RULES)
    assert sh.LOGICAL_RULES_SERVE["embed"] == ()
    for k, v in sh.LOGICAL_RULES.items():
        if k != "embed":
            assert sh.LOGICAL_RULES_SERVE[k] == v, k
    mesh = _mesh(2, 2, 2, names=("pod", "data", "model"))
    assert sh.spec_for(("embed",), (64,), mesh) == P(("pod", "data"))
    assert sh.spec_for(("embed",), (64,), mesh,
                       sh.LOGICAL_RULES_SERVE) == P(None)


def test_greedy_prefix_respects_divisibility():
    """FSDP composes ("pod", "data") greedily: a dim divisible by pod
    but not by pod*data shards over pod alone."""
    mesh = _mesh(2, 2, 2, names=("pod", "data", "model"))
    assert sh.spec_for(("embed",), (6,), mesh) == P("pod")
    assert sh.spec_for(("embed",), (4,), mesh) == P(("pod", "data"))
    assert sh.spec_for(("embed",), (7,), mesh) == P(None)


def test_param_shardings_follow_serve_rules():
    cfg = SMOKES["qwen1.5-0.5b"]
    params = jax.eval_shape(lambda: lm.init_model(jax.random.key(0), cfg))
    mesh = _mesh(2, 4)
    train = sh.param_shardings(mesh, params)
    serve = sh.param_shardings(mesh, params, serve=True)
    # embed table [vocab, embed]: vocab -> model either way; the embed
    # (FSDP) axis shards over data only under the training rules
    assert train["embed"].value.spec == P("model", "data")
    assert serve["embed"].value.spec == P("model", None)
    for p_t, p_s in zip(jax.tree.leaves(train), jax.tree.leaves(serve)):
        spec_s = [ax for ax in p_s.spec if ax is not None]
        assert "data" not in spec_s and "pod" not in spec_s


# ------------------------------------------------------ cache layouts
def test_cache_shardings_slot_axis_and_features():
    cfg = SMOKES["qwen1.5-0.5b"].with_(compute_dtype="float32")
    states = jax.eval_shape(
        lambda: lm.make_decode_state(cfg, 4, 32, dtype=np.float32))
    mesh = _mesh(2, 2)
    shardings = sh.cache_shardings(mesh, states)
    assert (jax.tree.structure(states)
            == jax.tree.structure(shardings))
    for leaf, ns in zip(jax.tree.leaves(states["groups"]),
                        jax.tree.leaves(shardings["groups"])):
        spec = list(ns.spec) + [None] * (leaf.ndim - len(ns.spec))
        assert spec[0] is None              # scan axis
        assert spec[1] == "data"            # slot axis (4 % 2 == 0)
        if leaf.ndim >= 4:
            assert spec[2] is None          # cache sequence axis
    # attention K/V are lane-dense [G, B, T, kv * hd]: the flat feature
    # dim takes "model" (2 divides kv = 4, so each shard holds whole heads)
    for leaf, ns in zip(states["groups"]["b0"],
                        shardings["groups"]["b0"]):
        assert leaf.shape == (cfg.n_groups, 4, 32, cfg.n_kv_heads * cfg.hd)
        assert tuple(ns.spec) == (None, "data", None, "model")


def test_cache_shardings_divisibility_fallback():
    cfg = SMOKES["qwen1.5-0.5b"].with_(compute_dtype="float32")
    # 3 slots over data=2: slot axis replicates; the flat K/V feature dim
    # (kv 4 x hd 16 = 64) over model=8 splits heads but divides
    states = jax.eval_shape(
        lambda: lm.make_decode_state(cfg, 3, 32, dtype=np.float32))
    # (a size-1 "data" axis always divides and shards nothing)
    for mesh, batch_axis in ((_mesh(2, 1), None), (_mesh(1, 8), "data")):
        shardings = sh.cache_shardings(mesh, states)
        for leaf, ns in zip(jax.tree.leaves(states["groups"]),
                            jax.tree.leaves(shardings["groups"])):
            spec = list(ns.spec) + [None] * (leaf.ndim - len(ns.spec))
            assert spec[1] == batch_axis if batch_axis else \
                spec[1] is None
            for ax, dim in zip(spec, leaf.shape):
                if ax is not None:
                    axes = ax if isinstance(ax, tuple) else (ax,)
                    total = int(np.prod([dict(zip(
                        mesh.axis_names, mesh.devices.shape))[a]
                        for a in axes]))
                    assert dim % total == 0


# ----------------------------------------------- never-used-twice property
_LOGICAL = [None, *sh.LOGICAL_RULES.keys()]
_DIMS = [1, 2, 3, 4, 5, 6, 7, 8, 12, 16, 24, 64, 240, 241]


def _axis_list(spec):
    out = []
    for entry in spec:
        if entry is None:
            continue
        out.extend(entry if isinstance(entry, tuple) else (entry,))
    return out


@settings(max_examples=60, deadline=None)
@given(st.lists(st.tuples(st.sampled_from(_LOGICAL),
                          st.sampled_from(_DIMS)),
                min_size=1, max_size=5),
       st.sampled_from(MESH_NAMES),
       st.booleans())
def test_spec_never_reuses_axis_and_always_divides(dims, mesh_name,
                                                   serve):
    """For ANY combination of logical axes and sizes, on ANY mesh:
    no mesh axis appears twice in the resolved spec, and every sharded
    dim is divisible by the product of its assigned axis sizes."""
    mesh = _meshes()[mesh_name]
    rules = sh.LOGICAL_RULES_SERVE if serve else sh.LOGICAL_RULES
    axes = tuple(a for a, _ in dims)
    shape = tuple(d for _, d in dims)
    spec = sh.spec_for(axes, shape, mesh, rules)
    used = _axis_list(spec)
    assert len(used) == len(set(used)), (axes, shape, spec)
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    for entry, dim in zip(spec, shape):
        if entry is None:
            continue
        group = entry if isinstance(entry, tuple) else (entry,)
        total = int(np.prod([sizes[a] for a in group]))
        assert dim % total == 0, (axes, shape, spec)
        assert all(a in mesh.axis_names for a in group)
