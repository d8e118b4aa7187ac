"""Sharding-rule tests on an abstract 16x16 (and 2x16x16) mesh — no
devices needed; these are the exact rules the dry-run lowers with."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hypothesis_compat import given, st
from jax.sharding import AbstractMesh, PartitionSpec as P

from repro.configs.base import get_config
from repro.models import layers, transformer as tf
from repro.parallel import sharding


POD = AbstractMesh((16, 16), ("data", "model"))
MULTI = AbstractMesh((2, 16, 16), ("pod", "data", "model"))


def test_default_rules_axes():
    r = sharding.default_rules(POD)
    assert r["batch"] == ("data",)
    assert r["vocab"] == ("model",)
    r2 = sharding.default_rules(MULTI)
    assert r2["batch"] == ("pod", "data")


def test_spec_for_leaf_divisible():
    r = sharding.default_rules(POD)
    spec = sharding.spec_for_leaf((4096, 256), ("embed", "ffn"), POD, r)
    assert spec == P("data", "model")


def test_spec_for_leaf_fallback_replicates():
    """A dim not divisible by its mesh axes silently replicates — and the
    fallback is recorded for the roofline report."""
    r = sharding.default_rules(POD)
    fb = []
    spec = sharding.spec_for_leaf((30, 256), ("vocab", "embed"), POD, r, fb)
    assert spec == P(None, "data")
    assert fb == [("vocab", 30, ("model",))]


def test_spec_for_leaf_none_axis_unsharded():
    r = sharding.default_rules(POD)
    spec = sharding.spec_for_leaf((8, 64), ("layer", None), POD, r)
    assert spec == P(None, None)


@given(st.integers(min_value=1, max_value=4096),
       st.sampled_from(["embed", "vocab", "heads", "ffn", "expert"]))
def test_spec_for_leaf_property(dim, ax):
    """Sharded iff divisible; never errors."""
    r = sharding.default_rules(POD)
    spec = sharding.spec_for_leaf((dim,), (ax,), POD, r)
    mapped = r[ax]
    size = int(np.prod([POD.shape[a] for a in mapped]))
    if dim % size == 0:
        assert spec != P(None)
    else:
        assert spec == P(None)


@pytest.mark.parametrize("mesh", [POD, MULTI], ids=["pod", "multipod"])
@pytest.mark.parametrize("arch", ["gemma3-12b", "granite-moe-3b-a800m",
                                  "jamba-v0.1-52b", "llava-next-34b"])
def test_param_shardings_full_config(arch, mesh):
    """Every full-config parameter leaf gets a legal NamedSharding: dims
    divisible by the assigned mesh axes, structure matches params."""
    cfg = get_config(arch)
    with layers.shape_only():
        ann = tf.init_model(cfg, jax.random.PRNGKey(0))
    params, axes = layers.split_annotated(ann)
    fallbacks = []
    specs = sharding.param_shardings(params, axes, mesh,
                                     collect_fallbacks=fallbacks)
    assert jax.tree_util.tree_structure(specs) == \
        jax.tree_util.tree_structure(params)
    for leaf, sh in zip(jax.tree_util.tree_leaves(params),
                        jax.tree_util.tree_leaves(specs)):
        for dim, entry in zip(leaf.shape, sh.spec):
            if entry is None:
                continue
            axs = entry if isinstance(entry, tuple) else (entry,)
            size = int(np.prod([mesh.shape[a] for a in axs]))
            assert dim % size == 0, (arch, leaf.shape, sh.spec)


def test_tp_actually_shards_the_big_matrices():
    """The TP axis must hit ffn/vocab/heads of a full config (the whole
    point of the model axis) — guard against silent all-replicated."""
    cfg = get_config("gemma3-12b")
    with layers.shape_only():
        ann = tf.init_model(cfg, jax.random.PRNGKey(0))
    params, axes = layers.split_annotated(ann)
    specs = sharding.param_shardings(params, axes, POD)
    flat = {"/".join(str(getattr(p, "key", getattr(p, "idx", p)))
                     for p in path): s
            for path, s in jax.tree_util.tree_flatten_with_path(specs)[0]}
    ffn_specs = [s.spec for k, s in flat.items() if "ffn" in k and "wg" in k]
    assert any("model" in str(s) for s in ffn_specs)
    emb = [s.spec for k, s in flat.items() if "embed" in k][0]
    assert "model" in str(emb)      # vocab TP
    assert "data" in str(emb)       # FSDP on d_model


def test_data_batch_specs_divisible_and_not():
    batch = {"tokens": jax.ShapeDtypeStruct((256, 4096), jnp.int32),
             "pos": jax.ShapeDtypeStruct((1,), jnp.int32)}
    specs = sharding.data_batch_specs(POD, batch)
    assert specs["tokens"].spec == P("data", None)
    assert specs["pos"].spec == P()
    specs_m = sharding.data_batch_specs(MULTI, batch)
    assert specs_m["tokens"].spec == P(("pod", "data"), None)


def test_cache_shardings_decode_batched():
    """(periods,B,S,KV,D) attention cache: batch on data, seq on model."""
    cfg = get_config("gemma3-12b")
    B, S = 128, 32768
    caches = jax.eval_shape(lambda: tf.init_caches(cfg, B, S))
    specs = sharding.cache_shardings(cfg, caches, POD, B)
    leaves = [s for s in jax.tree_util.tree_leaves(specs)]
    seq_sharded = [s for s in leaves if "model" in str(s.spec)]
    assert seq_sharded, "KV cache seq dim must shard on model axis"
    batch_sharded = [s for s in leaves if "data" in str(s.spec)]
    assert batch_sharded, "KV cache batch dim must shard on data axis"


def test_cache_shardings_long_context_b1():
    """B=1 long_500k: the 500k-row cache spreads over (data, model)."""
    cfg = get_config("gemma3-12b")
    caches = jax.eval_shape(lambda: tf.init_caches(cfg, 1, 524_288))
    specs = sharding.cache_shardings(cfg, caches, POD, 1)
    found = False
    for leaf, s in zip(jax.tree_util.tree_leaves(caches),
                       jax.tree_util.tree_leaves(specs)):
        if leaf.ndim == 5 and leaf.shape[2] >= 16:   # global attn layers
            assert ("data" in str(s.spec) and "model" in str(s.spec)), \
                (leaf.shape, s.spec)
            found = True
    assert found


def test_mesh_factory_shapes():
    """make_production_mesh is a function returning the assigned meshes
    (validated structurally here; device-backed in the dry-run)."""
    import inspect
    from repro.launch import mesh as mesh_mod
    src = inspect.getsource(mesh_mod.make_production_mesh)
    assert "(2, 16, 16)" in src and "(16, 16)" in src
    assert '"pod", "data", "model"' in src.replace("'", '"')


def test_parallel_shard_noop_without_mesh():
    from repro.parallel import ops as pops
    x = jnp.ones((4, 4))
    y = pops.shard(x, "batch", None)
    assert y.shape == x.shape
