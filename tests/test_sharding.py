"""Sharding rule table: divisibility auto-drop, axis-reuse protection,
cache/param tree alignment (hypothesis property tests)."""
import jax
import numpy as np
from hypothesis import given, settings, strategies as st
from jax.sharding import PartitionSpec as P

from repro.launch import sharding as sh
from repro.launch.mesh import make_demo_mesh


def _amesh(sizes, names):
    # fake abstract mesh: axis *names* drive the rule logic
    return jax.sharding.AbstractMesh(sizes, names)


def _mesh_2d():
    # 1 real device, but axis *names* drive the rule logic; use a fake
    # abstract mesh for spec computation via jax.sharding.AbstractMesh
    return _amesh((16, 16), ("data", "model"))


def test_spec_basic_rules():
    mesh = _mesh_2d()
    spec = sh.spec_for((256, 4096), ("batch", None), mesh, sh.BASE_RULES)
    assert spec == P("data", None)      # no pod axis in this mesh
    spec = sh.spec_for((4096, 14336), ("embed", "mlp"), mesh,
                       sh.BASE_RULES)
    assert spec == P("data", "model")


def test_spec_divisibility_autodrop():
    mesh = _mesh_2d()
    # 40 experts don't divide 16 -> replicate
    spec = sh.spec_for((40, 64, 64), ("experts", "embed", "mlp"), mesh,
                       sh.BASE_RULES)
    assert spec[0] is None
    # batch 8 divides 16? no -> drop ("pod","data")->("pod")->none
    spec = sh.spec_for((8,), ("batch",), mesh, sh.BASE_RULES)
    assert spec == P(None)


def test_spec_axis_reuse_protection():
    mesh = _mesh_2d()
    # two dims both wanting "model": second one must drop
    spec = sh.spec_for((64, 64), ("mlp", "vocab"), mesh, sh.BASE_RULES)
    assert spec == P("model", None)


def test_multi_pod_batch_rule():
    mesh = _amesh((2, 16, 16), ("pod", "data", "model"))
    spec = sh.spec_for((256,), ("batch",), mesh, sh.BASE_RULES)
    assert spec == P(("pod", "data"))
    # batch=1 (long_500k) -> fully replicated
    spec = sh.spec_for((1,), ("batch",), mesh, sh.BASE_RULES)
    assert spec == P(None)


@given(st.integers(1, 4096), st.integers(1, 64))
@settings(max_examples=60, deadline=None)
def test_autodrop_always_divides(dim, other):
    """Whatever sharding is chosen, the dim must be divisible by the
    total shards (NamedSharding validity invariant)."""
    mesh = _amesh((2, 16, 16), ("pod", "data", "model"))
    for rules in (sh.BASE_RULES, sh.EXPERT_PARALLEL_RULES,
                  sh.LONG_CONTEXT_RULES):
        spec = sh.spec_for((dim, other), ("batch", "kv_seq"), mesh, rules)
        sizes = dict(zip(mesh.axis_names, mesh.axis_sizes))
        for d, entry in zip((dim, other), spec):
            if entry is None:
                continue
            axes = entry if isinstance(entry, tuple) else (entry,)
            total = int(np.prod([sizes[a] for a in axes]))
            assert d % total == 0


def test_param_tree_sharding_alignment():
    """Every param leaf gets a sharding and they lower on a 1-device
    mesh (structure check with real NamedSharding)."""
    import repro.configs as C
    from repro.models import param as P_
    from repro.models import transformer as T
    cfg = C.get_reduced("jamba-1.5-large-398b")
    specs = T.param_specs(cfg)
    ab = P_.abstract_params(specs)
    axes = P_.logical_axes(specs)
    mesh = make_demo_mesh()
    shardings = sh.logical_to_sharding(ab, axes, mesh)
    assert jax.tree.structure(shardings) == jax.tree.structure(ab)


def test_cache_axes_structure_matches():
    import repro.configs as C
    from repro.models import transformer as T
    for arch in ("jamba-1.5-large-398b", "whisper-base",
                 "deepseek-v3-671b"):
        cfg = C.get_reduced(arch)
        cache_ab = T.cache_abstract(cfg, 2, 32, 8)
        axes = T.cache_axes(cfg)
        mesh = make_demo_mesh()
        shardings = sh.logical_to_sharding(cache_ab, axes, mesh)
        assert jax.tree.structure(shardings) == \
            jax.tree.structure(cache_ab)
