"""Sharding-rule unit tests: logical-axis → PartitionSpec mapping for all
four rule sets, including the divisibility fallback that motivated the
`zero` rules (§Perf Cell A)."""

import jax
import pytest
from jax.sharding import PartitionSpec as P

from repro.launch.mesh import make_mesh
from repro.models.layers import ParamSpec
from repro.parallel import sharding as shd


@pytest.fixture(scope="module")
def mesh():
    # 4 = 2×2 stand-in for (data, model); divisibility logic is identical
    devs = jax.devices() * 4  # replicate the single CPU device
    return make_mesh(devs[:4], (2, 2))


def spec(shape, axes):
    return ParamSpec(shape, axes)


def test_baseline_tp_mapping(mesh):
    r = shd.make_rules(multi_pod=False)
    assert shd.spec_to_pspec(spec((64, 8, 16), ("embed", "heads", "head")),
                             r, mesh) == P(None, "model")
    assert shd.spec_to_pspec(spec((1024, 64), ("vocab", "embed")),
                             r, mesh) == P("model")


def test_indivisible_heads_fall_back_to_replication(mesh):
    """The qwen pathology in miniature: 3 heads on a 2-way model axis."""
    r = shd.make_rules(multi_pod=False)
    ps = shd.spec_to_pspec(spec((64, 3, 16), ("embed", "heads", "head")),
                           r, mesh)
    assert ps == P()          # heads axis dropped — replicated


def test_zero_rules_shard_embed_over_everything(mesh):
    r = shd.make_rules(multi_pod=False, zero=True)
    ps = shd.spec_to_pspec(spec((64, 3, 16), ("embed", "heads", "head")),
                           r, mesh)
    assert ps == P(("data", "model"))      # embed over the whole mesh
    assert shd.batch_pspec(r) == P(("data", "model"))


def test_tp2d_rules_shard_ff_2d_no_batch(mesh):
    r = shd.make_rules(multi_pod=False, tp2d=True)
    ps = shd.spec_to_pspec(spec((8, 64, 16), ("experts", "embed", "ff")),
                           r, mesh)
    assert ps == P(None, None, ("data", "model"))
    assert shd.batch_pspec(r) == P(None)


def test_multipod_adds_pod_axis():
    r = shd.make_rules(multi_pod=True)
    assert tuple(r["batch"]) == ("pod", "data")
    rz = shd.make_rules(multi_pod=True, zero=True)
    assert tuple(rz["batch"]) == ("pod", "data", "model")


def test_mesh_axis_used_once_per_param(mesh):
    """A mesh axis may appear in at most one dim of a PartitionSpec."""
    r = shd.make_rules(multi_pod=False, zero=True)
    # embed appears twice (square weight): second occurrence must drop
    ps = shd.spec_to_pspec(spec((64, 64), ("embed", "embed")), r, mesh)
    flat = []
    for e in tuple(ps):
        if e is None:
            continue
        flat.extend(e if isinstance(e, tuple) else (e,))
    assert len(flat) == len(set(flat))


@pytest.mark.parametrize("batch,lead", [(8, "data"), (6, None)])
def test_constrain_logical_keeps_data_axis_on_batch(batch, lead):
    """Under make_mesh's Auto axes the activation constraint traces, and on
    a (4, 1) data-parallel mesh `data` lands on the batch dim wherever the
    batch divides by 4 (else the dim stays replicated)."""
    import jax.numpy as jnp
    from repro.parallel.ctx import constrain_logical, sharding_ctx

    mesh = make_mesh(jax.devices() * 4, (4, 1))
    rules = shd.make_rules(multi_pod=False)

    def f(x):
        with sharding_ctx(mesh, rules):
            return constrain_logical(x, ("batch", "seq", "act_embed"))

    traced = jax.jit(f).trace(jax.ShapeDtypeStruct((batch, 16, 32),
                                                   jnp.float32))
    [eqn] = [e for e in traced.jaxpr.eqns
             if e.primitive.name == "sharding_constraint"]
    assert eqn.params["sharding"].spec == (P(lead) if lead else P())
