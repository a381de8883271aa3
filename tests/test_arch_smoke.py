"""Per-architecture smoke tests: instantiate the REDUCED same-family config,
run one forward/train step and a prefill→decode step on CPU; assert output
shapes and no NaNs. The FULL configs are compiled, not run, by
test_tpu_compile.py."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import configs
from repro.launch.mesh import make_mesh
from repro.models import model as M
from repro.parallel import sharding as shd
from repro.parallel import steps as steps_mod

B, S = 2, 32


def make_batch(cfg, rng, batch=B, seq=S):
    F = cfg.frontend_tokens
    text = seq - F if cfg.family == "vlm" else seq
    b = {"tokens": jax.random.randint(rng, (batch, text), 0, cfg.vocab_size)}
    if cfg.family == "vlm":
        b["vision_embeds"] = jax.random.normal(
            rng, (batch, F, cfg.d_model), jnp.float32)
    if cfg.family == "audio":
        b["audio_embeds"] = jax.random.normal(
            rng, (batch, F, cfg.d_model), jnp.float32)
    return b


@pytest.fixture(scope="module")
def mesh():
    return make_mesh(jax.devices()[:1], (1, 1))


@pytest.mark.parametrize("arch", configs.ARCHS)
def test_full_config_matches_assignment(arch):
    cfg = configs.get(arch)
    assert cfg.name == arch
    # spot-check the published numbers are wired through
    published = {
        "mamba2-130m": (24, 768, 50280), "granite-8b": (36, 4096, 49152),
        "qwen2.5-14b": (48, 5120, 152064),
        "mistral-nemo-12b": (40, 5120, 131072),
        "llama3-405b": (126, 16384, 128256),
        "recurrentgemma-2b": (26, 2560, 256000),
        "internvl2-26b": (48, 6144, 92553),
        "mixtral-8x22b": (56, 6144, 32768),
        "moonshot-v1-16b-a3b": (27, 2048, 163840),
        "seamless-m4t-large-v2": (24, 1024, 256206),
    }[arch]
    assert (cfg.num_layers, cfg.d_model, cfg.vocab_size) == published


@pytest.mark.parametrize("arch", configs.ARCHS)
def test_smoke_forward_and_loss(arch):
    cfg = configs.get_smoke(arch)
    rng = jax.random.PRNGKey(0)
    params = M.init_params(cfg, rng)
    batch = make_batch(cfg, rng)
    logits, aux = M.forward(params, cfg, batch)
    assert logits.shape == (B, S, cfg.vocab_size)
    assert not np.any(np.isnan(np.asarray(logits)))
    loss = M.loss_fn(params, cfg, batch)
    assert np.isfinite(float(loss))


@pytest.mark.parametrize("arch", configs.ARCHS)
def test_smoke_train_step(arch, mesh):
    cfg = configs.get_smoke(arch)
    rules = shd.make_rules(multi_pod=False)
    step = steps_mod.make_train_step(cfg, mesh, rules)
    rng = jax.random.PRNGKey(1)
    state = steps_mod.init_train_state(cfg, rng)
    batch = make_batch(cfg, rng)
    with mesh:
        new_state, metrics = step(state, batch)
    assert np.isfinite(float(metrics["loss"]))
    assert np.isfinite(float(metrics["grad_norm"]))
    assert int(new_state["step"]) == 1


@pytest.mark.parametrize("arch", configs.ARCHS)
def test_smoke_prefill_then_decode(arch, mesh):
    cfg = configs.get_smoke(arch)
    if cfg.is_encdec and cfg.frontend_tokens == 0:
        pytest.skip("enc-dec needs frontend tokens")
    rng = jax.random.PRNGKey(2)
    params = M.init_params(cfg, rng)
    batch = make_batch(cfg, rng)
    max_len = S + 4
    logits, cache = M.prefill(params, cfg, batch, max_len)
    assert logits.shape == (B, cfg.vocab_size)
    assert not np.any(np.isnan(np.asarray(logits)))
    tok = jnp.argmax(logits, -1)[:, None].astype(jnp.int32)
    pos = jnp.full((B,), S, jnp.int32)
    logits2, cache2 = M.decode_step(params, cfg, cache, tok, pos)
    assert logits2.shape == (B, cfg.vocab_size)
    assert not np.any(np.isnan(np.asarray(logits2)))


@pytest.mark.parametrize("arch", configs.ARCHS + ["tiny"])
def test_stack_layout_follows_layer_kinds(arch):
    """The params and the cache hold a stacked ``layers`` group (and a
    stacked ``dense_layers`` group of the leading dense layers) exactly
    when the layer kinds after those are all one kind; otherwise one
    ``layer_i`` dict per layer. Only the hybrid pattern unrolls."""
    from repro.models import transformer as tfm
    from repro.models.layers import ParamSpec

    cfg = configs.get_smoke(arch)
    lead = cfg.first_dense_layers
    uniform = len(set(tfm.layer_kinds(cfg)[lead:])) == 1
    assert uniform == (cfg.family != "hybrid")
    params = M.param_shapes(cfg)
    cache = M.cache_shapes(cfg, B, S)
    groups = {"layers": cfg.num_layers - lead, "dense_layers": lead}

    def leading(tree, is_leaf, dim):
        return {dim(x) for x in jax.tree_util.tree_leaves(tree, is_leaf)}

    def spec_dims(tree):
        return leading(tree, lambda x: isinstance(x, ParamSpec),
                       lambda sp: (sp.axes[0], sp.shape[0]))

    def cache_dims(tree):           # cache leaves are (shape, dtype)
        return leading(tree, lambda x: isinstance(x, tuple)
                       and isinstance(x[0], tuple), lambda sd: sd[0][0])

    for tree in (params, cache):
        if uniform:
            assert {g for g in groups if g in tree} == \
                {g for g, n in groups.items() if n}
        else:
            assert "dense_layers" not in tree
            assert set(tree["layers"]) == {
                f"layer_{i}" for i in range(cfg.num_layers)}
    if uniform:
        for g, n in groups.items():
            if n:
                assert spec_dims(params[g]) == {("layers", n)}
                assert cache_dims(cache[g]) == {n}


@pytest.mark.parametrize("arch", [
    "granite-8b", "mamba2-130m", "recurrentgemma-2b", "mixtral-8x22b",
    "moonshot-v1-16b-a3b"])
def test_prefill_decode_consistency(arch):
    """greedy decode over [prefill(x[:n]), step(x[n])] ≈ prefill(x[:n+1]) —
    the cache is a faithful summary of the prefix."""
    # float32 so the check is structural, not a bf16-noise measurement
    cfg = configs.get_smoke(arch).replace(dtype="float32")
    rng = jax.random.PRNGKey(3)
    params = M.init_params(cfg, rng)
    batch = make_batch(cfg, rng, seq=S)
    full_logits, _ = M.prefill(params, cfg, batch, S)
    head = {k: v[:, :S - 1] if k == "tokens" else v for k, v in batch.items()}
    _, cache = M.prefill(params, cfg, head, S)
    tok = batch["tokens"][:, S - 1:S]
    pos = jnp.full((B,), S - 1, jnp.int32)
    step_logits, _ = M.decode_step(params, cfg, cache, tok, pos)
    np.testing.assert_allclose(np.asarray(step_logits),
                               np.asarray(full_logits),
                               rtol=2e-3, atol=2e-3)


def test_moe_active_params_smaller_than_total():
    cfg = configs.get("mixtral-8x22b")
    assert cfg.active_param_count() < cfg.param_count()
    dense = configs.get("granite-8b")
    assert dense.active_param_count() == dense.param_count()


def test_llama3_405b_param_count():
    n = configs.get("llama3-405b").param_count()
    assert 3.9e11 < n < 4.2e11, n  # ~405B


def test_mixtral_param_count():
    n = configs.get("mixtral-8x22b").param_count()
    assert 1.2e11 < n < 1.5e11, n  # ~141B total


def test_moe_sparse_decode_matches_dense():
    """The serving path's grouped dropless dispatch equals the training
    path's dense capacity dispatch wherever the latter drops nothing (one
    token a row: every expert's capacity is at least 1)."""
    from repro.models import moe as moe_mod
    from repro.models.layers import init_tree
    cfg = configs.get_smoke("mixtral-8x22b").replace(dtype="float32")
    p = init_tree(moe_mod.moe_specs(cfg), jax.random.PRNGKey(7), jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(8), (5, 1, cfg.d_model))
    served = moe_mod.moe_serve(p, x, cfg)
    dense, _ = moe_mod.moe_apply(p, x, cfg)
    np.testing.assert_allclose(np.asarray(served), np.asarray(dense),
                               rtol=1e-5, atol=1e-5)
