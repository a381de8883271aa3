"""The decode step writes each row's new key and value into the batched cache
and nothing else, and its logits match a prefill of the whole sequence.

Rows sit at different depths, as under continuous batching: each row is
prefilled on its own and spliced into the batched cache, then every row
advances by one token a step. After each step the logits of row b must match
the last logits of a prefill of that row's sequence through the token just
decoded, and every cache entry outside the rows the step wrote must be
bit-for-bit what it was. The sliding-window case decodes past its ring
buffer's wrap-around; the latent-attention case (moonshot) writes one latent
row a token into each of two stacked groups, its leading dense layer's and
its MoE layers'. Float32 throughout, so the check is structural.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import configs
from repro.models import model as M

LENGTHS = (4, 29, 34)        # prompt lengths: rows at different depths
STEPS = 4                    # row 1 crosses mixtral-smoke's 32-slot window;
                             # row 2 starts past it
MAX_LEN = 48


def _config(arch):
    return configs.get_smoke(arch).replace(dtype="float32")


def _inputs(cfg, row, length, seq):
    batch = {"tokens": seq[row:row + 1, :length]}
    if cfg.family == "audio":
        batch["audio_embeds"] = jax.random.normal(
            jax.random.PRNGKey(100 + row), (1, cfg.frontend_tokens, cfg.d_model))
    return batch


def _splice(full, row_cache, b):
    """Put a batch-1 prefill cache into row ``b`` of the batched cache."""
    def one(path, f, r):
        if M.stacked(path):
            return f.at[:, b].set(r[:, 0])
        return f.at[b].set(r[0])
    return jax.tree_util.tree_map_with_path(one, full, row_cache)


WRITTEN = ("k", "v", "latent")


def _index(key, b, slot):
    return (Ellipsis, b, slot, slice(None)) if key == "latent" else \
        (Ellipsis, b, slice(None), slot, slice(None))


def _written(path, leaf, pos):
    """Mask of the entries a step at ``pos`` may change in this leaf, or None
    for a recurrent state, which the step rewrites whole."""
    key = path[-1].key
    if key in ("enc_k", "enc_v"):
        return np.zeros(leaf.shape, bool)            # cross memory: read only
    if key not in WRITTEN:
        return None
    mask = np.zeros(leaf.shape, bool)
    slots = leaf.shape[-2]
    for b, p in enumerate(pos):
        mask[_index(key, b, p % slots)] = True
    return mask


@pytest.mark.parametrize("arch", ["granite-8b", "mixtral-8x22b", "mamba2-130m",
                                  "recurrentgemma-2b",
                                  "seamless-m4t-large-v2",
                                  "moonshot-v1-16b-a3b"])
def test_decode_writes_only_new_rows(arch):
    cfg = _config(arch)
    B = len(LENGTHS)
    params = M.init_params(cfg, jax.random.PRNGKey(0))
    seq = jax.random.randint(jax.random.PRNGKey(1), (B, MAX_LEN), 0,
                             cfg.vocab_size)
    prefill = jax.jit(lambda p, batch: M.prefill(p, cfg, batch, MAX_LEN))
    decode = jax.jit(lambda p, c, t, q: M.decode_step(p, cfg, c, t, q))

    cache = M.init_cache(cfg, B, MAX_LEN)
    for b, n in enumerate(LENGTHS):
        _, row = prefill(params, _inputs(cfg, b, n, seq))
        cache = _splice(cache, row, b)

    pos = np.array(LENGTHS, np.int32)
    for _ in range(STEPS):
        tok = jnp.asarray(np.asarray(seq)[np.arange(B), pos][:, None])
        logits, new = decode(params, cache, tok, jnp.asarray(pos))
        for b in range(B):
            ref_logits, ref_cache = prefill(
                params, _inputs(cfg, b, int(pos[b]) + 1, seq))
            np.testing.assert_allclose(np.asarray(logits[b]),
                                       np.asarray(ref_logits[0]),
                                       rtol=2e-3, atol=2e-3)
            # the row written is the one the longer prefill holds there
            for path, got in jax.tree_util.tree_leaves_with_path(new):
                key = path[-1].key
                if key not in WRITTEN:
                    continue
                ref = jax.tree_util.tree_leaves_with_path(ref_cache)
                want = dict((jax.tree_util.keystr(p), x) for p, x in ref)[
                    jax.tree_util.keystr(path)]
                slot = int(pos[b]) % got.shape[-2]
                np.testing.assert_allclose(
                    np.asarray(got[_index(key, b, slot)]),
                    np.asarray(want[_index(key, 0, slot)]), rtol=1e-4,
                    atol=1e-4)
        old = jax.tree_util.tree_leaves_with_path(cache)
        for (path, before), after in zip(old, jax.tree_util.tree_leaves(new)):
            mask = _written(path, before, pos)
            if mask is None:
                continue
            np.testing.assert_array_equal(np.asarray(after)[~mask],
                                          np.asarray(before)[~mask],
                                          err_msg=jax.tree_util.keystr(path))
        cache, pos = new, pos + 1
