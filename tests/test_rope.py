"""Rotary positions: the half-split rotation over the whole head, and
GLM-4's rotation of the leading dims of each head in adjacent pairs
(`ArchConfig.partial_rotary_factor`, `rope_interleave`), in training and
in decoding through the cache."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config, smoke_config
from repro.models import decode_step, forward, init_decode_state, init_params
from repro.models.layers import apply_rope, rope_cos_sin


def _rotated(x, pos, theta, rot, interleave):
    """Each pair turned on its own, in numpy."""
    out = np.array(x, np.float64)
    for i in range(rot // 2):
        a, b = (2 * i, 2 * i + 1) if interleave else (i, i + rot // 2)
        ang = pos[:, None] * theta ** (-2.0 * i / rot)
        c, s = np.cos(ang), np.sin(ang)
        out[..., a] = x[..., a] * c - x[..., b] * s
        out[..., b] = x[..., b] * c + x[..., a] * s
    return out


@pytest.mark.parametrize("rot,interleave", [(16, False), (16, True),
                                            (8, True), (8, False)])
def test_apply_rope_turns_the_pairs_it_names_and_passes_the_rest(
        rot, interleave):
    x = np.asarray(jax.random.normal(jax.random.PRNGKey(0), (5, 3, 16)))
    pos = np.arange(5) + 7
    cos, sin = rope_cos_sin(jnp.asarray(pos), rot, 1e4)
    got = np.asarray(apply_rope(jnp.asarray(x), cos, sin, interleave))
    np.testing.assert_allclose(got, _rotated(x, pos, 1e4, rot, interleave),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_array_equal(got[..., rot:], x[..., rot:])


def test_glm4_rotates_half_of_each_head_in_adjacent_pairs():
    cfg = get_config("glm4-9b")
    assert (cfg.rotary_dim, cfg.rope_interleave, cfg.qkv_bias,
            cfg.norm_eps, cfg.tie_embeddings) == (64, True, True, 1.5625e-7,
                                                  False)
    assert get_config("qwen2-0.5b").rotary_dim == 64   # its whole head


@pytest.mark.parametrize("variant", [{}, {"rope_interleave": False},
                                     {"partial_rotary_factor": 1.0}])
def test_decoding_through_the_cache_gives_the_forward_logits(variant):
    cfg = dataclasses.replace(smoke_config(get_config("glm4-9b")),
                              dtype="float32", **variant)
    params = init_params(jax.random.PRNGKey(0), cfg)
    # Biases and weights large enough that rotary moves the scores.
    params = jax.tree.map(
        lambda x: x + 0.1 * jax.random.normal(jax.random.PRNGKey(x.size),
                                              x.shape, x.dtype), params)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 12), 0,
                                cfg.vocab_size)
    with jax.default_matmul_precision("highest"):
        full, _ = jax.jit(lambda p, t: forward(p, cfg, tokens=t, chunk=4))(
            params, tokens)
        step = jax.jit(lambda p, s, t, i: decode_step(p, s, cfg, t, i))
        state = init_decode_state(cfg, batch=2, max_len=16)
        for i in range(tokens.shape[1]):
            logits, state = step(params, state, tokens[:, i], jnp.int32(i))
            np.testing.assert_allclose(np.asarray(logits),
                                       np.asarray(full[:, i]),
                                       rtol=1e-4, atol=1e-4)
