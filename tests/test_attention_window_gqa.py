"""The attention kernel's sliding window and grouped key/value heads
(ops/attention_pallas.py), interpreted on the CPU, against an explicit
mask over (cache + unroll): forward and every gradient, float32."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from torched_impala_tpu.ops.attention_pallas import (
    _block_sizes,
    _tile_may_see,
    _visibility,
    windowed_attention,
)


def explicit(q, k, v, seg_q, seg_ctx, W, window):
    """softmax(q k / sqrt(dh)) v under the written-out mask, query head h
    on key/value head h // group."""
    B, T, H, dh = q.shape
    group = H // k.shape[2]
    k, v = jnp.repeat(k, group, axis=2), jnp.repeat(v, group, axis=2)
    mask = _visibility(seg_q, seg_ctx, T, k.shape[1], W, window)
    scores = jnp.einsum("bthd,bshd->bhts", q, k) / np.sqrt(dh)
    scores = jnp.where(mask[:, None], scores, -1e30)
    return jnp.einsum("bhts,bshd->bthd", jax.nn.softmax(scores, -1), v)


def case(seed, B=2, T=11, H=4, Hkv=2, dh=16, W=8, p_first=0.15):
    rng = np.random.default_rng(seed)
    f = lambda *s: jnp.asarray(rng.standard_normal(s), jnp.float32)  # noqa: E731
    seg0 = rng.integers(1, 9, size=(B, 1))
    seg_q = seg0 + np.cumsum(rng.random((B, T)) < p_first, axis=1)
    # the newest `held` slots belong to the running episode, older ones to
    # the episode before it or to none
    held = rng.integers(0, W + 1, size=(B, 1))
    slot = np.arange(W)[None, :]
    kv_seg = np.where(slot >= W - held, seg0, np.where(slot % 2, -1, seg0 - 1))
    seg_ctx = np.concatenate([kv_seg, seg_q], axis=1).astype(np.int32)
    return (
        f(B, T, H, dh), f(B, W + T, Hkv, dh), f(B, W + T, Hkv, dh),
        jnp.asarray(seg_q, jnp.int32), jnp.asarray(seg_ctx),
    )


CASES = {
    "window4_grouped": dict(H=4, Hkv=2, W=8, window=4),
    "window_one_sees_itself_only": dict(H=4, Hkv=2, W=8, window=1),
    "window_equals_cache": dict(H=4, Hkv=2, W=4, window=4),
    "window_wider_than_everything": dict(H=4, Hkv=2, W=8, window=64),
    "no_window_grouped": dict(H=4, Hkv=2, W=8, window=None),
    "no_window_one_kv_head": dict(H=4, Hkv=1, W=8, window=None),
    "window4_ungrouped": dict(H=2, Hkv=2, W=8, window=4),
    "several_tiles": dict(H=4, Hkv=2, W=128, window=96, T=300),
}


@pytest.mark.parametrize("name", sorted(CASES))
def test_window_and_grouped_heads_match_the_explicit_mask(name):
    spec = dict(CASES[name])
    window = spec.pop("window")
    q, k, v, seg_q, seg_ctx = case(7, **spec)
    W = spec["W"]
    wq = jnp.asarray(
        np.random.default_rng(1).standard_normal(q.shape), jnp.float32
    )

    def loss(fn, q, k, v):
        return jnp.sum(fn(q, k, v) * wq)

    ours = lambda q, k, v: windowed_attention(  # noqa: E731
        q, k, v, seg_q, seg_ctx, W, True, window
    )
    ref = lambda q, k, v: explicit(  # noqa: E731
        q, k, v, seg_q, seg_ctx, W, window
    )
    np.testing.assert_allclose(
        ours(q, k, v), ref(q, k, v), rtol=1e-5, atol=1e-5
    )
    got = jax.grad(lambda *a: loss(ours, *a), argnums=(0, 1, 2))(q, k, v)
    want = jax.grad(lambda *a: loss(ref, *a), argnums=(0, 1, 2))(q, k, v)
    for g, w, what in zip(got, want, "qkv"):
        np.testing.assert_allclose(g, w, rtol=1e-4, atol=1e-5, err_msg=what)


def test_a_query_sees_exactly_window_positions_of_a_long_episode():
    """One episode, cache full: the mask has `window` ones a row."""
    T, W, window = 11, 8, 4
    seg_q = jnp.ones((1, T), jnp.int32)
    seg_ctx = jnp.ones((1, W + T), jnp.int32)
    mask = _visibility(seg_q, seg_ctx, T, W + T, W, window)
    assert np.asarray(mask).sum(-1).tolist() == [[window] * T]
    # ... and the newest of them is the query itself
    assert all(bool(mask[0, t, W + t]) for t in range(T))
    assert not any(bool(mask[0, t, W + t - window]) for t in range(T))


def test_tiles_wholly_behind_the_window_are_skipped():
    """T=2048 behind a cache of 512 with a window of 512: of the tiles
    the kernel's grid visits, the position test lets through the band
    around the diagonal and nothing else."""
    T, W, window = 2048, 512, 512
    Tb, Tp, Sb, Sp = _block_sizes(T, W + T)
    seen = np.array([
        [bool(_tile_may_see(t0, s0, Tb, Sb, W, window))
         for s0 in range(0, Sp, Sb)]
        for t0 in range(0, Tp, Tb)
    ])
    t = np.arange(T)[:, None]
    s = np.arange(W + T)[None, :]
    needed = (s - W <= t) & (t + W - s < window)
    want = np.array([
        [needed[t0 : t0 + Tb, s0 : s0 + Sb].any() for s0 in range(0, Sp, Sb)]
        for t0 in range(0, Tp, Tb)
    ])
    assert (seen == want).all()
    assert seen.sum() <= 0.45 * seen.size  # 2 of 5 tiles a query block


def test_heads_must_divide():
    q, k, v, seg_q, seg_ctx = case(3, H=3, Hkv=2)
    with pytest.raises(ValueError, match="do not divide"):
        windowed_attention(q, k, v, seg_q, seg_ctx, 8, True)
