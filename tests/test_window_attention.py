"""Windowed flash attention (query i sees keys i − w < j ≤ i): forward and
all three gradients against plain attention, at a query group of 7 heads a
key/value head, on the CPU through the Pallas interpreter; and the tiles a
window narrower than the default tile takes."""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from jaxpr_kernels import pallas_calls
from tony_tpu.ops import flash_attention, reference_attention
from tony_tpu.ops.attention import (_band_blocks, _valid_kj, _valid_qi,
                                    _window_blocks)
from tony_tpu.ops.ring import ring_attention
from tony_tpu.ops.ulysses import ulysses_attention

G = 7       # q heads a kv head, as SmallThinker's 28 / 4


def _qkv(s, hk=2, d=16, b=1, seed=0):
    ks = jax.random.split(jax.random.key(seed), 3)
    q = jax.random.normal(ks[0], (b, s, hk * G, d), jnp.float32)
    k = jax.random.normal(ks[1], (b, s, hk, d), jnp.float32)
    v = jax.random.normal(ks[2], (b, s, hk, d), jnp.float32)
    return q, k, v


def _plain(q, k, v, window):
    return reference_attention(q, jnp.repeat(k, G, axis=2),
                               jnp.repeat(v, G, axis=2), window=window)


# (seq, window, block_q, block_k): w < s on tile edges, w not a multiple of
# the tile, w = 1 (the diagonal alone), ragged s, unlike tiles, w >= s; and,
# with no tile named, windows below the default one, whose tiles follow
# them: 128 at w = 64, 256 at w = 200 (s ragged against it), 128 at w = 128.
CASES = [(128, 32, 32, 32), (128, 40, 32, 32), (128, 1, 32, 32),
         (200, 72, 128, 32), (256, 100, 128, 64), (128, 128, 32, 32),
         (128, 500, 32, 32), (256, 64, None, None), (384, 200, None, None),
         (512, 128, None, None)]


def _blocks(bq, bk):
    return {} if bq is None else {"block_q": bq, "block_k": bk}


@pytest.mark.parametrize("s,w,bq,bk", CASES)
def test_windowed_forward_matches_plain_attention(s, w, bq, bk):
    q, k, v = _qkv(s)
    out = flash_attention(q, k, v, window=w, **_blocks(bq, bk))
    np.testing.assert_allclose(out, _plain(q, k, v, w), atol=2e-5,
                               rtol=2e-5)


@pytest.mark.parametrize("s,w,bq,bk", CASES)
def test_windowed_gradients_match_plain_attention(s, w, bq, bk):
    q, k, v = _qkv(s, hk=1, seed=1)
    cot = jax.random.normal(jax.random.key(9), q.shape, jnp.float32)

    def via(attn):
        return jax.grad(lambda q, k, v: jnp.sum(attn(q, k, v) * cot),
                        argnums=(0, 1, 2))(q, k, v)

    got = via(lambda q, k, v: flash_attention(q, k, v, window=w,
                                              **_blocks(bq, bk)))
    want = via(lambda q, k, v: _plain(q, k, v, w))
    for g, r, name in zip(got, want, ("dq", "dk", "dv")):
        np.testing.assert_allclose(g, r, atol=1e-4, rtol=1e-4, err_msg=name)


def test_window_names_its_kernels_and_a_whole_window_is_the_full_call():
    q, k, v = _qkv(128)

    def names(window):
        return set(pallas_calls(jax.make_jaxpr(jax.grad(
            lambda q: jnp.sum(flash_attention(
                q, k, v, block_q=32, block_k=32, window=window))))(q)))

    assert names(40) == {"flash_win_fwd", "flash_win_dq", "flash_win_dkv"}
    assert names(None) == names(128) == {"flash_fwd", "flash_dq",
                                         "flash_dkv"}


def test_a_windowed_call_s_tiles_follow_its_window():
    """A window under the caller's tile takes tiles of its own width, in
    whole lanes; one over it keeps the caller's. At w = 512 and s = 8,192 a
    q tile of 512 walks 2 k tiles (50 % of its pairs inside the band) where
    one of 1,024 walked 2 of 1,024 (at most 25 %); a window of 4,096 walks
    what it walked."""
    assert _window_blocks(512, 1024, 1024) == (512, 512)
    assert _window_blocks(4096, 1024, 1024) == (1024, 1024)
    assert _window_blocks(200, 1024, 1024) == (256, 256)
    assert _window_blocks(64, 32, 16) == (32, 16)
    assert _band_blocks(16, 16, lambda i: _valid_kj(i, 512, 512, 512)) == 2
    assert _band_blocks(16, 16, lambda j: _valid_qi(j, 512, 512, 512)) == 2
    # the grid the forward call is given: (b, h, q tiles, band)
    q, k, v = _qkv(512)
    text = str(jax.make_jaxpr(
        lambda q: flash_attention(q, k, v, window=100))(q))
    assert re.findall(r"grid=\(([\d, ]+)\)", text) == [f"1, {2 * G}, 4, 2"]


def test_band_is_what_the_valid_ranges_span():
    """At 1024 x 1024 tiles and s = 16,384 a q tile under w = 4096 has 5
    live k tiles of 16, and a k tile 5 live q tiles: the grid's last axis."""
    assert _band_blocks(16, 16, lambda i: _valid_kj(i, 1024, 1024, 4096)) \
        == 5
    assert _band_blocks(16, 16, lambda j: _valid_qi(j, 1024, 1024, 4096)) \
        == 5
    # Every unmasked (row, col) lies in a tile the ranges name.
    bq, bk, w, s = 32, 16, 40, 128
    for r in range(s):
        for c in range(max(0, r - w + 1), r + 1):
            first, last = _valid_kj(r // bq, bq, bk, w)
            assert first <= c // bk <= last
            first, last = _valid_qi(c // bk, bq, bk, w)
            assert first <= r // bq <= last


@pytest.mark.parametrize("attn", [ring_attention, ulysses_attention])
def test_sequence_parallel_attention_refuses_a_window(attn):
    q, k, v = _qkv(64)
    with pytest.raises(ValueError, match="no windowed mask"):
        attn(q, k, v, window=16)


def test_window_needs_causal():
    q, k, v = _qkv(64)
    with pytest.raises(ValueError, match="causal"):
        flash_attention(q, k, v, causal=False, window=16)
