"""Ring attention / sequence parallelism on the 8-device virtual CPU mesh:
golden parity with dense causal attention and with dense prefill
(SURVEY.md §4.3 — multi-chip semantics without a cluster)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from mcpx.models.gemma.config import GemmaConfig
from mcpx.models.gemma.model import _attend, init_params, prefill, init_kv_cache
from mcpx.parallel.mesh import make_mesh
from mcpx.parallel.ring_attention import ring_attention, ring_prefill


def dense_reference(q, k, v, seq_lens):
    """model._attend with the causal + right-padding mask ring builds."""
    B, T = q.shape[0], q.shape[1]
    pos = jnp.arange(T)
    mask = (pos[None, None, :] <= pos[None, :, None]) & (
        pos[None, None, :] < seq_lens[:, None, None]
    )
    mask = jnp.broadcast_to(mask, (B, T, T))
    return _attend(q, k, v, mask)


@pytest.mark.parametrize(
    "mesh_kw,B,T,K,G",
    [
        ({"seq": 8}, 2, 64, 2, 2),  # pure SP
        ({"seq": 4, "model": 2}, 2, 32, 2, 1),  # SP x TP(heads), MQA-ish
        ({"data": 2, "seq": 4}, 4, 32, 1, 3),  # DP x SP, GQA
    ],
)
def test_ring_matches_dense(mesh_kw, B, T, K, G):
    mesh = make_mesh(**mesh_kw)
    hd = 8
    key = jax.random.PRNGKey(0)
    kq, kk, kv_, kl = jax.random.split(key, 4)
    q = jax.random.normal(kq, (B, T, K, G, hd), jnp.float32)
    k = jax.random.normal(kk, (B, T, K, hd), jnp.float32)
    v = jax.random.normal(kv_, (B, T, K, hd), jnp.float32)
    # Ragged valid lengths, including one full and one very short row.
    seq_lens = jnp.asarray(
        np.concatenate([[T, 3], jax.random.randint(kl, (max(B - 2, 0),), 1, T + 1)])[:B],
        jnp.int32,
    )

    ref = dense_reference(q, k, v, seq_lens)
    with jax.set_mesh(mesh):
        out = jax.jit(lambda *a: ring_attention(*a, mesh))(q, k, v, seq_lens)

    # Compare only valid query positions (padded queries are don't-care).
    valid = np.arange(T)[None, :] < np.asarray(seq_lens)[:, None]
    np.testing.assert_allclose(
        np.asarray(out)[valid], np.asarray(ref)[valid], rtol=2e-5, atol=2e-5
    )


def test_ring_prefill_matches_dense_prefill():
    cfg = GemmaConfig.named("test")
    mesh = make_mesh(seq=8)
    B, T = 2, 64
    params = init_params(cfg, jax.random.PRNGKey(0))
    tokens = jax.random.randint(jax.random.PRNGKey(1), (B, T), 0, 255)
    seq_lens = jnp.asarray([T, 37], jnp.int32)

    ref_logits, ref_cache = jax.jit(prefill, static_argnums=1)(
        params, cfg, tokens, seq_lens, init_kv_cache(cfg, B, T)
    )
    with jax.set_mesh(mesh):
        logits, cache = jax.jit(
            lambda p, t, sl: ring_prefill(p, cfg, t, sl, mesh)
        )(params, tokens, seq_lens)

    valid = np.arange(T)[None, :] < np.asarray(seq_lens)[:, None]
    # bf16 params: reduction-order differences between the masked-dense and
    # online-softmax paths leave ~bf16-eps absolute noise on the logits.
    np.testing.assert_allclose(
        np.asarray(logits)[valid], np.asarray(ref_logits)[valid], rtol=2e-2, atol=7e-2
    )
    # KV caches must agree on valid positions too (they feed later decode).
    for name in ("k", "v"):
        got = np.asarray(cache[name], np.float32)[:, valid]
        want = np.asarray(ref_cache[name], np.float32)[:, valid]
        np.testing.assert_allclose(got, want, rtol=2e-2, atol=2e-2)


def test_ring_requires_seq_axis_and_divisibility():
    from mcpx.core.errors import ConfigError

    q = jnp.zeros((1, 8, 1, 1, 4))
    k = jnp.zeros((1, 8, 1, 4))
    sl = jnp.asarray([8], jnp.int32)
    with pytest.raises(ConfigError):
        ring_attention(q, k, k, sl, make_mesh(data=2, model=4))
    mesh = make_mesh(seq=8)
    with pytest.raises(ConfigError):
        ring_attention(q[:, :6], k[:, :6], k[:, :6], sl, mesh)
