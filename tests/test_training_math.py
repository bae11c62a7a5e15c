"""Numerical equivalences: chunked paths vs direct computations."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ExecutionPlan, get_config, smoke_config
from repro.models import attention as A
from repro.models import ssm
from repro.models.layers import init_moe, apply_moe

KEY = jax.random.PRNGKey(0)


def _qkv(b=2, s=64, h=4, kv=2, hd=32, dtype=jnp.float32):
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (b, s, h, hd), dtype)
    k = jax.random.normal(ks[1], (b, s, kv, hd), dtype)
    v = jax.random.normal(ks[2], (b, s, kv, hd), dtype)
    pos = jnp.broadcast_to(jnp.arange(s), (b, s))
    return q, k, v, pos


@pytest.mark.parametrize("window,cap", [(0, 0.0), (16, 0.0), (0, 30.0),
                                        (24, 50.0)])
def test_chunked_equals_dense(window, cap):
    q, k, v, pos = _qkv()
    dense = A.dense_attention(q, k, v, pos, pos, window=window, logit_cap=cap)
    chunked = A.chunked_attention(q, k, v, pos, pos, window=window,
                                  logit_cap=cap, chunk=16)
    np.testing.assert_allclose(np.asarray(chunked), np.asarray(dense),
                               rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("window", [8, 16, 24])
def test_banded_equals_dense(window):
    q, k, v, pos = _qkv(s=128)
    dense = A.dense_attention(q, k, v, pos, pos, window=window)
    banded = A.banded_attention(q, k, v, pos, pos, window=window, q_chunk=16)
    np.testing.assert_allclose(np.asarray(banded), np.asarray(dense),
                               rtol=2e-4, atol=2e-4)


def test_split_kv_merge_equals_full():
    """FlashDecoding merge over page stripes == full attention (the math
    behind the distributed paged-DBS read)."""
    b, h, kv, hd, s = 2, 4, 2, 32, 64
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (b, 1, h, hd))
    k = jax.random.normal(ks[1], (b, s, kv, hd))
    v = jax.random.normal(ks[2], (b, s, kv, hd))
    q_pos = jnp.full((b, 1), s - 1, jnp.int32)
    k_pos = jnp.broadcast_to(jnp.arange(s), (b, s))
    full = A.decode_attention(q, k, v, q_pos, k_pos)

    parts = []
    n_shards = 4
    for r in range(n_shards):
        # stripe r sees positions where (pos // 8) % n_shards == r
        mask_pos = jnp.where((k_pos // 8) % n_shards == r, k_pos,
                             jnp.iinfo(jnp.int32).max)
        parts.append(A.decode_partial(q, k, v, q_pos, mask_pos))
    o = jnp.stack([p[0] for p in parts])
    m = jnp.stack([p[1] for p in parts])
    l = jnp.stack([p[2] for p in parts])
    merged = A.merge_partials(o, m, l)
    bshape = merged.shape
    merged = merged.reshape(bshape[0], bshape[1] * bshape[2], 1, -1
                            ).swapaxes(1, 2)
    np.testing.assert_allclose(np.asarray(merged, np.float32),
                               np.asarray(full, np.float32),
                               rtol=1e-4, atol=1e-4)


def test_chunked_ce_equals_direct():
    from repro.models.layers import init_embeddings
    from repro.training.train_step import chunked_cross_entropy, _ce_block
    cfg = smoke_config("granite-3-8b")
    emb = init_embeddings(KEY, cfg)
    h = jax.random.normal(KEY, (2, 32, cfg.d_model))
    labels = jax.random.randint(KEY, (2, 32), 0, cfg.vocab_size)
    direct = _ce_block(emb, h, labels, cfg)
    chunked = chunked_cross_entropy(emb, h, labels, cfg, chunk=8)
    np.testing.assert_allclose(float(chunked), float(direct), rtol=1e-5)


def test_mamba_chunked_equals_stepwise():
    cfg = smoke_config("hymba-1.5b")
    p = ssm.init_mamba(KEY, cfg)
    x = jax.random.normal(KEY, (2, 32, cfg.d_model))
    y_chunk, st_chunk = ssm.mamba_forward(p, x, chunk=8)
    # step-by-step
    st = ssm.mamba_init_state(p, 2, x.dtype)
    ys = []
    for t in range(32):
        y, st = ssm.mamba_step(p, x[:, t:t + 1], st)
        ys.append(y)
    y_step = jnp.concatenate(ys, axis=1)
    np.testing.assert_allclose(np.asarray(y_chunk), np.asarray(y_step),
                               rtol=5e-4, atol=5e-4)
    np.testing.assert_allclose(np.asarray(st_chunk[1]), np.asarray(st[1]),
                               rtol=5e-4, atol=5e-4)


def test_rwkv_chunked_equals_stepwise():
    cfg = smoke_config("rwkv6-3b")
    p = ssm.init_rwkv6(KEY, cfg)
    x = jax.random.normal(KEY, (2, 32, cfg.d_model))
    st0 = ssm.rwkv6_init_state(cfg, 2, x.dtype)
    y_chunk, stc = ssm.rwkv6_time_mix(p, x, st0, cfg, chunk=8)
    st = dict(st0)
    ys = []
    for t in range(32):
        y, upd = ssm.rwkv6_time_mix(p, x[:, t:t + 1], st, cfg, chunk=1)
        st = {**st, **upd}
        ys.append(y)
    y_step = jnp.concatenate(ys, axis=1)
    np.testing.assert_allclose(np.asarray(y_chunk), np.asarray(y_step),
                               rtol=1e-3, atol=1e-3)


def test_moe_dropless_routes_every_token():
    cfg = smoke_config("granite-moe-3b-a800m")
    p = init_moe(KEY, cfg)
    x = jax.random.normal(KEY, (1, 16, cfg.d_model))
    out, aux = apply_moe(p, x, cfg)
    assert out.shape == x.shape
    assert np.isfinite(np.asarray(out)).all()
    assert float(aux) > 0.0
    # grads flow through ragged_dot
    g = jax.grad(lambda xx: apply_moe(p, xx, cfg)[0].sum())(x)
    assert np.isfinite(np.asarray(g)).all()
    assert float(jnp.abs(g).sum()) > 0


def test_optimizers_descend_quadratic():
    from repro.training.optimizer import make_optimizer
    target = jnp.asarray([1.5, -2.0, 0.5])

    for name in ("adamw", "adafactor"):
        init, update = make_optimizer(name, lr=0.1, warmup=1,
                                      total_steps=200, weight_decay=0.0)
        params = {"w": jnp.zeros((3,)), "m": jnp.zeros((4, 4))}
        state = init(params)
        for _ in range(120):
            grads = {"w": params["w"] - target,
                     "m": params["m"] - jnp.eye(4)}
            params, state, gnorm = update(grads, state, params)
        np.testing.assert_allclose(np.asarray(params["w"]),
                                   np.asarray(target), atol=0.15)
        np.testing.assert_allclose(np.asarray(params["m"]),
                                   np.asarray(jnp.eye(4)), atol=0.15)


def test_gradient_compression_roundtrip():
    from repro.distributed.collectives import compress_int8, decompress_int8
    x = jax.random.normal(KEY, (128,)) * 3.0
    q, s = compress_int8(x)
    back = decompress_int8(q, s)
    assert q.dtype == jnp.int8
    np.testing.assert_allclose(np.asarray(back), np.asarray(x),
                               atol=float(s) * 0.51 + 1e-6)
