"""Pallas kernel validation: shape/dtype sweeps against the ref.py oracles,
through ``ops`` (which interprets the kernel bodies on CPU)."""

import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref


# ---------------------------------------------------------------------------
# prefix scan
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("rows,n,bc", [(1, 64, 64), (4, 1000, 256),
                                       (7, 130, 32), (16, 4096, 512)])
def test_prefix_scan_shapes(rows, n, bc):
    x = jnp.asarray(np.random.default_rng(0).normal(size=(rows, n)),
                    jnp.float32)
    got = ops.prefix_scan(x, block_cols=bc)
    np.testing.assert_allclose(np.asarray(got),
                               np.asarray(ref.prefix_scan_ref(x)),
                               rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.int32])
def test_prefix_scan_dtypes(dtype):
    x = jnp.asarray(np.random.default_rng(1).integers(0, 9, size=(3, 257)),
                    dtype)
    got = ops.prefix_scan(x, block_cols=64)
    np.testing.assert_allclose(np.asarray(got),
                               np.asarray(ref.prefix_scan_ref(x)))


# ---------------------------------------------------------------------------
# dispatch positions
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("t,e,bt", [(64, 4, 32), (533, 6, 128), (100, 32, 64),
                                    (8, 128, 8)])
def test_dispatch_positions_shapes(t, e, bt):
    rng = np.random.default_rng(t + e)
    e_idx = jnp.asarray(rng.integers(0, e, size=t), jnp.int32)
    base = jnp.asarray(rng.integers(0, 3, size=e), jnp.int32)
    pos, fill = ops.dispatch_positions(e_idx, base, n_experts=e,
                                          block_tokens=bt)
    pos_r, fill_r = ref.dispatch_positions_ref(e_idx, base, e)
    np.testing.assert_array_equal(np.asarray(pos), np.asarray(pos_r))
    np.testing.assert_array_equal(np.asarray(fill), np.asarray(fill_r))


def test_dispatch_positions_matches_moe_layer_semantics():
    """The kernel computes the paper's load scan S: position == number of
    earlier same-expert tokens + base."""
    e_idx = jnp.asarray([2, 0, 2, 2, 1, 0], jnp.int32)
    base = jnp.asarray([10, 0, 5], jnp.int32)
    pos, fill = ops.dispatch_positions(e_idx, base, n_experts=3,
                                          block_tokens=4)
    assert list(np.asarray(pos)) == [5, 10, 6, 7, 0, 11]
    assert list(np.asarray(fill)) == [12, 1, 8]


@pytest.mark.parametrize("r,t,e,bt", [(1, 64, 4, 32), (3, 533, 6, 128),
                                      (5, 100, 32, 64), (2, 8, 128, 8)])
def test_dispatch_work_prefix_shapes(r, t, e, bt):
    rng = np.random.default_rng(r * t + e)
    e_idx = rng.integers(-1, e, size=(r, t)).astype(np.int32)
    w = rng.exponential(2.0, size=(r, t)).astype(np.float32)
    w[e_idx < 0] = 0.0
    pos, fill = ops.dispatch_work_prefix(
        jnp.asarray(e_idx), jnp.asarray(w), n_experts=e, block_tokens=bt)
    # oracle: running per-destination weight in token order, per row
    pos_r = np.zeros((r, t), np.float32)
    fill_r = np.zeros((r, e), np.float32)
    for i in range(r):
        acc = np.zeros(e, np.float32)
        for j in range(t):
            if e_idx[i, j] >= 0:
                pos_r[i, j] = acc[e_idx[i, j]]
                acc[e_idx[i, j]] += w[i, j]
        fill_r[i] = acc
    np.testing.assert_allclose(np.asarray(pos), pos_r, rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(fill), fill_r,
                               rtol=1e-5, atol=1e-5)


def test_dispatch_work_prefix_unit_weights_match_positions():
    """With unit weights the weighted prefix IS the positional scan."""
    rng = np.random.default_rng(9)
    e_idx = rng.integers(0, 5, size=200).astype(np.int32)
    pos_i, fill_i = ops.dispatch_positions(
        jnp.asarray(e_idx), jnp.zeros(5, jnp.int32), n_experts=5)
    pos_w, fill_w = ops.dispatch_work_prefix(
        jnp.asarray(e_idx[None, :]), jnp.ones((1, 200), jnp.float32),
        n_experts=5)
    np.testing.assert_allclose(np.asarray(pos_w)[0], np.asarray(pos_i))
    np.testing.assert_allclose(np.asarray(fill_w)[0], np.asarray(fill_i))


# ---------------------------------------------------------------------------
# flash attention
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("h,kv,s,hd", [(4, 4, 128, 32), (4, 2, 130, 64),
                                       (8, 1, 96, 32)])
def test_flash_attention_gqa_shapes(h, kv, s, hd):
    rng = np.random.default_rng(h * s)
    q = jnp.asarray(rng.normal(size=(2, h, s, hd)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(2, kv, s, hd)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(2, kv, s, hd)), jnp.float32)
    got = ops.flash_attention(q, k, v, block_q=64, block_k=64)
    want = ref.flash_attention_ref(q, k, v)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("window", [None, 16])
@pytest.mark.parametrize("softcap", [None, 8.0])
def test_flash_attention_window_softcap(window, softcap):
    rng = np.random.default_rng(3)
    q = jnp.asarray(rng.normal(size=(1, 2, 128, 32)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(1, 2, 128, 32)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(1, 2, 128, 32)), jnp.float32)
    got = ops.flash_attention(q, k, v, window=window, softcap=softcap,
                                 block_q=32, block_k=32)
    want = ref.flash_attention_ref(q, k, v, window=window, softcap=softcap)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


def test_flash_attention_bf16():
    rng = np.random.default_rng(4)
    q = jnp.asarray(rng.normal(size=(1, 2, 64, 32)), jnp.bfloat16)
    k = jnp.asarray(rng.normal(size=(1, 2, 64, 32)), jnp.bfloat16)
    v = jnp.asarray(rng.normal(size=(1, 2, 64, 32)), jnp.bfloat16)
    got = ops.flash_attention(q, k, v, block_q=32, block_k=32)
    want = ref.flash_attention_ref(q, k, v)
    assert got.dtype == jnp.bfloat16
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               rtol=3e-2, atol=3e-2)


def test_flash_attention_matches_model_attention():
    """Kernel agrees with the chunked-XLA path the model actually runs."""
    from repro.models.attention import chunked_attention
    rng = np.random.default_rng(5)
    b, s, h, kv, hd = 2, 96, 4, 2, 32
    q = jnp.asarray(rng.normal(size=(b, s, h, hd)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(b, s, kv, hd)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(b, s, kv, hd)), jnp.float32)
    pos = jnp.broadcast_to(jnp.arange(s)[None], (b, s))
    xla = chunked_attention(q, k, v, q_positions=pos, kv_positions=pos,
                            block=32)
    pal = ops.flash_attention(q.transpose(0, 2, 1, 3),
                                 k.transpose(0, 2, 1, 3),
                                 v.transpose(0, 2, 1, 3),
                                 block_q=32, block_k=32)
    np.testing.assert_allclose(np.asarray(pal.transpose(0, 2, 1, 3)),
                               np.asarray(xla), rtol=2e-5, atol=2e-5)


# ---------------------------------------------------------------------------
# mamba scan
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("s,di,bt,bd", [(64, 128, 16, 128), (70, 36, 16, 16),
                                        (33, 256, 32, 128), (128, 64, 128, 64)])
def test_mamba_scan_shapes(s, di, bt, bd):
    rng = np.random.default_rng(s + di)
    da = jnp.asarray(rng.uniform(0.6, 1.0, size=(2, s, 4, di)), jnp.float32)
    dbx = jnp.asarray(rng.normal(size=(2, s, 4, di)), jnp.float32)
    got = ops.mamba_scan(da, dbx, block_t=bt, block_d=bd)
    want = ref.mamba_scan_ref(da, dbx)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-4, atol=1e-4)


def test_mamba_scan_matches_model_chunked_scan():
    from repro.models.ssm import selective_scan_chunked
    rng = np.random.default_rng(6)
    b, s, di, n = 1, 48, 32, 4
    da = jnp.asarray(rng.uniform(0.5, 1.0, size=(b, s, di, n)), jnp.float32)
    dbx = jnp.asarray(rng.normal(size=(b, s, di, n)), jnp.float32)
    model_h, _ = selective_scan_chunked(da, dbx, chunk=16)
    # kernel layout is (B,S,N,di)
    kern_h = ops.mamba_scan(da.transpose(0, 1, 3, 2),
                               dbx.transpose(0, 1, 3, 2),
                               block_t=16, block_d=32)
    np.testing.assert_allclose(np.asarray(kern_h.transpose(0, 1, 3, 2)),
                               np.asarray(model_h), rtol=1e-4, atol=1e-4)


# ---------------------------------------------------------------------------
# ops dispatcher
# ---------------------------------------------------------------------------

def test_ops_backend_selection():
    """``ops`` runs the kernel, interpreted off-TPU and compiled for a TPU
    (``tests/test_tpu_compile.py`` shows the compiled side)."""
    import jax
    x = jnp.ones((2, 64))
    np.testing.assert_allclose(np.asarray(ops.prefix_scan(x)),
                               np.asarray(ref.prefix_scan_ref(x)))
    text = jax.jit(ops.prefix_scan).lower(x).as_text()
    assert jax.default_backend() != "tpu"
    assert "tpu_custom_call" not in text  # CPU lowering: the interpreter
