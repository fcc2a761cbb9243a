"""Pallas kernels vs pure-jnp oracles: shape/dtype sweeps in interpret mode."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ref
from repro.kernels import decode_attention as da
from repro.kernels import flash_attention as fa
from repro.kernels import quant_matmul as qm
from repro.kernels import ssd_scan as ssd

KEY = jax.random.key(42)


def rand(*shape, dtype=jnp.float32, key=KEY, scale=1.0):
    return (jax.random.normal(key, shape, jnp.float32) * scale).astype(dtype)


TOL = {jnp.float32: dict(rtol=3e-5, atol=3e-5),
       jnp.bfloat16: dict(rtol=3e-2, atol=3e-2)}


# ---------------------------------------------------------------------------
@pytest.mark.parametrize("B,S,H,KV,D", [
    (1, 64, 4, 4, 32),     # MHA
    (2, 160, 8, 4, 64),    # GQA, ragged block boundary
    (1, 257, 6, 2, 128),   # odd length
    (2, 128, 25, 5, 64),   # hymba-style non-pow2 heads
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention_shapes(B, S, H, KV, D, dtype):
    q = rand(B, S, H, D, dtype=dtype)
    k = rand(B, S, KV, D, dtype=dtype)
    v = rand(B, S, KV, D, dtype=dtype)
    want = ref.flash_attention(q, k, v)
    got = fa.flash_attention(q, k, v, block_q=64, block_k=64,
                             interpret=True)
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want, np.float32),
        **TOL[dtype])


@pytest.mark.parametrize("kwargs", [
    dict(window=32), dict(softcap=20.0), dict(window=16, prefix=8),
    dict(window=32, softcap=50.0, prefix=4), dict(q_offset=64),
])
def test_flash_attention_masking_modes(kwargs):
    B, S, H, KV, D = 2, 96, 4, 2, 32
    q, k, v = (rand(B, S, n, D, key=jax.random.key(i))
               for i, n in ((0, H), (1, KV), (2, KV)))
    want = ref.flash_attention(q, k, v, **kwargs)
    got = fa.flash_attention(q, k, v, block_q=32, block_k=32,
                             interpret=True, **kwargs)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=3e-5, atol=3e-5)


# ---------------------------------------------------------------------------
@pytest.mark.parametrize("B,T,H,KV,D", [
    (2, 300, 8, 4, 64),
    (1, 64, 4, 4, 32),
    (3, 1000, 14, 2, 64),  # internvl2-style
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_decode_attention_shapes(B, T, H, KV, D, dtype):
    q = rand(B, H, D, dtype=dtype)
    kc = rand(B, T, KV, D, dtype=dtype, key=jax.random.key(1))
    vc = rand(B, T, KV, D, dtype=dtype, key=jax.random.key(2))
    lengths = jnp.asarray(
        np.random.default_rng(0).integers(1, T, B), jnp.int32)
    want = ref.decode_attention(q, kc, vc, lengths)
    got = da.decode_attention(q, kc, vc, lengths, block_t=128,
                              interpret=True)
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want, np.float32),
        **TOL[dtype])


@pytest.mark.parametrize("B,T,H,KV,D,ps", [
    (2, 300, 8, 4, 64, 128),
    (3, 96, 4, 2, 32, 16),   # many small pages, ragged last page
    (1, 64, 4, 4, 32, 64),   # single page per sequence
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_paged_decode_attention_matches_dense(B, T, H, KV, D, ps, dtype):
    """The paged kernel gathers KV blocks through a (permuted) page
    table and must match the dense kernel's math exactly — including
    per-sequence valid lengths that end mid-page."""
    q = rand(B, H, D, dtype=dtype)
    kc = rand(B, T, KV, D, dtype=dtype, key=jax.random.key(1))
    vc = rand(B, T, KV, D, dtype=dtype, key=jax.random.key(2))
    lengths = jnp.asarray(
        np.random.default_rng(7).integers(1, T, B), jnp.int32)
    want = ref.decode_attention(q, kc, vc, lengths)
    kp, vp, table = da.paginate_kv(kc, vc, lengths, ps)
    # The physical layout is really scattered, not logical order.
    if B * ((T + ps - 1) // ps) > 1:
        assert not np.array_equal(
            np.asarray(table).ravel(),
            np.arange(table.size))
    got = da.paged_decode_attention(q, kp, vp, table, lengths,
                                    interpret=True)
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want, np.float32),
        **TOL[dtype])
    # And through the ops dispatcher's reference path.
    from repro.kernels import ops
    got_ref = ops.paged_decode_attention(q, kp, vp, table, lengths,
                                         impl="reference")
    np.testing.assert_allclose(
        np.asarray(got_ref, np.float32), np.asarray(want, np.float32),
        **TOL[dtype])


def test_decode_attention_window_softcap():
    B, T, H, KV, D = 2, 200, 4, 2, 32
    q = rand(B, H, D)
    kc = rand(B, T, KV, D, key=jax.random.key(1))
    vc = rand(B, T, KV, D, key=jax.random.key(2))
    lengths = jnp.array([150, 37], jnp.int32)
    for kwargs in [dict(window=64), dict(softcap=30.0),
                   dict(window=32, prefix=8)]:
        want = ref.decode_attention(q, kc, vc, lengths, **kwargs)
        got = da.decode_attention(q, kc, vc, lengths, block_t=64,
                                  interpret=True, **kwargs)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=3e-5, atol=3e-5)


# ---------------------------------------------------------------------------
@pytest.mark.parametrize("M,K,N,group,bits", [
    (64, 256, 128, 128, 8),
    (100, 384, 200, 128, 8),   # ragged M/N
    (32, 128, 64, 32, 4),      # int4
    (8, 512, 512, 512, 8),     # single group
])
def test_quant_matmul_shapes(M, K, N, group, bits):
    x = rand(M, K)
    w = rand(K, N, key=jax.random.key(7))
    wq, sc = ref.quantize_weights(w, bits=bits, group=group)
    want = ref.quant_matmul(x, wq, sc)
    got = qm.quant_matmul(x, wq, sc, block_m=32, block_n=64, block_k=group,
                          interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-4, atol=2e-4)


def test_quant_matmul_batched_lhs():
    x = rand(2, 5, 7, 128)
    w = rand(128, 96, key=jax.random.key(3))
    wq, sc = ref.quantize_weights(w, bits=8, group=64)
    want = ref.quant_matmul(x, wq, sc)
    got = qm.quant_matmul(x, wq, sc, interpret=True)
    assert got.shape == (2, 5, 7, 96)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-4, atol=2e-4)


def test_quant_matmul_under_jit():
    """The served path calls the kernel inside ``jax.jit``, where the
    leading dims are traced: sizing the row block must stay static."""
    x = rand(2, 11, 256)
    w = rand(256, 200, key=jax.random.key(3))
    wq, sc = ref.quantize_weights(w, bits=8, group=32)
    got = jax.jit(lambda x, w, s: qm.quant_matmul(x, w, s, interpret=True)
                  )(x, wq, sc)
    np.testing.assert_allclose(np.asarray(got),
                               np.asarray(ref.quant_matmul(x, wq, sc)),
                               rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("spec", [(None, "model"), ("model", None),
                                  (None, None)])
def test_quant_matmul_per_shard(spec):
    """On a mesh the dispatcher runs the kernel per shard: an N-sharded
    weight concatenates, a K-sharded one sums partial products."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.kernels import ops

    n = jax.device_count()
    mesh = jax.make_mesh((1, n), ("data", "model"),
                         axis_types=(jax.sharding.AxisType.Auto,) * 2)
    x = rand(3, 64 * n)
    w = rand(64 * n, 32 * n, key=jax.random.key(4))
    wq, sc = ref.quantize_weights(w, bits=8, group=32)
    wq = jax.device_put(wq, NamedSharding(mesh, P(*spec)))
    got = jax.jit(lambda x, w, s: ops.quant_matmul(
        x, w, s, impl="pallas", mesh=mesh, spec=spec))(x, wq, sc)
    np.testing.assert_allclose(np.asarray(got),
                               np.asarray(ref.quant_matmul(x, wq, sc)),
                               rtol=2e-4, atol=2e-4)


def test_quant_matmul_per_shard_rejects_split_scale_groups():
    """A K-sharded weight whose scale groups do not divide over the mesh
    axis is an error, never a silent gather of the whole weight."""
    from jax.sharding import AbstractMesh

    from repro.kernels import ops

    mesh = AbstractMesh((1, 4), ("data", "model"))
    x = rand(3, 192)
    wq, sc = ref.quantize_weights(rand(192, 64, key=jax.random.key(4)),
                                  bits=8, group=32)  # 6 groups over 4
    with pytest.raises(ValueError, match="scale groups"):
        ops.quant_matmul(x, wq, sc, impl="pallas", mesh=mesh,
                         spec=("model", None))


def test_quantize_roundtrip_error_bounded():
    w = rand(256, 128, key=jax.random.key(11))
    for bits, bound in ((8, 0.02), (4, 0.35)):
        wq, sc = ref.quantize_weights(w, bits=bits, group=64)
        wd = (wq.astype(jnp.float32).reshape(4, 64, 128)
              * sc[:, None, :]).reshape(256, 128)
        err = float(jnp.max(jnp.abs(wd - w)))
        assert err < bound, f"{bits}-bit max err {err}"


# ---------------------------------------------------------------------------
@pytest.mark.parametrize("B,S,H,P,G,N,chunk", [
    (1, 64, 2, 16, 1, 8, 16),
    (2, 96, 4, 32, 2, 16, 32),
    (1, 50, 2, 16, 1, 8, 16),   # ragged chunk
    (2, 128, 48, 64, 1, 128, 64),  # mamba2-like dims (scaled down B/S)
])
def test_ssd_scan_shapes(B, S, H, P, G, N, chunk):
    ks = jax.random.split(jax.random.key(5), 6)
    x = rand(B, S, H, P, key=ks[0], scale=0.5)
    dt = jax.nn.softplus(rand(B, S, H, key=ks[1]))
    A = -jnp.exp(rand(H, key=ks[2], scale=0.5))
    Bm = rand(B, S, G, N, key=ks[3], scale=0.3)
    Cm = rand(B, S, G, N, key=ks[4], scale=0.3)
    D = rand(H, key=ks[5])
    want, wstate = ref.ssd_scan(x, dt, A, Bm, Cm, D, return_state=True)
    got_c, cstate = ref.ssd_scan_chunked(x, dt, A, Bm, Cm, D, chunk=chunk,
                                         return_state=True)
    np.testing.assert_allclose(np.asarray(got_c), np.asarray(want),
                               rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(np.asarray(cstate), np.asarray(wstate),
                               rtol=2e-4, atol=2e-4)
    got_p, pstate = ssd.ssd_scan(x, dt, A, Bm, Cm, D, chunk=chunk,
                                 return_state=True, interpret=True)
    np.testing.assert_allclose(np.asarray(got_p), np.asarray(want),
                               rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(np.asarray(pstate), np.asarray(wstate),
                               rtol=2e-4, atol=2e-4)


def test_ssd_scan_under_jit():
    """``ssd_scan`` inside ``jax.jit`` (as prefill runs it), across a
    ragged chunk boundary, with the state carried out."""
    B, S, H, P, G, N = 1, 40, 4, 16, 2, 8
    ks = jax.random.split(jax.random.key(21), 6)
    args = (rand(B, S, H, P, key=ks[0], scale=0.5),
            jax.nn.softplus(rand(B, S, H, key=ks[1])),
            -jnp.exp(rand(H, key=ks[2], scale=0.5)),
            rand(B, S, G, N, key=ks[3], scale=0.3),
            rand(B, S, G, N, key=ks[4], scale=0.3), rand(H, key=ks[5]))
    got, gstate = jax.jit(lambda *a: ssd.ssd_scan(
        *a, chunk=16, return_state=True, interpret=True))(*args)
    want, wstate = ref.ssd_scan(*args, return_state=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(np.asarray(gstate), np.asarray(wstate),
                               rtol=2e-4, atol=2e-4)


def test_ssd_state_continuation():
    """Scanning [0:S1] then [S1:S] with carried state == scanning [0:S]."""
    B, S, H, P, G, N = 1, 80, 2, 16, 1, 8
    ks = jax.random.split(jax.random.key(9), 6)
    x = rand(B, S, H, P, key=ks[0], scale=0.5)
    dt = jax.nn.softplus(rand(B, S, H, key=ks[1]))
    A = -jnp.exp(rand(H, key=ks[2], scale=0.5))
    Bm = rand(B, S, G, N, key=ks[3], scale=0.3)
    Cm = rand(B, S, G, N, key=ks[4], scale=0.3)
    D = rand(H, key=ks[5])
    full = ref.ssd_scan_chunked(x, dt, A, Bm, Cm, D, chunk=16)
    y1, st1 = ref.ssd_scan_chunked(
        x[:, :48], dt[:, :48], A, Bm[:, :48], Cm[:, :48], D, chunk=16,
        return_state=True)
    y2 = ref.ssd_scan_chunked(
        x[:, 48:], dt[:, 48:], A, Bm[:, 48:], Cm[:, 48:], D, chunk=16,
        init_state=st1)
    np.testing.assert_allclose(
        np.asarray(jnp.concatenate([y1, y2], axis=1)), np.asarray(full),
        rtol=2e-4, atol=2e-4)


def test_ssd_step_matches_scan():
    """Sequential ssd_step over tokens == the batched scan."""
    B, S, H, P, G, N = 1, 12, 2, 8, 1, 4
    ks = jax.random.split(jax.random.key(13), 6)
    x = rand(B, S, H, P, key=ks[0], scale=0.5)
    dt = jax.nn.softplus(rand(B, S, H, key=ks[1]))
    A = -jnp.exp(rand(H, key=ks[2], scale=0.5))
    Bm = rand(B, S, G, N, key=ks[3], scale=0.3)
    Cm = rand(B, S, G, N, key=ks[4], scale=0.3)
    D = rand(H, key=ks[5])
    want = ref.ssd_scan(x, dt, A, Bm, Cm, D)
    state = jnp.zeros((B, H, P, N), jnp.float32)
    outs = []
    for t in range(S):
        y, state = ref.ssd_step(x[:, t], dt[:, t], A, Bm[:, t], Cm[:, t],
                                D, state)
        outs.append(y)
    got = jnp.stack(outs, axis=1)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-4, atol=2e-4)


def test_conv1d_step_matches_batch():
    B, S, C, W = 2, 10, 8, 4
    ks = jax.random.split(jax.random.key(17), 3)
    x = rand(B, S, C, key=ks[0])
    w = rand(W, C, key=ks[1])
    b = rand(C, key=ks[2], scale=0.1)
    want = ref.causal_conv1d(x, w, b)
    buf = jnp.zeros((B, W - 1, C))
    outs = []
    for t in range(S):
        y, buf = ref.causal_conv1d_step(x[:, t], w, b, buf)
        outs.append(y)
    np.testing.assert_allclose(
        np.asarray(jnp.stack(outs, 1)), np.asarray(want),
        rtol=1e-5, atol=1e-5)
