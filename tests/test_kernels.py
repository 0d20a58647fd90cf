"""Per-kernel shape/dtype sweeps: pallas interpret=True vs pure-jnp oracle."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

jax.config.update("jax_platform_name", "cpu")


def _allclose(a, b, rtol, atol, what=""):
    a = np.asarray(a, np.float32)
    b = np.asarray(b, np.float32)
    np.testing.assert_allclose(a, b, rtol=rtol, atol=atol, err_msg=what)


# ---------------------------------------------------------------------------
# flash_attention
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("B,S,H,KV,D", [
    (1, 128, 4, 4, 32), (2, 256, 4, 2, 64), (1, 512, 8, 2, 32),
    (2, 128, 2, 1, 128),
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("causal", [True, False])
def test_flash_attention_sweep(B, S, H, KV, D, dtype, causal):
    from repro.kernels.flash_attention.ops import flash_attention
    from repro.kernels.flash_attention.ref import flash_attention_ref
    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(k1, (B, S, H, D), dtype)
    k = jax.random.normal(k2, (B, S, KV, D), dtype)
    v = jax.random.normal(k3, (B, S, KV, D), dtype)
    out = flash_attention(q, k, v, causal=causal, bq=64, bk=64,
                          interpret=True)
    ref = flash_attention_ref(q, k, v, causal=causal)
    tol = 2e-2 if dtype == jnp.bfloat16 else 2e-5
    _allclose(out, ref, rtol=tol, atol=tol, what="flash vs ref")


def test_flash_attention_sliding_window():
    from repro.kernels.flash_attention.ops import flash_attention
    from repro.kernels.flash_attention.ref import flash_attention_ref
    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(1), 3)
    B, S, H, D = 1, 256, 2, 32
    q = jax.random.normal(k1, (B, S, H, D))
    k = jax.random.normal(k2, (B, S, H, D))
    v = jax.random.normal(k3, (B, S, H, D))
    out = flash_attention(q, k, v, causal=True, window=64, bq=64, bk=64,
                          interpret=True)
    ref = flash_attention_ref(q, k, v, causal=True, window=64)
    _allclose(out, ref, rtol=2e-5, atol=2e-5, what="sliding window")


# ---------------------------------------------------------------------------
# ssd_chunk
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("B,H,S,P,N,chunk", [
    (1, 2, 128, 32, 16, 32), (2, 4, 256, 64, 64, 64), (1, 1, 64, 16, 8, 16),
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_ssd_chunk_sweep(B, H, S, P, N, chunk, dtype):
    from repro.kernels.ssd_chunk.kernel import ssd_chunk_bhcp
    from repro.kernels.ssd_chunk.ref import ssd_chunk_ref
    ks = jax.random.split(jax.random.PRNGKey(2), 4)
    x = jax.random.normal(ks[0], (B, H, S, P), dtype)
    a_dt = -jax.nn.softplus(jax.random.normal(ks[1], (B, H, S))) * 0.5
    b = jax.random.normal(ks[2], (B, 1, S, N), dtype) * 0.3
    c = jax.random.normal(ks[3], (B, 1, S, N), dtype) * 0.3
    out = ssd_chunk_bhcp(x, a_dt.astype(dtype), b, c, chunk=chunk,
                         interpret=True)
    ref = ssd_chunk_ref(x.astype(jnp.float32), a_dt,
                        b.astype(jnp.float32), c.astype(jnp.float32),
                        chunk=chunk)
    tol = 5e-2 if dtype == jnp.bfloat16 else 1e-4
    _allclose(out, ref, rtol=tol, atol=tol, what="ssd chunk vs ref")


def test_ssd_chunk_matches_stepwise():
    """Chunked kernel == step-by-step recurrence (ground truth)."""
    from repro.kernels.ssd_chunk.kernel import ssd_chunk_bhcp
    from repro.models.ssm import ssd_step
    ks = jax.random.split(jax.random.PRNGKey(3), 4)
    B, H, S, P, N = 1, 2, 64, 16, 8
    x = jax.random.normal(ks[0], (B, H, S, P))
    a_dt = -jax.nn.softplus(jax.random.normal(ks[1], (B, H, S))) * 0.5
    b = jax.random.normal(ks[2], (B, 1, S, N)) * 0.3
    c = jax.random.normal(ks[3], (B, 1, S, N)) * 0.3
    out = ssd_chunk_bhcp(x, a_dt, b, c, chunk=16, interpret=True)
    state = jnp.zeros((B, H, P, N))
    ys = []
    ones = jnp.ones((B, H))
    for t in range(S):
        y, state = ssd_step(x[:, :, t], a_dt[:, :, t], b[:, 0, t], c[:, 0, t],
                            ones, state)
        ys.append(y)
    ref = jnp.stack(ys, axis=2)
    _allclose(out, ref, rtol=1e-4, atol=1e-4, what="chunk vs stepwise")


# ---------------------------------------------------------------------------
# mlstm_chunk
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("B,H,S,D,chunk", [
    (1, 2, 128, 32, 32), (2, 2, 64, 64, 16), (1, 4, 256, 16, 64),
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_mlstm_chunk_sweep(B, H, S, D, chunk, dtype):
    from repro.kernels.mlstm_chunk.kernel import mlstm_chunk_bhsd
    from repro.kernels.mlstm_chunk.ref import mlstm_chunk_ref
    ks = jax.random.split(jax.random.PRNGKey(4), 5)
    q = jax.random.normal(ks[0], (B, H, S, D), dtype)
    k = jax.random.normal(ks[1], (B, H, S, D), dtype)
    v = jax.random.normal(ks[2], (B, H, S, D), dtype)
    log_i = jax.nn.log_sigmoid(jax.random.normal(ks[3], (B, H, S)) - 2.0)
    log_f = jax.nn.log_sigmoid(jax.random.normal(ks[4], (B, H, S)) + 2.0)
    out = mlstm_chunk_bhsd(q, k, v, log_i, log_f, chunk=chunk,
                           interpret=True)
    ref = mlstm_chunk_ref(q.astype(jnp.float32), k.astype(jnp.float32),
                          v.astype(jnp.float32), log_i, log_f, chunk=chunk)
    tol = 5e-2 if dtype == jnp.bfloat16 else 1e-4
    _allclose(out, ref, rtol=tol, atol=tol, what="mlstm chunk vs ref")


def test_mlstm_chunk_matches_stepwise():
    from repro.kernels.mlstm_chunk.kernel import mlstm_chunk_bhsd
    from repro.models.xlstm import mlstm_cell_step
    ks = jax.random.split(jax.random.PRNGKey(5), 5)
    B, H, S, D = 1, 2, 32, 16
    q = jax.random.normal(ks[0], (B, H, S, D))
    k = jax.random.normal(ks[1], (B, H, S, D))
    v = jax.random.normal(ks[2], (B, H, S, D))
    log_i = jax.nn.log_sigmoid(jax.random.normal(ks[3], (B, H, S)) - 1.0)
    log_f = jax.nn.log_sigmoid(jax.random.normal(ks[4], (B, H, S)) + 1.0)
    out = mlstm_chunk_bhsd(q, k, v, log_i, log_f, chunk=8, interpret=True)
    carry = (jnp.zeros((B, H, D, D)), jnp.zeros((B, H, D)),
             jnp.zeros((B, H)))
    ys = []
    for t in range(S):
        y, carry = mlstm_cell_step(q[:, :, t], k[:, :, t], v[:, :, t],
                                   log_i[:, :, t], log_f[:, :, t], carry)
        ys.append(y)
    ref = jnp.stack(ys, axis=2)
    _allclose(out, ref, rtol=1e-4, atol=1e-4, what="mlstm chunk vs stepwise")


# ---------------------------------------------------------------------------
# enoki_merge
# ---------------------------------------------------------------------------

# one whole-arena tile; several tiles with the slot count padded to the
# tile; a sub-lane-width payload padded to one 128-row group
@pytest.mark.parametrize("R,V", [(256, 128), (3000, 256), (64, 8)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16, jnp.int32])
def test_enoki_merge_sweep(R, V, dtype):
    from repro.kernels.enoki_merge.kernel import enoki_merge_rows
    from repro.kernels.enoki_merge.ref import enoki_merge_ref
    ks = jax.random.split(jax.random.PRNGKey(6), 4)
    if dtype == jnp.int32:
        a = jax.random.randint(ks[0], (R, V), 0, 100, dtype)
        b = jax.random.randint(ks[1], (R, V), 0, 100, dtype)
    else:
        a = jax.random.normal(ks[0], (R, V), dtype)
        b = jax.random.normal(ks[1], (R, V), dtype)
    aver = jax.random.randint(ks[2], (R,), 0, 50, jnp.int32)
    bver = jax.random.randint(ks[3], (R,), 0, 50, jnp.int32)
    mv, mver = enoki_merge_rows(a, aver, b, bver, interpret=True)
    rv, rver = enoki_merge_ref(a, aver, b, bver)
    _allclose(mv, rv, 0, 0, "merge values")
    _allclose(mver, rver, 0, 0, "merge versions")


def test_enoki_merge_commutative_idempotent():
    """CRDT laws on the kernel itself (versions totally ordered => LWW is a
    proper CRDT)."""
    from repro.kernels.enoki_merge.kernel import enoki_merge_rows
    ks = jax.random.split(jax.random.PRNGKey(7), 4)
    R, V = 128, 64
    a = jax.random.normal(ks[0], (R, V))
    b = jax.random.normal(ks[1], (R, V))
    # distinct versions => merge is commutative even on values
    aver = jax.random.permutation(ks[2], jnp.arange(R, dtype=jnp.int32))
    bver = jax.random.permutation(ks[3], jnp.arange(R, dtype=jnp.int32)) + R
    ab = enoki_merge_rows(a, aver, b, bver, interpret=True)
    ba = enoki_merge_rows(b, bver, a, aver, interpret=True)
    _allclose(ab[0], ba[0], 0, 0, "commutative values")
    _allclose(ab[1], ba[1], 0, 0, "commutative versions")
    aa = enoki_merge_rows(ab[0], ab[1], ab[0], ab[1], interpret=True)
    _allclose(aa[0], ab[0], 0, 0, "idempotent")


@pytest.mark.parametrize("width,dtype,ok", [
    (2560, jnp.float32, True), (2561, jnp.float32, False),
    (5120, jnp.bfloat16, True), (5121, jnp.bfloat16, False)])
def test_merge_width_refused_when_declared(width, dtype, ok):
    """A keygroup whose 1024-row merge tile would overrun the kernel's
    VMEM ceiling is refused when it is declared, not at its first merge;
    the widest admitted width stays under the ceiling."""
    from repro.core.keygroup import KeygroupSpec
    from repro.kernels.enoki_merge.kernel import (MAX_VMEM_BYTES,
                                                  merge_vmem_bytes)
    need = merge_vmem_bytes(4096, width, jnp.dtype(dtype).itemsize)
    assert (need <= MAX_VMEM_BYTES) == ok
    if ok:
        KeygroupSpec(name="wide", value_width=width, dtype=dtype)
    else:
        with pytest.raises(ValueError, match="too wide"):
            KeygroupSpec(name="wide", value_width=width, dtype=dtype)


@pytest.mark.parametrize("n,row_width", [(10, 4), (8, 4), (3, 4), (7, 7)])
def test_merge_flat_keygroup_ragged_tail(n, row_width):
    """Row-granularity contract: ceil(N/row_width) version entries, the
    last owning the ragged tail — its version must be MERGED into the
    returned versions (max of the compared pair), never dropped, and the
    tail payload follows the strictly-greater version like full rows do."""
    from repro.kernels.enoki_merge.ops import merge_flat_keygroup
    rows = n // row_width
    nver = rows + (1 if rows * row_width < n else 0)
    ks = jax.random.split(jax.random.PRNGKey(11), 2)
    a = jax.random.normal(ks[0], (n,))
    b = jax.random.normal(ks[1], (n,))
    aver = (jnp.arange(nver, dtype=jnp.int32) * 3 + 1) % 7      # mixed wins
    bver = (jnp.arange(nver, dtype=jnp.int32) * 5 + 2) % 7
    out, mver = merge_flat_keygroup(a, b_flat=b, a_ver=aver, b_ver=bver,
                                    row_width=row_width, interpret=True)
    assert out.shape == (n,) and mver.shape == (nver,)
    _allclose(mver, jnp.maximum(aver, bver), 0, 0, "flat versions")
    # per-row reference: row i (incl. the ragged tail row) follows b iff
    # b's version is strictly greater
    ref = np.asarray(a).copy()
    bn = np.asarray(b)
    for i in range(nver):
        lo, hi = i * row_width, min((i + 1) * row_width, n)
        if int(bver[i]) > int(aver[i]):
            ref[lo:hi] = bn[lo:hi]
    _allclose(out, jnp.asarray(ref), 0, 0, "flat payload")
    if rows * row_width < n:
        # the old tail-dropping call shape (rows version entries) must be
        # rejected loudly, not silently mis-merged
        with pytest.raises(AssertionError):
            merge_flat_keygroup(a, aver[:rows], b, bver[:rows],
                                row_width=row_width, interpret=True)
