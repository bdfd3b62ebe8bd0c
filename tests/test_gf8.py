"""GF(2^8) field, RS matrices, and device encode/reconstruct.

Mirrors the reference's erasure-coding unit tests
(weed/storage/erasure_coding/ec_test.go:21 TestEncodingDecoding): encode,
drop <= p shards, reconstruct, byte-compare.
"""

import numpy as np
import pytest

from seaweedfs_tpu.ops import crc32c, gf8, rs_jax


def test_field_basics():
    assert gf8.gf_mul(0, 5) == 0
    assert gf8.gf_mul(1, 77) == 77
    # commutativity + distributivity spot checks
    rng = np.random.default_rng(0)
    for _ in range(50):
        a, b, c = (int(x) for x in rng.integers(0, 256, 3))
        assert gf8.gf_mul(a, b) == gf8.gf_mul(b, a)
        assert gf8.gf_mul(a, b ^ c) == gf8.gf_mul(a, b) ^ gf8.gf_mul(a, c)
    for a in range(1, 256):
        assert gf8.gf_mul(a, gf8.gf_inv(a)) == 1


def test_known_field_values():
    # generator-2 field with poly 0x11D: 2*128 = 0x11D ^ 0x100 = 0x1D
    assert gf8.gf_mul(2, 128) == 0x1D
    assert gf8.gf_pow(2, 8) == 0x1D  # 2^8 = 2 * 2^7 = 2*128 = 0x11D mod x^8.. = 0x1D
    assert gf8.gf_pow(2, 255) == 1


@pytest.mark.parametrize("d,p", [(10, 4), (14, 2), (4, 2), (3, 1)])
def test_encode_matrix_systematic(d, p):
    enc = gf8.encode_matrix(d, p)
    assert enc.shape == (d + p, d)
    np.testing.assert_array_equal(enc[:d], np.eye(d, dtype=np.uint8))
    # any d rows of enc must be invertible (MDS property)
    rng = np.random.default_rng(1)
    for _ in range(10):
        rows = sorted(rng.choice(d + p, size=d, replace=False).tolist())
        gf8.gf_mat_inv(enc[rows])  # must not raise


@pytest.mark.parametrize("d,p", [(10, 4), (14, 2)])
def test_numpy_encode_reconstruct_roundtrip(d, p):
    rng = np.random.default_rng(2)
    data = rng.integers(0, 256, size=(d, 257), dtype=np.uint8)
    parity = gf8.np_encode(data, p)
    shards = np.concatenate([data, parity], axis=0)
    # drop p shards (mixed data+parity), reconstruct all
    lost = [1, d + p - 1][: p if p < 2 else 2]
    present = [i for i in range(d + p) if i not in lost]
    corrupted = shards.copy()
    corrupted[lost] = 0
    rebuilt = gf8.np_reconstruct(corrupted, present, d, p)
    np.testing.assert_array_equal(rebuilt, shards)


def test_bit_matrix_expansion_matches_field():
    rng = np.random.default_rng(3)
    for _ in range(20):
        c, x = (int(v) for v in rng.integers(0, 256, 2))
        m = gf8.bit_matrix_of_const(c)
        xbits = np.array([(x >> i) & 1 for i in range(8)], dtype=np.uint8)
        ybits = (m.astype(np.int32) @ xbits) & 1
        y = int(sum(int(b) << i for i, b in enumerate(ybits)))
        assert y == gf8.gf_mul(c, x)


@pytest.mark.parametrize("d,p", [(10, 4), (14, 2)])
def test_jax_encode_matches_numpy(d, p):
    rng = np.random.default_rng(4)
    data = rng.integers(0, 256, size=(3, d, 128), dtype=np.uint8)
    got = np.asarray(rs_jax.encode_jit(data, d, p))
    for b in range(3):
        np.testing.assert_array_equal(got[b], gf8.np_encode(data[b], p))


@pytest.mark.parametrize("d,p,lost", [(10, 4, (0, 3, 11, 13)), (14, 2, (5, 14))])
def test_jax_reconstruct(d, p, lost):
    rng = np.random.default_rng(5)
    data = rng.integers(0, 256, size=(2, d, 96), dtype=np.uint8)
    parity = np.asarray(rs_jax.encode_jit(data, d, p))
    shards = np.concatenate([data, parity], axis=1)  # [B, n, L]
    present = tuple(i for i in range(d + p) if i not in lost)
    survivors = shards[:, sorted(present)[:d], :]
    got = np.asarray(rs_jax.reconstruct_jit(survivors, present, lost, d, p))
    np.testing.assert_array_equal(got, shards[:, list(lost), :])


def test_crc32c_known_vector():
    # RFC 3720 test vector: "123456789" -> 0xE3069283
    assert crc32c.crc32c(b"123456789") == 0xE3069283
    assert crc32c.crc32c(b"") == 0


def test_crc32c_chaining():
    data = bytes(range(200))
    v = crc32c.crc32c(data[:77])
    assert crc32c.crc32c(data[77:], v) == crc32c.crc32c(data)


def test_device_crc_batch():
    import jax

    rng = np.random.default_rng(6)
    lengths = [1, 5, 64, 100, 512, 513, 1000]
    chunk = 64
    lmax = 1024
    blocks = np.zeros((len(lengths), lmax), dtype=np.uint8)
    msgs = []
    for i, n in enumerate(lengths):
        m = rng.integers(0, 256, n, dtype=np.uint8)
        msgs.append(m)
        blocks[i, lmax - n:] = m  # LEFT-pad with zeros
    states = np.asarray(jax.jit(lambda b: crc32c.device_crc_states(b, chunk))(blocks))
    vals = crc32c.finalize(states, np.array(lengths))
    for i, m in enumerate(msgs):
        assert int(vals[i]) == crc32c.crc32c(m.tobytes()), f"len={lengths[i]}"


@pytest.mark.parametrize("chunk", [64, 256, 512])
@pytest.mark.parametrize("t", [0, 1, 2, 3, 5, 8, 13, 64])
def test_device_crc_fold_matches_host(t, chunk):
    """The loop-free program against the host CRC: T chunks a row, a power
    of two and not, every length that sits on a chunk's or the row's edge,
    in blocks of one row, two rows (the scrub's 8 MiB and 4 MiB buckets)
    and all of them."""
    import jax

    l = t * chunk
    lengths = sorted({n for n in (0, 1, chunk - 1, chunk, chunk + 1, l - 1, l)
                      if 0 <= n <= l})
    rng = np.random.default_rng(chunk + t)
    fn = jax.jit(lambda b: crc32c.device_crc_states(b, chunk))
    for rows in (1, 2, len(lengths)):
        # the longest lengths last, so one and two rows hold L-1 and L
        lens = (lengths * rows)[-rows:]
        blocks = np.zeros((rows, l), dtype=np.uint8)
        for i, n in enumerate(lens):
            blocks[i, l - n:] = rng.integers(0, 256, n, dtype=np.uint8)
        states = np.asarray(fn(blocks))
        assert states.shape == (rows,) and states.dtype == np.uint32
        vals = crc32c.finalize(states, np.array(lens))
        for i, n in enumerate(lens):
            assert int(vals[i]) == crc32c.crc32c(blocks[i, l - n:].tobytes()), \
                f"rows={rows} len={n}"


@pytest.mark.parametrize("k", [1, 64, 256, 512])
def test_crc_fold_and_plane_matrices(k):
    """The fold's matrices are S_K squared over and over, S_2n = S_n S_n,
    and the plane matrices are C_K's columns regrouped by bit."""
    s_k, c_k = crc32c.chunk_matrices(k)
    for j, m in enumerate(crc32c.fold_matrices(k, 3)):  # S_K, S_2K, S_4K
        np.testing.assert_array_equal(m.T, crc32c.chunk_matrices(k << j)[0])
    planes = crc32c.plane_matrices(k)
    assert planes.shape == (8, k, 32) and planes.dtype == np.int8
    for j in range(8):
        np.testing.assert_array_equal(planes[j], c_k[:, j::8].T)


# ---------------------------------------------------------------------------
# Pallas kernel (ops/rs_pallas.py) — interpreter mode on CPU, compiled on TPU
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("d,p", [(10, 4), (14, 2), (4, 2), (8, 3)])
def test_pallas_encode_matches_numpy(d, p):
    from seaweedfs_tpu.ops import rs_pallas
    rng = np.random.default_rng(6)
    interp = not rs_pallas.available()
    # lane sizes: tile-aligned, sub-128, and non-multiple-of-128
    for C in (512, 100, 384):
        data = rng.integers(0, 256, size=(2, d, C), dtype=np.uint8)
        got = np.asarray(rs_pallas.encode_jit(data, d, p, interpret=interp))
        for b in range(2):
            np.testing.assert_array_equal(got[b], gf8.np_encode(data[b], p))


@pytest.mark.parametrize("d,p,lost", [(10, 4, (0, 3, 11, 13)), (14, 2, (5, 14))])
def test_pallas_reconstruct(d, p, lost):
    from seaweedfs_tpu.ops import rs_pallas
    rng = np.random.default_rng(7)
    interp = not rs_pallas.available()
    data = rng.integers(0, 256, size=(2, d, 256), dtype=np.uint8)
    parity = np.asarray(rs_pallas.encode_jit(data, d, p, interpret=interp))
    shards = np.concatenate([data, parity], axis=1)
    present = tuple(i for i in range(d + p) if i not in lost)
    survivors = shards[:, sorted(present)[:d], :]
    got = np.asarray(rs_pallas.reconstruct_jit(
        survivors, present, lost, d, p, interpret=interp))
    np.testing.assert_array_equal(got, shards[:, list(lost), :])
