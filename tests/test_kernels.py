"""Per-kernel validation: shape/dtype sweeps + hypothesis properties,
always against the pure-jnp oracle, in interpret mode on CPU."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

pytest.importorskip("hypothesis")  # container may lack it
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.kernels.flash_attention.ops import flash_attention
from repro.kernels.flash_attention.ref import attention_ref
from repro.kernels.ssm_scan.ops import ssm_scan
from repro.kernels.ssm_scan.ref import ssm_scan_chunked, ssm_scan_ref
from repro.kernels.checksum.ops import checksum_digest
from repro.kernels.checksum.ref import digest_ref
from repro.core.integrity import checksum_bytes


def _tol(dtype):
    return dict(rtol=2e-2, atol=2e-2) if dtype == jnp.bfloat16 \
        else dict(rtol=2e-5, atol=2e-5)


# ---------------------------------------------------------------------------
# flash attention
# ---------------------------------------------------------------------------
ATTN_CASES = [
    # B, Sq, Skv, H, KV, dh, causal, window
    (2, 128, 128, 4, 2, 64, True, None),
    (1, 128, 128, 4, 4, 128, True, None),   # MHA, MXU-aligned dh
    (1, 96, 96, 8, 1, 32, True, None),      # MQA, ragged seq
    (2, 64, 256, 4, 4, 48, False, None),    # cross/bidir, padded dh
    (1, 256, 256, 4, 2, 64, True, 96),      # sliding window
    (1, 130, 130, 2, 2, 80, True, 64),      # non-multiple seq + window
]


@pytest.mark.parametrize("case", ATTN_CASES,
                         ids=[f"a{i}" for i in range(len(ATTN_CASES))])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention_matches_ref(case, dtype):
    B, Sq, Skv, H, KV, dh, causal, window = case
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(ks[0], (B, Sq, H, dh), dtype)
    k = jax.random.normal(ks[1], (B, Skv, KV, dh), dtype)
    v = jax.random.normal(ks[2], (B, Skv, KV, dh), dtype)
    got = flash_attention(q, k, v, causal=causal, window=window)
    want = attention_ref(q, k, v, causal=causal, window=window)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), **_tol(dtype))


@settings(max_examples=12, deadline=None)
@given(st.integers(1, 2), st.sampled_from([32, 64, 96]),
       st.sampled_from([1, 2, 4]), st.sampled_from([16, 32, 64]),
       st.booleans())
def test_flash_attention_property(b, s, kv, dh, causal):
    h = kv * 2
    ks = jax.random.split(jax.random.PRNGKey(s + dh), 3)
    q = jax.random.normal(ks[0], (b, s, h, dh), jnp.float32)
    k = jax.random.normal(ks[1], (b, s, kv, dh), jnp.float32)
    v = jax.random.normal(ks[2], (b, s, kv, dh), jnp.float32)
    got = flash_attention(q, k, v, causal=causal, window=None)
    want = attention_ref(q, k, v, causal=causal, window=None)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=3e-5, atol=3e-5)


def test_flash_attention_causality():
    """Future tokens must not influence earlier outputs."""
    ks = jax.random.split(jax.random.PRNGKey(3), 3)
    q = jax.random.normal(ks[0], (1, 64, 2, 32), jnp.float32)
    k = jax.random.normal(ks[1], (1, 64, 2, 32), jnp.float32)
    v = jax.random.normal(ks[2], (1, 64, 2, 32), jnp.float32)
    base = flash_attention(q, k, v, causal=True, window=None)
    k2 = k.at[:, 40:].set(99.0)
    v2 = v.at[:, 40:].set(-99.0)
    pert = flash_attention(q, k2, v2, causal=True, window=None)
    np.testing.assert_allclose(np.asarray(base[:, :40]),
                               np.asarray(pert[:, :40]), rtol=1e-6, atol=1e-6)


# ---------------------------------------------------------------------------
# ssm scan
# ---------------------------------------------------------------------------
def _ssm_inputs(B, T, H, K, V, seed, scalar=False, decay_scale=1.5):
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    q = jax.random.normal(ks[0], (B, T, H, K)) * 0.5
    k = jax.random.normal(ks[1], (B, T, H, K)) * 0.5
    v = jax.random.normal(ks[2], (B, T, H, V)) * 0.5
    if scalar:
        g = -jnp.exp(jax.random.normal(ks[3], (B, T, H, 1)) - decay_scale)
        g = jnp.broadcast_to(g, (B, T, H, K))
    else:
        g = -jnp.exp(jax.random.normal(ks[3], (B, T, H, K)) - decay_scale)
    s0 = jax.random.normal(ks[4], (B, H, K, V)) * 0.3
    u = jax.random.normal(ks[5], (H, K)) * 0.5
    return q, k, v, g, s0, u


SSM_CASES = [
    # B, T, H, K, V, use_u, chunk, sub
    (2, 64, 3, 8, 16, False, 32, 8),
    (1, 128, 2, 16, 16, False, 64, 16),
    (2, 48, 2, 8, 8, True, 16, 8),
    (1, 40, 4, 8, 8, True, 16, 4),          # pad path (40 % 16 != 0)
    (1, 256, 1, 32, 32, False, 128, 16),
]


@pytest.mark.parametrize("case", SSM_CASES,
                         ids=[f"s{i}" for i in range(len(SSM_CASES))])
def test_ssm_scan_matches_ref(case):
    B, T, H, K, V, use_u, chunk, sub = case
    q, k, v, g, s0, u = _ssm_inputs(B, T, H, K, V, seed=T + K)
    uu = u if use_u else None
    y_ref, s_ref = ssm_scan_ref(q, k, v, g, u=uu, initial_state=s0)
    y, s_fin = ssm_scan(q, k, v, g, u=uu, initial_state=s0,
                        chunk=chunk, subchunk=sub)
    np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref),
                               rtol=3e-5, atol=3e-5)
    np.testing.assert_allclose(np.asarray(s_fin), np.asarray(s_ref),
                               rtol=3e-5, atol=3e-5)


@settings(max_examples=10, deadline=None)
@given(st.sampled_from([16, 32, 48]), st.integers(1, 3),
       st.sampled_from([4, 8]), st.booleans(), st.booleans())
def test_ssm_chunked_jnp_property(T, H, K, use_u, scalar):
    q, k, v, g, s0, u = _ssm_inputs(1, T, H, K, K, seed=T * H + K,
                                    scalar=scalar)
    uu = u if use_u else None
    y_ref, s_ref = ssm_scan_ref(q, k, v, g, u=uu, initial_state=s0)
    y, s = ssm_scan_chunked(q, k, v, g, u=uu, initial_state=s0,
                            chunk=16, subchunk=8,
                            scalar_decay=scalar and not use_u)
    np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref),
                               rtol=5e-5, atol=5e-5)
    np.testing.assert_allclose(np.asarray(s), np.asarray(s_ref),
                               rtol=5e-5, atol=5e-5)


def test_ssm_scan_strong_decay_stability():
    """Strong decays (rwkv-style) must not overflow the chunked form."""
    q, k, v, g, s0, u = _ssm_inputs(1, 64, 2, 8, 8, seed=0, decay_scale=-1.5)
    # decay_scale -1.5 -> log decays around -e^{1.5} ~ -4.5 per step
    y_ref, s_ref = ssm_scan_ref(q, k, v, g, u=u, initial_state=s0)
    y, s = ssm_scan(q, k, v, g, u=u, initial_state=s0, chunk=32, subchunk=8)
    assert np.isfinite(np.asarray(y)).all()
    np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref),
                               rtol=1e-4, atol=1e-4)


# ---------------------------------------------------------------------------
# checksum
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("shape,dtype", [
    ((1024,), jnp.float32), ((8, 128), jnp.float32), ((1000,), jnp.float32),
    ((333,), jnp.int32), ((64, 9), jnp.bfloat16), ((5,), jnp.float32),
])
def test_checksum_kernel_matches_bytes(shape, dtype):
    x = jax.random.normal(jax.random.PRNGKey(0), shape).astype(dtype)
    d_kernel = checksum_digest(x, use_pallas=True)
    d_jnp = checksum_digest(x, use_pallas=False)
    d_bytes = digest_ref(np.asarray(x).tobytes())
    assert d_kernel == d_bytes == d_jnp


@pytest.mark.parametrize("shape,dtype", [
    ((1300, 128), np.float32),           # ragged last grid block
    ((2048 * 128 + 17,), jnp.bfloat16),  # odd count: half-filled last word
    ((4097 * 33 + 5,), np.int8),         # 1-byte elements, partial word
], ids=["fp32", "bf16", "int8"])
def test_checksum_word_view_matches_digest_ref(shape, dtype):
    """The kernel reads elements, not words: each must land at its byte
    position in the little-endian word stream that digest_ref hashes."""
    rng = np.random.default_rng(7)
    if dtype == np.int8:
        x = rng.integers(-128, 128, shape, dtype=np.int8)
    else:
        x = (rng.standard_normal(shape) * 1000).astype(dtype)
    want = digest_ref(x.tobytes())
    assert checksum_digest(jnp.asarray(x)) == want
    assert checksum_digest(jnp.asarray(x), use_pallas=False) == want


@pytest.mark.parametrize("n_bytes", [0, 3, 20, 21, 40, 103])
def test_digest_ref_slices_match_the_definition(monkeypatch, n_bytes):
    """digest_ref folds its words in slices of ``CHUNK``: with slices of
    5 words, every length across slice ends gives the definition's
    ``a = sum w_i``, ``b = sum i * w_i`` (mod 2**32)."""
    from repro.kernels.checksum import ref
    monkeypatch.setattr(ref, "CHUNK", 5)
    data = np.random.default_rng(n_bytes).integers(
        0, 256, n_bytes, dtype=np.uint8).tobytes()
    padded = data + b"\0" * ((-n_bytes) % 4)
    words = [int.from_bytes(padded[k:k + 4], "little")
             for k in range(0, len(padded), 4)]
    a = sum(words) % (1 << 32)
    b = sum(i * w for i, w in enumerate(words, 1)) % (1 << 32)
    assert ref.digest_ref(data) == f"{b:08x}{a:08x}"
    signed = np.frombuffer(padded, dtype="<i4")
    assert ref.lanesum32_ref(signed) == (a, b)


@settings(max_examples=30, deadline=None)
@given(st.binary(min_size=0, max_size=3000))
def test_lanesum32_stream_matches_ref(data):
    assert checksum_bytes(data, "lanesum32") == digest_ref(data)


def test_checksum_detects_single_bitflip():
    x = np.random.default_rng(1).standard_normal(4096).astype(np.float32)
    d0 = checksum_digest(jnp.asarray(x))
    raw = bytearray(x.tobytes())
    raw[1234] ^= 0x01
    x2 = np.frombuffer(bytes(raw), np.float32)
    assert checksum_digest(jnp.asarray(x2)) != d0
