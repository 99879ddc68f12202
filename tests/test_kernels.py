"""Pallas kernels vs pure-jnp oracles: shape/dtype sweeps, exact equality."""
import numpy as np
import jax.numpy as jnp
import pytest

from repro.core.lz4_types import HASH_PRIME
from repro.kernels import ops
from repro.kernels.ref import fibhash_ref, match_extend_ref


def _np_hash(words: np.ndarray, bits: int) -> np.ndarray:
    return (((words.astype(np.uint64) * HASH_PRIME) & 0xFFFFFFFF) >> (32 - bits)).astype(np.int64)


@pytest.mark.parametrize("n", [2048, 4096, 65536, 3000, 5555])
@pytest.mark.parametrize("bits", [6, 8, 12, 13])
def test_fibhash_pallas_vs_ref(n, bits):
    rng = np.random.default_rng(n * 31 + bits)
    block = rng.integers(0, 256, n + 3, dtype=np.int32)
    w_p, h_p = ops.hash_positions(jnp.asarray(block), hash_bits=bits, use_pallas=True)
    w_r, h_r = ops.hash_positions(jnp.asarray(block), hash_bits=bits, use_pallas=False)
    np.testing.assert_array_equal(np.asarray(w_p), np.asarray(w_r))
    np.testing.assert_array_equal(np.asarray(h_p), np.asarray(h_r))
    # also vs a numpy-computed oracle
    d = block.astype(np.uint64)
    words = (d[:n] | (d[1 : n + 1] << 8) | (d[2 : n + 2] << 16) | (d[3 : n + 3] << 24)) & 0xFFFFFFFF
    np.testing.assert_array_equal(np.asarray(h_p), _np_hash(words, bits))


@pytest.mark.parametrize("n", [1024, 2048, 65536, 2500])
@pytest.mark.parametrize("max_match", [12, 20, 36, 68])
def test_match_extend_pallas_vs_ref(n, max_match):
    rng = np.random.default_rng(n * 7 + max_match)
    # low-entropy data so real matches occur
    block = rng.integers(0, 4, n + max_match, dtype=np.int32)
    cand = rng.integers(0, np.maximum(1, n - 64), n, dtype=np.int32)
    valid = rng.random(n) < 0.5
    out_p = ops.match_lengths(
        jnp.asarray(block), jnp.asarray(cand), jnp.asarray(valid), n,
        max_match=max_match, use_pallas=True,
    )
    out_r = ops.match_lengths(
        jnp.asarray(block), jnp.asarray(cand), jnp.asarray(valid), n,
        max_match=max_match, use_pallas=False,
    )
    np.testing.assert_array_equal(np.asarray(out_p), np.asarray(out_r))
    assert np.asarray(out_p)[valid].min() >= 4
    assert np.asarray(out_p).max() <= max_match
    assert (np.asarray(out_p)[~valid] == 0).all()


def test_match_extend_against_python_oracle():
    """Check the bounded prefix semantics against a dead-simple python loop."""
    rng = np.random.default_rng(0)
    n = 2048
    max_match = 36
    block = rng.integers(0, 3, n + max_match, dtype=np.int32)
    cand = rng.integers(0, n - 64, n, dtype=np.int32)
    valid = np.ones(n, dtype=bool)
    out = np.asarray(
        ops.match_lengths(
            jnp.asarray(block), jnp.asarray(cand), jnp.asarray(valid), n,
            max_match=max_match, use_pallas=True,
        )
    )
    for p in rng.integers(0, n, 200):
        q = cand[p]
        cap = min(max_match - 4, n - 5 - (p + 4))
        cap = max(cap, 0)
        l = 0
        while l < cap and block[p + 4 + l] == block[q + 4 + l]:
            l += 1
        assert out[p] == 4 + l, (p, q, out[p], 4 + l)


def test_match_extend_end_of_block_cap():
    """Match end must respect the last-5-literals rule."""
    n = 2048
    block = np.zeros(n + 36, dtype=np.int32)  # all zeros -> max-length matches
    cand = np.zeros(n, dtype=np.int32)
    valid = np.ones(n, dtype=bool)
    out = np.asarray(
        ops.match_lengths(
            jnp.asarray(block), jnp.asarray(cand), jnp.asarray(valid), n,
            max_match=36, use_pallas=True,
        )
    )
    p = np.arange(n)
    expected = 4 + np.clip(n - 5 - (p + 4), 0, 32)
    np.testing.assert_array_equal(out, expected)


# --- execution mode per backend (repro.kernels.backend) ----------------------

from repro.kernels import backend  # noqa: E402


@pytest.mark.parametrize("kernel", backend.KERNELS)
def test_interpret_mode_per_backend(kernel):
    """Interpreted off the TPU; compiled on a TPU, or a named refusal."""
    assert backend.interpret_mode(kernel, "cpu") is True
    if kernel in backend.TPU_REFUSED:
        with pytest.raises(backend.PallasUnsupportedError, match=kernel) as e:
            backend.interpret_mode(kernel, "tpu")
        assert backend.TPU_REFUSED[kernel] in str(e.value)
    else:
        assert backend.interpret_mode(kernel, "tpu") is False


def test_interpret_mode_rejects_unknown_kernel():
    with pytest.raises(ValueError, match="unknown Pallas kernel"):
        backend.interpret_mode("no_such_kernel", "tpu")


def test_use_pallas_on_tpu_raises_for_a_refused_kernel(monkeypatch):
    """The engine path resolves through the same place: with the backend
    reported as TPU, tracing the Pallas match-extend raises instead of
    interpreting."""
    monkeypatch.setattr(backend.jax, "default_backend", lambda: "tpu")
    n = 3 * 2048 + 7  # a shape no other test compiles, so this one traces
    block = jnp.zeros((n + 40,), jnp.int32)
    cand = jnp.zeros((n,), jnp.int32)
    valid = jnp.zeros((n,), bool)
    with pytest.raises(backend.PallasUnsupportedError, match="match_extend"):
        ops.match_lengths(block, cand, valid, n, max_match=36,
                          use_pallas=True)
