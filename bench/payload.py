"""Payloads made from the run's seed: the corpus pool and the KV cache.

The same seed gives the same bytes.  `bench/digests.json` pins both, and
`bench/tests/test_yardstick.py` checks the pins.
"""
from __future__ import annotations

import numpy as np

from bench.corpus import corpus_files


def sub_seeds(seed: int, n: int) -> list[int]:
    """``n`` 31-bit seeds drawn from any whole-number ``seed``."""
    return [int(s) for s in np.random.SeedSequence(seed).generate_state(n) >> 1]


def corpus_pool(seed: int, pool_bytes: int, corpus_seeds: int) -> bytes:
    """``pool_bytes`` of the corpus: its 14 files for each of ``corpus_seeds``
    seeds drawn from ``seed``, joined, cut to length."""
    parts = []
    for s in sub_seeds(seed, corpus_seeds):
        parts.extend(corpus_files(s).values())
    pool = b"".join(parts)
    if len(pool) < pool_bytes:
        raise ValueError(f"{corpus_seeds} corpus seeds give {len(pool)} bytes, "
                         f"fewer than the pool's {pool_bytes}")
    return pool[:pool_bytes]


def kv_shape(kv: dict) -> tuple[int, ...]:
    """(layers, sessions, slots, kv_heads, head_dim) of one K or V leaf."""
    return (kv["num_hidden_layers"], kv["sessions"], kv["slots"],
            kv["num_key_value_heads"], kv["head_dim"])


def kv_cache_fn(kv: dict):
    """A jitted function from a PRNG key to the paused sessions' KV cache.

    The tree has the layout the serving stack's decode cache has
    (`{"layers": [{"0": {"k", "v", "pos"}}], "enc_memory", "pos"}`, K and V
    stacked over layers): K and V of the first ``filled`` slots are seeded
    standard normals in bfloat16, the rest of the slots are zero, and
    ``pos`` marks the filled slots.  One call makes it on the device.
    """
    import jax
    import jax.numpy as jnp

    shape = kv_shape(kv)
    slots, filled = kv["slots"], kv["filled"]
    dtype = jnp.dtype(kv["dtype"])

    @jax.jit
    def make(key):
        kk, vk = jax.random.split(key)
        live = (jnp.arange(slots) < filled)[None, None, :, None, None]
        k = jnp.where(live, jax.random.normal(kk, shape, jnp.float32), 0).astype(dtype)
        v = jnp.where(live, jax.random.normal(vk, shape, jnp.float32), 0).astype(dtype)
        pos = jnp.where(jnp.arange(slots) < filled, jnp.arange(slots), -1)
        pos = jnp.broadcast_to(pos.astype(jnp.int32), (shape[0], slots))
        return {"layers": [{"0": {"k": k, "v": v, "pos": pos}}], "enc_memory": None,
                "pos": jnp.int32(filled)}

    return make


def kv_key(seed: int):
    import jax

    return jax.random.wrap_key_data(
        np.asarray(np.random.SeedSequence(seed).generate_state(2), np.uint32),
        impl="threefry2x32")


def kv_cache(kv: dict, seed: int):
    return kv_cache_fn(kv)(kv_key(seed))
