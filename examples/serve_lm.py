"""Serve a small model with batched requests + LZ4 KV-cache offload.

  PYTHONPATH=src python examples/serve_lm.py
"""
import jax
import numpy as np

from repro.configs.base import get_config
from repro.distributed.sharding import single_device_mesh, use_mesh
from repro.models import lm
from repro.serving.engine import Request, ServingEngine, offload_cache, restore_cache

if __name__ == "__main__":
    from repro import compile_cache

    compile_cache.enable()
    cfg = get_config("gemma2-9b").reduced()
    rng = np.random.default_rng(0)
    with use_mesh(single_device_mesh()):
        params = lm.init_params(jax.random.PRNGKey(0), cfg)
        engine = ServingEngine(cfg, params, max_batch=4, cache_len=128)
        for uid in range(6):
            engine.add_request(Request(
                uid=uid,
                prompt=rng.integers(0, cfg.vocab_size, int(rng.integers(5, 20))).tolist(),
                max_new_tokens=8,
            ))
        done = engine.run()
        for r in done:
            print(f"req {r.uid}: {len(r.prompt)} prompt tokens -> {r.output}")

        # pause a session: LZ4-offload its KV cache, restore bit-exactly
        batch = {"tokens": np.array([done[0].prompt + done[0].output], np.int32)}
        cache, _ = jax.jit(lm.prefill, static_argnums=(2, 3))(params, batch, cfg, 128)
        blob, stats = offload_cache(cache)
        restored = restore_cache(blob)
        ok = all(
            np.array_equal(np.asarray(a), np.asarray(b))
            for a, b in zip(jax.tree.leaves(cache), jax.tree.leaves(restored))
        )
        print(f"KV offload: {stats['raw']} -> {stats['compressed']} bytes "
              f"(ratio {stats['ratio']:.2f}), bit-exact restore: {ok}")
