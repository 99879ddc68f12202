"""Opt-in GPipe-style pipeline parallelism over the "pod" axis.

The production posture for the assigned mesh keeps "pod" as an outer DP axis
(FSDP+TP fit the largest assigned model with headroom, and pod=2 pipelines
poorly: bubble = (S-1)/(T+S-1)).  This module provides the PP building block
for meshes where it *is* the right call (deep models on many pods):
microbatches flow stage -> stage via jax.lax.ppermute inside shard_map —
the jax-native mapping of the 1F1B/GPipe communication pattern.

Semantics: `pipeline_apply(stage_fn, stage_params, x)` computes

    y = stage_fn(p[S-1], stage_fn(p[S-2], ... stage_fn(p[0], x)))

with the S stages resident on S pods, T microbatches in flight, verified
token-exact against the sequential composition in tests/test_pipeline.py.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from .sharding import get_mesh


def pipeline_apply(stage_fn, stage_params, x, *, axis: str = "pod", n_micro: int | None = None):
    """Run a pipelined stack of stages.

    stage_fn     : (params_leaf_tree, (mb, ...)) -> (mb, ...)
    stage_params : pytree with leading axis = n_stages on every leaf
    x            : (batch, ...) global input (batch % n_micro == 0)
    """
    mesh = get_mesh()
    S = mesh.shape[axis]
    B = x.shape[0]
    T = n_micro or S  # default: as many microbatches as stages
    assert B % T == 0, (B, T)
    mb = B // T
    xm = x.reshape(T, mb, *x.shape[1:])

    def local(params_local, xm_local):
        # params_local leaves: (1, ...) — this stage's slice
        p_mine = jax.tree.map(lambda a: a[0], params_local)
        stage = jax.lax.axis_index(axis)
        steps = T + S - 1
        fwd_perm = [(i, i + 1) for i in range(S - 1)]

        def body(carry, t):
            recv, outbuf = carry
            # stage 0 ingests microbatch t (zeros once the stream is done)
            feed = jnp.where(
                t < T,
                jax.lax.dynamic_index_in_dim(xm_local, jnp.minimum(t, T - 1), 0,
                                             keepdims=False),
                jnp.zeros_like(recv),
            )
            inp = jnp.where(stage == 0, feed, recv)
            out = stage_fn(p_mine, inp)
            # last stage collects microbatch (t - (S-1)) once warm
            slot = jnp.clip(t - (S - 1), 0, T - 1)
            take = (stage == S - 1) & (t >= S - 1)
            outbuf = jax.lax.dynamic_update_index_in_dim(
                outbuf,
                jnp.where(take, out, jax.lax.dynamic_index_in_dim(outbuf, slot, 0, False)),
                slot, 0,
            )
            recv = jax.lax.ppermute(out, axis, fwd_perm)
            return (recv, outbuf), None

        recv0 = jnp.zeros_like(xm_local[0])
        outbuf0 = jnp.zeros_like(xm_local)
        (_, outbuf), _ = jax.lax.scan(body, (recv0, outbuf0), jnp.arange(steps))
        # only the last stage holds real outputs; broadcast them to all pods
        outbuf = jax.lax.psum(
            jnp.where(stage == S - 1, outbuf, jnp.zeros_like(outbuf)), axis
        )
        return outbuf

    out = jax.shard_map(
        local,
        mesh=mesh,
        in_specs=(P(axis), P()),
        out_specs=P(),
        check_vma=False,
    )(stage_params, xm)
    return out.reshape(B, *x.shape[1:])


def bubble_fraction(n_stages: int, n_micro: int) -> float:
    """GPipe bubble: idle fraction of the pipeline schedule."""
    return (n_stages - 1) / (n_micro + n_stages - 1)
