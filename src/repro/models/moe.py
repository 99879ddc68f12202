"""Mixtral-style MoE FFN (8 experts, top-2) with shard_map expert compute.

Communication pattern (mapped onto jax-native constructs, not NCCL-emulated):
  * tokens stay local to their DP shard — dispatch is a per-device sort
    (stable argsort by expert id + capacity clamp), so there is NO cross-
    device token exchange;
  * expert hidden dim is TP-sharded on "model" -> one psum per layer (same
    collective as a dense TP FFN);
  * with FSDP, expert weights are additionally sharded on "data" and
    all-gathered on use (XLA turns the gradient into a reduce-scatter).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.distributed.sharding import batch_axes, get_mesh
from .layers import _init


def moe_init(key, cfg, dtype=jnp.float32):
    d, f, e = cfg.d_model, cfg.d_ff, cfg.n_experts
    ks = jax.random.split(key, 4)
    return {
        "wr": {"w": _init(ks[0], (d, e), dtype=dtype)},
        "w1": {"w": _init(ks[1], (e, d, f), dtype=dtype)},
        "w3": {"w": _init(ks[2], (e, d, f), dtype=dtype)},
        "w2": {"w": _init(ks[3], (e, f, d), scale=1.0 / (f**0.5), dtype=dtype)},
    }


def _local_moe(x, wr, w1, w3, w2, cfg, fsdp: bool, tp: bool = True):
    """Per-DP-shard expert compute. x: (B_loc, S, d) local shard."""
    B, S, d = x.shape
    E, k = cfg.n_experts, cfg.top_k
    T = B * S
    C = max(8, int(T * k / E * cfg.capacity_factor))  # static capacity

    if fsdp:
        gax = "data" if tp else ("data", "model")
        w1 = jax.lax.all_gather(w1, gax, axis=1, tiled=True)
        w3 = jax.lax.all_gather(w3, gax, axis=1, tiled=True)
        w2 = jax.lax.all_gather(w2, gax, axis=2, tiled=True)

    t = x.reshape(T, d)
    logits = (t.astype(jnp.float32) @ wr.astype(jnp.float32))  # (T, E)
    top_vals, top_idx = jax.lax.top_k(logits, k)               # (T, k)
    gates = jax.nn.softmax(top_vals, axis=-1)                  # mixtral renorm

    # --- sort-based dispatch (per device) ---
    flat_e = top_idx.reshape(-1)                                # (T*k,)
    order = jnp.argsort(flat_e, stable=True)
    sorted_e = flat_e[order]
    pos_in_e = jnp.arange(T * k, dtype=jnp.int32) - jnp.searchsorted(
        sorted_e, sorted_e, side="left"
    ).astype(jnp.int32)
    keep = pos_in_e < C
    slot = jnp.where(keep, sorted_e * C + pos_in_e, E * C)      # OOB -> dropped
    tok = order // k
    buf = (
        jnp.zeros((E * C, d), x.dtype)
        .at[slot]
        .add(t[tok] * keep[:, None].astype(x.dtype), mode="drop")
    )
    be = buf.reshape(E, C, d)

    # --- expert FFN (hidden dim TP-sharded; dims here are the local F/TP) ---
    h = jnp.einsum("ecd,edf->ecf", be, w1.astype(x.dtype))
    g = jnp.einsum("ecd,edf->ecf", be, w3.astype(x.dtype))
    o = jnp.einsum("ecf,efd->ecd", jax.nn.silu(h) * g, w2.astype(x.dtype))
    if tp:
        o = jax.lax.psum(o, "model")                             # TP reduce

    # --- combine ---
    slot_by_flat = jnp.zeros((T * k,), jnp.int32).at[order].set(
        jnp.where(keep, slot, -1)
    )
    ok = slot_by_flat >= 0
    gathered = jnp.take(o.reshape(E * C, d), jnp.clip(slot_by_flat, 0), axis=0)
    gathered = gathered * ok[:, None].astype(x.dtype)
    y = (gathered.reshape(T, k, d) * gates[..., None].astype(x.dtype)).sum(1)

    # load-balancing aux loss (GShard): E * sum_e fraction_e * prob_e
    probs = jax.nn.softmax(logits, axis=-1)
    frac = jnp.zeros((E,), jnp.float32).at[flat_e].add(1.0) / (T * k)
    aux = E * jnp.sum(frac * probs.mean(0))
    return y.reshape(B, S, d), aux


def _dense_moe(p, x, cfg):
    """All-experts einsum path for tiny/non-DP-divisible token counts (decode
    with small batch): identical function value when no capacity drops occur."""
    E, k = cfg.n_experts, cfg.top_k
    w1, w3, w2 = p["w1"]["w"], p["w3"]["w"], p["w2"]["w"]
    logits = (x.astype(jnp.float32) @ p["wr"]["w"].astype(jnp.float32))  # (B,S,E)
    top_vals, top_idx = jax.lax.top_k(logits, k)
    gates = jax.nn.softmax(top_vals, axis=-1)
    gate_full = jnp.zeros(logits.shape, jnp.float32)
    for i in range(k):
        gate_full = gate_full + jax.nn.one_hot(top_idx[..., i], E) * gates[..., i : i + 1]
    h = jnp.einsum("bsd,edf->bsef", x, w1.astype(x.dtype))
    g = jnp.einsum("bsd,edf->bsef", x, w3.astype(x.dtype))
    o = jnp.einsum("bsef,efd->bsed", jax.nn.silu(h) * g, w2.astype(x.dtype))
    y = jnp.einsum("bsed,bse->bsd", o, gate_full.astype(x.dtype))
    probs = jax.nn.softmax(logits, axis=-1)
    frac = jax.nn.one_hot(top_idx, E).sum((0, 1, 2)) / (logits.shape[0] * logits.shape[1] * k)
    aux = E * jnp.sum(frac * probs.mean((0, 1)))
    return y, aux


def moe_ffn(p, x, cfg):
    """x: (B, S, d) global. Returns (y, aux_loss)."""
    import numpy as np

    mesh = get_mesh()
    ba = batch_axes(mesh, cfg.pure_dp)
    n_dp = int(np.prod([mesh.shape[a] for a in ba])) if (mesh and ba) else 1
    if x.shape[0] % n_dp != 0:
        return _dense_moe(p, x, cfg)
    fsdp = cfg.fsdp and mesh is not None and mesh.shape.get("data", 1) > 1
    tp = not cfg.pure_dp
    fax = ("data", "model") if (fsdp and not tp) else ("data" if fsdp else None)
    wspec_df = P(None, fax, "model" if tp else None)
    wspec_fd = P(None, "model" if tp else None, fax)

    def wrapped(xx, wr, w1, w3, w2):
        y, aux = _local_moe(xx, wr, w1, w3, w2, cfg, fsdp, tp)
        if ba:
            aux = jax.lax.pmean(aux, ba)
        return y, aux

    fn = jax.shard_map(
        wrapped,
        mesh=mesh,
        in_specs=(
            P(ba, None, None),
            P(None, None),
            wspec_df,
            wspec_df,
            wspec_fd,
        ),
        out_specs=(P(ba, None, None), P()),
        check_vma=False,
    )
    y, aux = fn(x, p["wr"]["w"], p["w1"]["w"], p["w3"]["w"], p["w2"]["w"])
    return y, aux
