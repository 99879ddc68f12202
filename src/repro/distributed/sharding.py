"""Mesh context + logical sharding rules for params, activations and caches.

Mesh axes: ("data", "model") single-pod, ("pod", "data", "model") multi-pod.
  * batch           -> ("pod","data")        (DP; pod is an outer DP axis)
  * q-heads, d_ff, experts' hidden, vocab -> "model"   (TP; GSPMD pads
    non-divisible head counts — whisper 12, minicpm 36)
  * FSDP: the non-TP big dimension of 2D+ weights -> "data" (ZeRO-3 style;
    XLA all-gathers on use, reduce-scatters grads)
  * decode KV caches: sequence axis -> "model" (32k) or ("data","model")
    (500k) — flash-decode style partial-softmax combine is inserted by SPMD.
"""
from __future__ import annotations

import contextlib
from typing import Any

import jax
import numpy as np
from jax.sharding import PartitionSpec as P
from jax.sharding import AxisType, Mesh, NamedSharding

_STATE: dict[str, Any] = {"mesh": None}


def set_global_mesh(mesh: Mesh | None):
    _STATE["mesh"] = mesh


def get_mesh() -> Mesh | None:
    return _STATE["mesh"]


@contextlib.contextmanager
def use_mesh(mesh: Mesh):
    prev = _STATE["mesh"]
    _STATE["mesh"] = mesh
    try:
        with mesh:
            yield mesh
    finally:
        _STATE["mesh"] = prev


def make_mesh(shape, axes, devices=None) -> Mesh:
    """`jax.make_mesh` with every axis Auto (jax defaults to Explicit), so
    sharding is propagated by the compiler from the rules below."""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes),
                         devices=devices)


def single_device_mesh() -> Mesh:
    return make_mesh((1, 1), ("data", "model"))


def batch_axes(mesh: Mesh | None = None, pure_dp: bool = False):
    mesh = mesh or get_mesh()
    if mesh is None:
        return ()
    names = ("pod", "data", "model") if pure_dp else ("pod", "data")
    return tuple(a for a in names if a in mesh.axis_names)


def sharding(spec: P, mesh: Mesh | None = None) -> NamedSharding:
    mesh = mesh or get_mesh()
    return NamedSharding(mesh, spec)


def constrain(x, *spec_elems):
    """with_sharding_constraint if a mesh is active (no-op otherwise)."""
    mesh = get_mesh()
    if mesh is None:
        return x
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, P(*spec_elems)))


def constrain_batch(x, seq_shard: bool = False, pure_dp: bool = False):
    """Shard the leading (batch) axis over the DP axes; optionally also the
    sequence axis on "model" (Megatron-style sequence parallelism)."""
    mesh = get_mesh()
    if mesh is None:
        return x
    ba = batch_axes(mesh, pure_dp)
    if pure_dp and x.shape[0] % (np.prod([mesh.shape[a] for a in ba]) or 1) != 0:
        ba = batch_axes(mesh)  # fall back when batch does not divide
    if seq_shard and not pure_dp and x.ndim >= 3 and x.shape[1] % mesh.shape["model"] == 0:
        spec = (ba, "model") + (None,) * (x.ndim - 2)
    else:
        spec = (ba,) + (None,) * (x.ndim - 1)
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, P(*spec)))


def local_batch(global_batch: int, mesh: Mesh | None = None) -> int:
    mesh = mesh or get_mesh()
    if mesh is None:
        return global_batch
    n = int(np.prod([mesh.shape[a] for a in batch_axes(mesh)])) or 1
    assert global_batch % n == 0, (global_batch, n)
    return global_batch // n


# ---------------------------------------------------------------------------
# Parameter sharding rules (path-based).
# ---------------------------------------------------------------------------

def _leaf_spec(path: str, shape: tuple[int, ...], fsdp: bool, tp: int) -> P:
    """Sharding spec for one parameter, from its tree path and shape."""
    stacked = "segments" in path  # scanned params carry a leading repeats axis
    off = 1 if stacked else 0

    def fs(ax):  # data-axis (FSDP/ZeRO-3) shard for big dims only
        return "data" if (fsdp and shape[off + ax] >= 1024) else None

    name = path.split("/")[-2] if path.endswith("/w") or path.endswith("/b") else path.split("/")[-1]

    def pad(spec_tail: tuple) -> P:
        full = (None,) * off + spec_tail
        assert len(full) == len(shape), (path, shape, full)
        return P(*full)

    nd = len(shape) - off
    if path.endswith("/b") or nd == 1:  # biases, norms, scalars
        return pad((None,) * nd)
    if name in ("embed", "unembed"):
        # (V, d): vocab on model, d on data (fsdp)
        return pad(("model", fs(1)))
    if name in ("wq",):
        return pad((fs(0), "model"))
    if name in ("wk", "wv"):  # kv heads < TP on every assigned arch: replicate TP
        return pad((fs(0), None))
    if name in ("wo",):
        return pad(("model", fs(1)))
    if name in ("w_gate", "w_up", "w_in", "wx", "wgate", "wa", "wi_gate"):
        return pad((fs(0), "model"))
    if name in ("w_down", "w_out", "wo_proj"):
        return pad(("model", fs(1)))
    if name in ("w1", "w3"):  # MoE (E, d, F)
        return pad((None, fs(1), "model"))
    if name in ("w2",):       # MoE (E, F, d)
        return pad((None, "model", fs(2)))
    if name in ("wr",):       # router (d, E)
        return pad((None, None))
    if nd == 2:
        # generic 2D: TP on the trailing dim if it divides, FSDP on the other
        if shape[off + 1] % tp == 0 and shape[off + 1] >= tp:
            return pad((fs(0), "model"))
        return pad((fs(0), None))
    return pad((None,) * nd)


def param_specs(params_shape, fsdp: bool, mesh: Mesh | None = None, pure_dp: bool = False):
    """PyTree of PartitionSpecs matching a params (shape) tree."""
    mesh = mesh or get_mesh()
    tp = mesh.shape["model"] if mesh is not None else 1

    def walk(tree, path):
        if isinstance(tree, dict):
            return {k: walk(v, f"{path}/{k}") for k, v in tree.items()}
        if isinstance(tree, (list, tuple)):
            out = [walk(v, f"{path}/{i}") for i, v in enumerate(tree)]
            return type(tree)(out)
        shape = tuple(tree.shape)
        spec = _leaf_spec(path, shape, fsdp, tp)
        if pure_dp:  # no TP: drop "model"; widen FSDP shards to both axes
            elems = [None if el == "model" else el for el in spec]
            elems = [("data", "model") if el == "data" else el for el in elems]
            spec = P(*elems)
        return spec

    return walk(params_shape, "")


def param_shardings(params_shape, fsdp: bool, mesh: Mesh | None = None):
    mesh = mesh or get_mesh()
    specs = param_specs(params_shape, fsdp, mesh)
    return jax.tree.map(lambda s: NamedSharding(mesh, s), specs,
                        is_leaf=lambda x: isinstance(x, P))
