"""Pipeline parallelism: pipelined output == sequential stage composition."""
import json
import subprocess
import sys
import textwrap

import pytest

from repro.distributed.pipeline import bubble_fraction


def test_bubble_fraction():
    assert bubble_fraction(2, 2) == pytest.approx(1 / 3)
    assert bubble_fraction(4, 16) == pytest.approx(3 / 19)
    assert bubble_fraction(1, 8) == 0.0


_CODE = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    import json
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.distributed.pipeline import pipeline_apply
    from repro.distributed.sharding import use_mesh

    from repro.distributed.sharding import make_mesh
    mesh = make_mesh((4,), ("pod",))
    rng = np.random.default_rng(0)
    S, d = 4, 16
    W = jnp.asarray(rng.normal(0, 0.5, (S, d, d)), jnp.float32)
    b = jnp.asarray(rng.normal(0, 0.1, (S, d)), jnp.float32)
    x = jnp.asarray(rng.normal(0, 1, (8, d)), jnp.float32)

    def stage_fn(p, h):
        return jnp.tanh(h @ p["w"] + p["b"])

    params = {"w": W, "b": b}
    with use_mesh(mesh):
        y_pipe = jax.jit(
            lambda pp, xx: pipeline_apply(stage_fn, pp, xx, n_micro=4)
        )(params, x)
    y_seq = x
    for s in range(S):
        y_seq = stage_fn({"w": W[s], "b": b[s]}, y_seq)
    err = float(jnp.max(jnp.abs(y_pipe - y_seq)))
    print("RESULT:" + json.dumps({"max_err": err}))
""")


def test_pipeline_matches_sequential_subprocess():
    proc = subprocess.run(
        [sys.executable, "-c", _CODE], capture_output=True, text=True, timeout=300,
        env={"PYTHONPATH": "src", "PATH": "/usr/bin:/bin", "HOME": "/root",
             "JAX_PLATFORMS": "cpu"},
        cwd="/root/repo",
    )
    assert proc.returncode == 0, proc.stderr[-2000:]
    line = [l for l in proc.stdout.splitlines() if l.startswith("RESULT:")][0]
    assert json.loads(line[len("RESULT:"):])["max_err"] < 1e-6
