"""Distribution: sharding rules, multi-device collectives (subprocess with
8 fake devices), gradient compression, elastic re-mesh."""
import json
import os
import subprocess
import sys
import textwrap

import pytest

from jax.sharding import PartitionSpec as P


def test_logical_to_spec_divisibility_fallback():
    """Non-divisible dims must drop the mesh axis, never error."""
    import numpy as np
    import jax
    from jax.sharding import Mesh
    from repro.dist.sharding import logical_to_spec
    dev = np.array(jax.devices()[:1]).reshape(1, 1)
    mesh = Mesh(dev, ("data", "model"))
    # axis size 1 → everything "fits" but size<=1 → dropped → all None
    spec = logical_to_spec(("batch", "heads"), (8, 6), mesh)
    assert spec == P(None, None)


ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_SUBPROC = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import json
    import jax, jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    from repro.dist.collectives import (
        make_dp_grad_fn, init_error_feedback, ring_all_reduce)
    from jax.experimental.shard_map import shard_map

    mesh = jax.make_mesh((4, 2), ("pod", "data"),
                         axis_types=(jax.sharding.AxisType.Auto,) * 2)
    out = {}

    # --- compressed DP grads ≈ exact grads ---
    def loss_fn(params, batch):
        pred = batch["x"] @ params["w"]
        return jnp.mean((pred - batch["y"]) ** 2), {}

    rng = np.random.default_rng(0)
    params = {"w": jnp.asarray(rng.random((6, 1), dtype=np.float32))}
    batch = {"x": jnp.asarray(rng.random((4, 8, 6), dtype=np.float32)),
             "y": jnp.asarray(rng.random((4, 8, 1), dtype=np.float32))}
    residuals = init_error_feedback(params, 4)
    with mesh:
        fn_c = make_dp_grad_fn(loss_fn, mesh, "pod", compress=True)
        fn_e = make_dp_grad_fn(loss_fn, mesh, "pod", compress=False)
        g_c, res, loss_c = jax.jit(fn_c)(params, batch, residuals)
        g_e, _, loss_e = jax.jit(fn_e)(params, batch, residuals)
    err = float(jnp.abs(g_c["w"] - g_e["w"]).max())
    out["compress_err"] = err
    out["residual_norm"] = float(jnp.abs(res["w"]).sum())
    out["loss_match"] = float(abs(loss_c - loss_e))

    # --- ring all-reduce == psum ---
    x = jnp.asarray(rng.random((4, 13), dtype=np.float32))
    def body(xs):
        r = ring_all_reduce(xs[0], "pod", 4)
        p = jax.lax.psum(xs[0], "pod")
        return (r - p)[None]
    with mesh:
        diff = shard_map(body, mesh=mesh, in_specs=(P("pod"),),
                         out_specs=P("pod"), check_rep=False)(x)
    out["ring_err"] = float(jnp.abs(diff).max())

    # --- elastic: save on 8-dev mesh, restore on 2-dev mesh ---
    import tempfile
    from repro.train import checkpoint as ckpt
    from repro.dist.elastic import make_mesh_for, reshard
    from jax.sharding import NamedSharding
    big = jax.make_mesh((4, 2), ("data", "model"),
                        axis_types=(jax.sharding.AxisType.Auto,) * 2)
    w = jax.device_put(jnp.arange(32.0).reshape(8, 4),
                       NamedSharding(big, P("data", "model")))
    with tempfile.TemporaryDirectory() as d:
        ckpt.save(d, 1, {"w": w})
        small = Mesh(np.array(jax.devices()[:2]).reshape(2, 1),
                     ("data", "model"))
        restored, _, _ = ckpt.restore(
            d, {"w": w},
            shardings={"w": NamedSharding(small, P("data", "model"))})
    out["elastic_ok"] = bool(
        (np.asarray(restored["w"]) == np.arange(32.0).reshape(8, 4)).all())
    out["elastic_ndev"] = len(restored["w"].sharding.device_set)
    print(json.dumps(out))
""")


@pytest.fixture(scope="module")
def subproc_results():
    env = dict(os.environ, PYTHONPATH=os.path.join(ROOT, "src"))
    env.pop("XLA_FLAGS", None)
    r = subprocess.run([sys.executable, "-c", _SUBPROC], env=env,
                       capture_output=True, text=True, timeout=420)
    assert r.returncode == 0, r.stderr[-3000:]
    return json.loads(r.stdout.strip().splitlines()[-1])


def test_compressed_grads_close_to_exact(subproc_results):
    # bf16 wire → ~3 decimal digits
    assert subproc_results["compress_err"] < 5e-3
    assert subproc_results["loss_match"] < 1e-6
    # error feedback actually carries a residual
    assert subproc_results["residual_norm"] >= 0.0


def test_ring_all_reduce_matches_psum(subproc_results):
    assert subproc_results["ring_err"] < 1e-5


def test_elastic_restore_smaller_mesh(subproc_results):
    assert subproc_results["elastic_ok"]
    assert subproc_results["elastic_ndev"] == 2
