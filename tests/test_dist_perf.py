"""Multi-device numerical validation of the §Perf mechanisms (8-device
subprocess): distributed_topk == plain top_k, sharded MoE dispatch ==
global dispatch, binned segment sum == flat segment sum."""
import json
import os
import subprocess
import sys
import textwrap

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_SUBPROC = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import json, dataclasses
    import jax, jax.numpy as jnp
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.dist.sharding import use_mesh_rules
    from repro.dist.collectives import distributed_topk

    mesh = jax.make_mesh((4, 2), ("data", "model"),
                         axis_types=(jax.sharding.AxisType.Auto,) * 2)
    out = {}
    rng = np.random.default_rng(0)

    # --- distributed 2-stage top-k == plain top-k (exact) ---
    with use_mesh_rules(mesh):
        scores = jnp.asarray(rng.standard_normal((8, 64)).astype(np.float32))
        scores = jax.device_put(scores, NamedSharding(mesh, P("data", "model")))
        v1, i1 = jax.jit(lambda s: distributed_topk(s, 5, mesh))(scores)
        v2, i2 = jax.lax.top_k(scores, 5)
    out["topk_val_err"] = float(jnp.abs(v1 - v2).max())
    out["topk_idx_match"] = bool((np.asarray(i1) == np.asarray(i2)).all())

    # --- sharded MoE dispatch == global (lossless capacity) ---
    from repro.models.moe import MoECfg, init_moe, moe_block
    cfg_g = MoECfg(d_model=32, d_ff=64, num_experts=4, top_k=2,
                   dispatch="global", capacity_factor=16.0)
    cfg_s = dataclasses.replace(cfg_g, dispatch="sharded")
    p = init_moe(jax.random.PRNGKey(0), cfg_g)
    x = jax.random.normal(jax.random.PRNGKey(1), (8, 16, 32))
    with use_mesh_rules(mesh):
        o1, _ = jax.jit(lambda p, x: moe_block(p, x, cfg_g))(p, x)
        o2, _ = jax.jit(lambda p, x: moe_block(p, x, cfg_s))(p, x)
    out["moe_err"] = float(jnp.abs(o1 - o2).max())

    # --- binned segment sum == flat (under the stripe contract) ---
    from repro.models.gnn import _binned_segment_sum
    import jax.ops
    n_out, shards = 32, 4
    stripe = n_out // shards
    per = 16  # values per shard
    segs, vals = [], []
    for s in range(shards):
        segs.append(rng.integers(s * stripe, (s + 1) * stripe, per))
        vals.append(rng.standard_normal((per, 3)).astype(np.float32))
    seg = jnp.asarray(np.concatenate(segs), jnp.int32)
    val = jnp.asarray(np.concatenate(vals))
    with use_mesh_rules(mesh):
        a = jax.jit(lambda v, s: _binned_segment_sum(v, s, n_out))(val, seg)
    b = jax.ops.segment_sum(val, seg, num_segments=n_out)
    out["binned_err"] = float(jnp.abs(a - b).max())
    print(json.dumps(out))
""")


@pytest.fixture(scope="module")
def results():
    env = dict(os.environ, PYTHONPATH=os.path.join(ROOT, "src"))
    env.pop("XLA_FLAGS", None)
    r = subprocess.run([sys.executable, "-c", _SUBPROC], env=env,
                       capture_output=True, text=True, timeout=420)
    assert r.returncode == 0, r.stderr[-3000:]
    return json.loads(r.stdout.strip().splitlines()[-1])


def test_distributed_topk_exact(results):
    assert results["topk_val_err"] == 0.0
    assert results["topk_idx_match"]


def test_moe_sharded_dispatch_equivalent(results):
    assert results["moe_err"] < 1e-6


def test_binned_segment_sum_equals_flat(results):
    assert results["binned_err"] < 1e-6
