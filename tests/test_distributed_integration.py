"""Multi-device integration tests (run in a subprocess with 8 fake
CPU devices so the main test process keeps its single-device world)."""

import json
import pathlib
import subprocess
import sys
import textwrap

import numpy as np
import pytest

# slow: subprocess jax restarts dominate runtime; multidevice: the CI
# multidevice lane runs these per PR (the subprocesses force their own
# 8 host devices, so the marker is routing, not a requirement)
pytestmark = [pytest.mark.slow, pytest.mark.multidevice]

SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import json
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from repro.training import compression as comp
    from repro.training import optimizer as opt_mod

    mesh = jax.make_mesh((8,), ("data",))
    opt = opt_mod.sgd(lr=0.1, momentum=0.0)

    # data-parallel quadratic: each shard holds its own target; the
    # compressed psum must converge to the MEAN target.
    targets = jnp.arange(8.0)  # per-shard target
    params = {"w": jnp.zeros(())}
    state = opt.init(params)
    cstate = comp.CompressionState.zeros_like({"w": jnp.zeros(())})

    def local_grad(w, tgt):
        return {"w": 2 * (w - tgt)}

    @jax.jit
    def step(params, state, cstate, targets):
        def inner(p, tgt, cres):
            grads = local_grad(p["w"], tgt[0])
            mean, new_c = comp.compressed_psum_step(
                grads, comp.CompressionState({"w": cres}), "data",
                mode="bf16")
            return mean["w"], new_c.residual["w"]

        mean_g, new_res = jax.shard_map(
            inner, mesh=mesh,
            in_specs=(P(), P("data"), P()),
            out_specs=(P(), P()),
            check_vma=False,
        )(params, targets, cstate.residual["w"])
        new_params, new_state = opt.update({"w": mean_g}, params, state)
        return new_params, new_state, comp.CompressionState({"w": new_res})

    for _ in range(80):
        params, state, cstate = step(params, state, cstate, targets)

    print(json.dumps({"w": float(params["w"]),
                      "target": float(jnp.mean(targets))}))
""")

SCRIPT_ELASTIC = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import json
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.checkpoint.checkpoint import CheckpointManager
    from repro.distributed.elastic import remesh_plan

    # train on an 8-device mesh, checkpoint, "lose" 4 devices, restore
    # on the remesh plan's smaller mesh.
    import tempfile
    d = tempfile.mkdtemp()
    mgr = CheckpointManager(d)

    mesh8 = jax.make_mesh((4, 2), ("data", "model"))
    sh = NamedSharding(mesh8, P("data", "model"))
    w = jax.device_put(jnp.arange(32.0).reshape(8, 4), sh)
    mgr.save(1, {"w": w}, {"shape": [4, 2], "axes": ["data", "model"]})

    plan = remesh_plan((4, 2), ("data", "model"), healthy_devices=4)
    mesh_new = jax.make_mesh(plan["shape"], plan["axes"],
                             devices=jax.devices()[:plan["devices_used"]])
    restored = mgr.restore(1, {"w": jnp.zeros((8, 4))})
    w2 = jax.device_put(jnp.asarray(restored["w"]),
                        NamedSharding(mesh_new, P("data", "model")))
    ok = bool(jnp.all(w2 == jnp.arange(32.0).reshape(8, 4)))
    print(json.dumps({"ok": ok, "shape": list(plan["shape"]),
                      "devices": plan["devices_used"]}))
""")


def _run(script: str) -> dict:
    # 8 fake devices on few-core CI runners oversubscribe the host and
    # the shard_map compile dominates wall time, so the budget is wide;
    # CPU time per script is ~90s
    repo_root = pathlib.Path(__file__).resolve().parent.parent
    out = subprocess.run(
        [sys.executable, "-c", script], capture_output=True, text=True,
        env={"PYTHONPATH": "src", "PATH": "/usr/bin:/bin",
             "HOME": "/tmp"}, timeout=900, cwd=repo_root)
    assert out.returncode == 0, out.stderr[-2000:]
    return json.loads(out.stdout.strip().splitlines()[-1])


def test_compressed_psum_shard_map_converges():
    res = _run(SCRIPT)
    assert abs(res["w"] - res["target"]) < 0.05, res


def test_elastic_checkpoint_remesh_roundtrip():
    res = _run(SCRIPT_ELASTIC)
    assert res["ok"]
    assert res["devices"] == 4


SCRIPT_MOE_A2A = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import json
    import jax, jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.models import transformer as T

    mesh = jax.make_mesh((2, 4), ("data", "model"))
    cfg = T.TransformerConfig(name="m", n_layers=1, d_model=32, n_heads=4,
                              n_kv_heads=4, d_head=8, d_ff=0, vocab_size=11,
                              moe=True, n_experts=8, moe_top_k=2,
                              d_ff_expert=16, capacity_factor=16.0,
                              sequence_parallel=True, moe_a2a=True)
    p = jax.tree.map(lambda a: a[0],
                     T.init_params(jax.random.PRNGKey(0), cfg)
                     ["layers"]["moe"])
    x = jax.random.normal(jax.random.PRNGKey(1), (64, 32))
    with jax.set_mesh(mesh):
        xs = jax.device_put(x, NamedSharding(mesh, P(("data", "model"), None)))
        out_a2a, _ = jax.jit(lambda p, x: T.moe_block_a2a(p, x, cfg))(p, xs)
        out_ref, _ = jax.jit(lambda p, x: T.moe_block(p, x, cfg))(p, x)
        fwd = float(jnp.max(jnp.abs(out_a2a.astype(jnp.float32)
                                    - out_ref.astype(jnp.float32))))
        g1 = jax.jit(jax.grad(lambda p, x: jnp.sum(
            T.moe_block_a2a(p, x, cfg)[0] ** 2)))(p, xs)
    g2 = jax.jit(jax.grad(lambda p, x: jnp.sum(
        T.moe_block(p, x, cfg)[0] ** 2)))(p, x)
    gerr = max(float(jnp.max(jnp.abs(a - b))) for a, b in
               zip(jax.tree.leaves(g1), jax.tree.leaves(g2)))
    print(json.dumps({"fwd": fwd, "grad": gerr}))
""")


def test_moe_a2a_matches_implicit_path():
    """shard_map all-to-all EP == SPMD path, forward AND gradients
    (no capacity drops at cf=16)."""
    res = _run(SCRIPT_MOE_A2A)
    assert res["fwd"] < 1e-5, res
    assert res["grad"] < 1e-4, res
