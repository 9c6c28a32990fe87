"""Mesh-dependent tests that need placeholder devices: each spawns a fresh
python with XLA_FLAGS set (per the brief, the flag must never be set in the
main test process).  Marked `subproc` (and slow-ish: each compiles a real
SPMD module)."""
import os
import subprocess
import sys
import textwrap

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(script: str, devices: int = 64, timeout: int = 560) -> str:
    env = dict(os.environ,
               XLA_FLAGS=f"--xla_force_host_platform_device_count={devices}",
               PYTHONPATH=os.path.join(REPO, "src"))
    p = subprocess.run([sys.executable, "-c", textwrap.dedent(script)],
                       env=env, capture_output=True, text=True,
                       timeout=timeout)
    assert p.returncode == 0, f"stderr:\n{p.stderr[-4000:]}"
    return p.stdout


@pytest.mark.subproc
def test_production_mesh_shapes():
    out = _run("""
        import jax
        from repro.launch.mesh import make_production_mesh, mesh_axis_sizes
        m = make_production_mesh()
        assert mesh_axis_sizes(m) == {"data": 16, "model": 16}
        m2 = make_production_mesh(multi_pod=True)
        assert mesh_axis_sizes(m2) == {"pod": 2, "data": 16, "model": 16}
        print("ok")
    """, devices=512)
    assert "ok" in out


@pytest.mark.subproc
def test_sharding_plan_all_archs():
    """Param specs build for every arch on the production mesh; sharded dims
    must divide the mesh axis size."""
    out = _run("""
        import jax
        from jax.sharding import PartitionSpec as P
        from repro.configs.registry import ARCH_IDS, get_config
        from repro.launch.mesh import make_production_mesh
        from repro.launch.sharding import make_plan
        from repro.launch.dryrun import params_shape, stack_worker_axis
        mesh = make_production_mesh(multi_pod=True)
        sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
        for arch in ARCH_IDS:
            cfg = get_config(arch)
            plan = make_plan(mesh, cfg)
            shapes = stack_worker_axis(params_shape(cfg), plan.num_workers)
            specs = plan.param_specs(shapes, with_worker_axis=True)
            flat_sh = jax.tree.leaves(shapes)
            flat_sp = jax.tree.leaves(specs, is_leaf=lambda x: isinstance(x, P))
            assert len(flat_sh) == len(flat_sp)
            for sds, spec in zip(flat_sh, flat_sp):
                for dim, ax in zip(sds.shape, tuple(spec) + (None,) * 8):
                    if ax is None:
                        continue
                    axes = ax if isinstance(ax, tuple) else (ax,)
                    n = 1
                    for a in axes:
                        n *= sizes[a]
                    assert dim % n == 0, (arch, sds.shape, spec)
        print("ok", len(ARCH_IDS))
    """, devices=512)
    assert "ok 10" in out


@pytest.mark.subproc
def test_mesh_collective_equivalence():
    """The production averaging on a real (pod,data) mesh matches the
    paper's dense matrix operators computed on host — proves the sharded
    einsum lowering (psum/all-gather collectives) implements T_k exactly."""
    out = _run("""
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import AxisType, Mesh, NamedSharding, PartitionSpec as P
        from jax.experimental import mesh_utils
        from repro.core.mllsgd import (MLLConfig, apply_schedule, build_network,
                                       build_state)
        from repro.core.simulator import apply_operator

        devs = mesh_utils.create_device_mesh((2, 4), jax.devices()[:8])
        mesh = Mesh(devs, ("pod", "data"), axis_types=(AxisType.Auto,) * 2)
        cfg = MLLConfig(tau=2, q=2, eta=0.1, hub_topology="ring",
                        granularity="worker_per_data")
        net = build_network(cfg, 2, 4)
        st = build_state(cfg, net)
        w = net.num_workers
        x = jax.random.normal(jax.random.PRNGKey(0), (w, 64, 8))
        stacked = {"p": x}
        spec = NamedSharding(mesh, P(("pod", "data"), None, None))
        xs = jax.device_put(stacked, {"p": spec})

        for mixing in ("dense", "two_stage"):
            c = MLLConfig(**{**cfg.__dict__, "mixing": mixing})
            for step, t in ((2, net.v_matrix()), (4, net.z_matrix())):
                f = jax.jit(lambda p, s=step: apply_schedule(
                        p, jnp.asarray(s), c, st),
                    in_shardings=({"p": spec},), out_shardings={"p": spec})
                with mesh:
                    got = f(xs)
                want = apply_operator(stacked, jnp.asarray(t, jnp.float32))
                np.testing.assert_allclose(np.asarray(got["p"]),
                                           np.asarray(want["p"]), atol=1e-5)
        print("ok")
    """, devices=8)
    assert "ok" in out


@pytest.mark.subproc
@pytest.mark.slow
def test_dryrun_one_combo_end_to_end():
    """The smallest production combo lowers + compiles on the 16x16 mesh with
    sane roofline output (the full 40-combo matrix runs via benchmarks)."""
    out = _run("""
        from repro.launch.dryrun import run_one
        r = run_one("xlstm-125m", "train_4k")
        assert r["roofline"]["flops"] > 0
        assert r["hlo_costs"]["collective_bytes"] > 0
        assert r["memory_analysis"], r
        print("ok", r["roofline"]["dominant"])
    """, devices=512)
    assert "ok" in out
