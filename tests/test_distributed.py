"""Distributed tests: run in a subprocess with 8 forced host devices.

Per the launch contract, only the dry-run (and these subprocesses) force a
device count -- the main pytest process must keep seeing one device, so each
test spawns ``python -c`` with XLA_FLAGS set in its environment.
"""

import os
import subprocess
import sys
import textwrap

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(code: str, n_devices: int = 8) -> str:
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={n_devices}"
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    out = subprocess.run(
        [sys.executable, "-c", textwrap.dedent(code)],
        capture_output=True, text=True, env=env, timeout=540,
    )
    assert out.returncode == 0, f"STDOUT:\n{out.stdout}\nSTDERR:\n{out.stderr}"
    return out.stdout


def test_dist_engine_equivalence_both_schedules():
    """Distributed engines (2x4 mesh) == single-host reference, bitwise."""
    print(_run("""
        import numpy as np, jax
        from repro.core.areas import mam_benchmark_spec
        from repro.core.connectivity import build_network
        from repro.core.engine import EngineConfig
        from repro.core.factory import make_simulation

        spec = mam_benchmark_spec(n_areas=4, n_per_area=32, k_intra=4, k_inter=4)
        net = build_network(spec, seed=12, size_multiple=8)
        mesh = jax.make_mesh((4, 2), ("data", "model"))
        for model in ("ignore_and_fire", "lif"):
            ref = make_simulation(spec, EngineConfig(neuron_model=model,
                                                      schedule="conventional"), net=net)
            for sched in ("structure_aware", "conventional"):
                eng = make_simulation(spec, EngineConfig(neuron_model=model,
                                                    schedule=sched), net=net, mesh=mesh)
                st, s0 = eng.init(), ref.init()
                for w in range(8):
                    s0, blk_ref = ref.window(s0)
                    st, blk = eng.window(st)
                    assert np.array_equal(np.asarray(blk).astype(bool),
                                          np.asarray(blk_ref)), (model, sched, w)
        print("OK")
    """))


def test_dist_engine_delivery_backend_equivalence():
    """Tentpole: every delivery backend, run through the shard_map window
    bodies (2x4 mesh), reproduces the single-host reference bitwise -- under
    both the fused D-cycle superstep (default: blocked ring access +
    single-pass blocked receive of the lumped exchange) and the legacy
    per-cycle window. The event backend exchanges sparse id packets instead
    of dense vectors and must report zero overflow."""
    print(_run("""
        import numpy as np, jax
        from repro.core.areas import mam_benchmark_spec
        from repro.core.connectivity import build_network
        from repro.core.engine import EngineConfig
        from repro.core.factory import make_simulation

        spec = mam_benchmark_spec(n_areas=4, n_per_area=32, k_intra=4,
                                  k_inter=4, rate_hz=30.0)
        net = build_network(spec, seed=12, size_multiple=8, outgoing=True)
        mesh = jax.make_mesh((4, 2), ("data", "model"))
        ref = make_simulation(spec, EngineConfig(
            neuron_model="ignore_and_fire", schedule="conventional"), net=net)
        s0 = ref.init()
        blocks = []
        for _ in range(6):
            s0, b = ref.window(s0)
            blocks.append(np.asarray(b))
        assert sum(b.sum() for b in blocks) > 0
        cases = [(b, sched, None) for b in ("scatter", "pallas", "event")
                 for sched in ("structure_aware", "conventional")]
        # The legacy (superstep=False) windows must stay equivalent too.
        cases += [("event", "structure_aware", False),
                  ("scatter", "structure_aware", False)]
        for backend, sched, superstep in cases:
            eng = make_simulation(spec, EngineConfig(
                                       neuron_model="ignore_and_fire",
                                       schedule=sched,
                                       delivery_backend=backend,
                                       s_max_floor=32,
                                       superstep=superstep), net=net, mesh=mesh)
            st = eng.init()
            for w in range(6):
                st, blk = eng.window(st)
                assert np.array_equal(np.asarray(blk).astype(bool),
                                      blocks[w]), (backend, sched, w)
            assert int(st.overflow) == 0, (backend, sched)
        print("OK")
    """))


def test_dist_engine_multi_pod_mesh():
    """The 3-axis (pod, data, model) mesh also reproduces the reference."""
    print(_run("""
        import numpy as np, jax
        from repro.core.areas import mam_benchmark_spec
        from repro.core.connectivity import build_network
        from repro.core.engine import EngineConfig
        from repro.core.factory import make_simulation

        spec = mam_benchmark_spec(n_areas=4, n_per_area=32, k_intra=4, k_inter=4)
        net = build_network(spec, seed=654, size_multiple=8)
        mesh = jax.make_mesh((2, 2, 2), ("pod", "data", "model"))
        ref = make_simulation(spec, EngineConfig(schedule="conventional",
                                                  neuron_model="lif"), net=net)
        eng = make_simulation(spec, EngineConfig(schedule="structure_aware",
                                            neuron_model="lif"), net=net, mesh=mesh)
        st, s0 = eng.init(), ref.init()
        for w in range(6):
            s0, blk_ref = ref.window(s0)
            st, blk = eng.window(st)
            assert np.array_equal(np.asarray(blk).astype(bool),
                                  np.asarray(blk_ref)), w
        print("OK")
    """))


def test_hierarchical_trainer_local_steps_and_sync():
    """Per-pod local steps diverge; the D-step sync re-converges replicas.
    With int8+EF compression the sync stays within quantisation error."""
    print(_run("""
        import numpy as np, jax, jax.numpy as jnp
        from repro.configs.registry import get_arch
        from repro.optim.adamw import AdamWConfig, adamw_init
        from repro.optim.hierarchical import Hierarchical, HierarchicalConfig
        from repro.train.steps import make_train_artifacts
        from repro.configs.common import ShapeSpec

        mesh = jax.make_mesh((2, 2, 2), ("pod", "data", "model"))
        bundle = get_arch("qwen2-0.5b", reduced=True)
        art = make_train_artifacts(
            bundle, mesh=mesh, batch_axes=("data",), fsdp_axis=None,
            hier_cfg=HierarchicalConfig(sync_every=4, compression="int8"),
        )
        hier = art.hier
        params = bundle.model.init_params(jax.random.PRNGKey(0))
        pparams = hier.replicate(params)
        popt = hier.replicate(adamw_init(params, AdamWConfig()))
        sync_state = hier.init_sync_state(params)

        rng = np.random.default_rng(0)
        def batch(step):
            toks = rng.integers(0, 64, (2, 8, 16))  # [pods, B/pod, S]
            return {"tokens": jnp.asarray(toks, jnp.int32),
                    "labels": jnp.asarray(toks, jnp.int32)}

        for step in range(4):
            pparams, popt, metrics = art.step_fn(pparams, popt, batch(step))
        # replicas must now differ (different pod data)
        leaf = jax.tree.leaves(pparams)[1]
        assert float(jnp.abs(leaf[0] - leaf[1]).max()) > 0
        pparams, sync_state = art.sync_fn(pparams, sync_state)
        for x in jax.tree.leaves(pparams):
            assert np.allclose(np.asarray(x[0]), np.asarray(x[1])), "not synced"
        print("losses:", [float(v) for v in np.atleast_1d(metrics["loss"])])
        print("OK")
    """))


def test_host_batch_sharding():
    print(_run("""
        import numpy as np, jax
        from repro.data.pipeline import SyntheticLM, host_batch

        mesh = jax.make_mesh((2, 2, 2), ("pod", "data", "model"))
        ds = SyntheticLM(vocab=64, seq_len=16, global_batch=8)
        b = ds.batch(0)
        sharded = host_batch(b, mesh, batch_axes=("data",), pod_axis="pod")
        assert sharded["tokens"].shape == (2, 4, 16)
        flat = np.asarray(sharded["tokens"]).reshape(8, 16)
        assert np.array_equal(flat, b["tokens"]), "sharding must not reorder"
        print("OK")
    """))


def test_moe_expert_parallel_lowering():
    """EP dispatch lowers with experts sharded over 'model' (all-to-alls)."""
    print(_run("""
        import jax, jax.numpy as jnp
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.models.moe import MoEConfig, moe_apply, moe_init, moe_pspecs

        mesh = jax.make_mesh((2, 4), ("data", "model"))
        cfg = MoEConfig(n_experts=8, top_k=1, d_ff=32, expert_sharding="ep")
        p = moe_init(jax.random.PRNGKey(0), 16, cfg)
        specs = moe_pspecs(cfg, fsdp="data", tp="model")
        p = jax.tree.map(
            lambda x, s: jax.device_put(x, NamedSharding(mesh, s)), p, specs,
            is_leaf=lambda x: isinstance(x, (jax.Array, P)))
        x = jax.device_put(
            jax.random.normal(jax.random.PRNGKey(1), (4, 16, 16)),
            NamedSharding(mesh, P("data", None, None)))
        with jax.set_mesh(mesh):
            y, aux = jax.jit(lambda p, x: moe_apply(p, x, cfg))(p, x)
        assert y.shape == x.shape
        print("OK")
    """))


def test_pipeline_parallel_matches_sequential():
    """GPipe wrapper == sequential stage application (4-stage pipe)."""
    print(_run("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.train.pipeline import pipeline_apply

        mesh = jax.make_mesh((4,), ("pipe",))
        S, M, mb, d = 4, 6, 2, 8
        key = jax.random.PRNGKey(0)
        w = jax.random.normal(key, (S, d, d)) * 0.3
        params = {"w": w}

        def stage(p, x):
            return jnp.tanh(x @ p["w"])

        x = jax.random.normal(jax.random.PRNGKey(1), (M, mb, d))
        with jax.set_mesh(mesh):
            got = pipeline_apply(stage, params, x, mesh)
        # sequential reference
        ref = x
        for s in range(S):
            ref = jnp.tanh(ref @ w[s])
        assert np.allclose(np.asarray(got), np.asarray(ref), atol=1e-5), \
            float(jnp.abs(got - ref).max())
        print("OK")
    """, n_devices=4))
