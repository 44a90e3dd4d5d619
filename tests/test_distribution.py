"""Distribution correctness on real (forced-host) multi-device meshes.

Tests run in subprocesses so the main pytest process keeps exactly one
visible device (conftest.run_multidevice)."""
import textwrap

import pytest

GRAD_SNIPPET = textwrap.dedent("""
    import jax, jax.numpy as jnp, numpy as np, dataclasses
    from jax.sharding import PartitionSpec as P
    from jax.experimental.shard_map import shard_map
    import warnings; warnings.filterwarnings("ignore")
    from repro.configs import ARCHS, smoke_config
    from repro.models import build_model, MeshInfo
    from repro.launch.mesh import mesh_info

    cfg = dataclasses.replace(smoke_config(ARCHS[{arch!r}]),
                              dtype="float32", **{extra})
    B, S = 4, 32
    batch = {{"tokens": jax.random.randint(jax.random.key(1), (B,S), 0,
                                           cfg.vocab, jnp.int32),
              "labels": jax.random.randint(jax.random.key(2), (B,S), 0,
                                           cfg.vocab, jnp.int32)}}
    m1 = build_model(dataclasses.replace(cfg, fsdp=False),
                     MeshInfo(model_size=1, data_size=1))
    params = m1.init(jax.random.key(0))
    g1 = jax.jit(jax.grad(lambda p: m1.loss(p, batch)[0]))(params)

    mesh = jax.make_mesh({mesh_shape}, ("data", "model"))
    m4 = build_model(cfg, mesh_info(mesh))
    def per_rank(p, b):
        loss, met = m4.loss(p, b)
        n = met["tokens"].astype(jnp.float32)
        return jax.lax.psum(loss*n, "data") / jax.lax.psum(n, "data")
    f = shard_map(per_rank, mesh=mesh,
                  in_specs=(m4.full_param_specs(),
                            {{k: P("data", None) for k in batch}}),
                  out_specs=P(), check_rep={check_rep})
    g4 = jax.jit(jax.grad(f))(params, batch)
    def cmp(t1, t2, path=""):
        if isinstance(t1, dict):
            for k in t1: cmp(t1[k], t2[k], path+"/"+k)
        else:
            a, b = np.asarray(t1, np.float32), np.asarray(t2, np.float32)
            err = np.max(np.abs(a-b)) / (np.max(np.abs(a)) + 1e-9)
            assert err < 2e-3, (path, float(err))
    cmp(g1, g4)
    print("OK")
""")


@pytest.mark.parametrize("arch,mesh_shape", [
    ("granite-8b", (1, 4)),
    ("granite-8b", (2, 2)),
    ("qwen2-0.5b", (2, 2)),
    ("mamba2-1.3b", (2, 2)),
    ("zamba2-2.7b", (2, 2)),
    ("whisper-base", (1, 4)),
    ("paligemma-3b", (2, 2)),
])
def test_tp_grads_match_single_device(multidevice, arch, mesh_shape):
    if arch in ("whisper-base", "paligemma-3b"):
        # these need modality inputs; token-only snippet covers them via
        # family defaults? no -> skip modality extras by using tokens-only
        # families here and modality archs in the smoke tests.
        pytest.skip("modality archs covered by single-device smoke tests")
    code = GRAD_SNIPPET.format(arch=arch, mesh_shape=mesh_shape,
                               check_rep=False, extra={})
    assert "OK" in multidevice(code, n_devices=4)


@pytest.mark.parametrize("arch", ["olmoe-1b-7b", "arctic-480b"])
def test_moe_tp_exact_when_capacity_matches(multidevice, arch):
    """TP=4, DP=1 -> identical capacity to single device -> exact grads."""
    code = GRAD_SNIPPET.format(arch=arch, mesh_shape=(1, 4),
                               check_rep=False, extra={})
    assert "OK" in multidevice(code, n_devices=4)


def test_fsdp_grads_match(multidevice):
    code = GRAD_SNIPPET.format(
        arch="granite-8b", mesh_shape=(2, 2), check_rep=False,
        extra=dict(fsdp=True, fsdp_min_elems=1))
    assert "OK" in multidevice(code, n_devices=4)


def test_check_rep_true_grads_match(multidevice):
    code = GRAD_SNIPPET.format(arch="granite-8b", mesh_shape=(2, 2),
                               check_rep=True, extra={})
    assert "OK" in multidevice(code, n_devices=4)


def test_train_step_program_runs(multidevice):
    code = textwrap.dedent("""
        import jax, jax.numpy as jnp, numpy as np
        import warnings; warnings.filterwarnings("ignore")
        from repro.configs import ARCHS, smoke_config
        from repro.launch.mesh import make_host_mesh
        from repro.launch import steps
        from repro.optim import AdamW
        cfg = smoke_config(ARCHS["qwen1.5-0.5b"])
        mesh = make_host_mesh(2, 2)
        prog = steps.make_train_step(cfg, mesh, AdamW(lr=1e-3),
                                     global_batch=4)
        f = prog.jit()
        params = prog.model.init(jax.random.key(0))
        opt = AdamW(lr=1e-3).init(params)
        B, S = 4, 32
        batch = {"tokens": jnp.ones((B,S), jnp.int32),
                 "labels": jnp.ones((B,S), jnp.int32)}
        l0 = None
        extra = {}
        for i in range(5):
            params, opt, m, extra = f(params, opt, batch, extra)
            if l0 is None: l0 = float(m["loss"])
        assert float(m["loss"]) < l0, (l0, float(m["loss"]))
        print("OK")
    """)
    assert "OK" in multidevice(code, n_devices=4)


def test_manual_comm_matches_auto(multidevice):
    """manual-SPMD gradient path (psums written by hand) must produce the
    same training trajectory as autodiff-through-shard_map."""
    code = textwrap.dedent("""
        import jax, jax.numpy as jnp, numpy as np, dataclasses
        import warnings; warnings.filterwarnings("ignore")
        from repro.configs import ARCHS, smoke_config
        from repro.launch.mesh import make_host_mesh
        from repro.launch import steps
        from repro.optim import AdamW
        cfg = dataclasses.replace(smoke_config(ARCHS["qwen1.5-0.5b"]),
                                  dtype="float32")
        mesh = make_host_mesh(2, 2)
        B, S = 4, 32
        batch = {"tokens": jax.random.randint(jax.random.key(1), (B,S), 0,
                                              cfg.vocab, jnp.int32),
                 "labels": jax.random.randint(jax.random.key(2), (B,S), 0,
                                              cfg.vocab, jnp.int32)}
        outs = {}
        for manual in (False, True):
            prog = steps.make_train_step(cfg, mesh, AdamW(lr=1e-3),
                                         global_batch=4,
                                         manual_comm=manual)
            f = prog.jit()
            params = prog.model.init(jax.random.key(0))
            opt = AdamW(lr=1e-3).init(params)
            extra = ({"err": jax.tree.map(
                lambda p: jnp.zeros(p.shape, jnp.float32), params)}
                if manual else {})
            for i in range(3):
                params, opt, m, extra = f(params, opt, batch, extra)
            outs[manual] = float(m["loss"])
        assert abs(outs[False] - outs[True]) < 1e-3, outs
        print("OK")
    """)
    assert "OK" in multidevice(code, n_devices=4)


def test_elastic_reshard_checkpoint(multidevice, tmp_path):
    """Save on a (2,2) mesh, restore onto (4,1) and (1,4): the logical
    state must be identical (elastic rescaling)."""
    code = textwrap.dedent(f"""
        import jax, jax.numpy as jnp, numpy as np
        import warnings; warnings.filterwarnings("ignore")
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.configs import ARCHS, smoke_config
        from repro.models import build_model
        from repro.launch.mesh import make_host_mesh, mesh_info
        from repro.ckpt.checkpoint import Checkpointer
        cfg = smoke_config(ARCHS["granite-8b"])
        mesh_a = make_host_mesh(2, 2)
        m_a = build_model(cfg, mesh_info(mesh_a))
        params = m_a.init(jax.random.key(0))
        ck = Checkpointer({str(tmp_path)!r})
        ck.save(1, params, blocking=True)

        for shape in ((4, 1), (1, 4)):
            mesh_b = make_host_mesh(*shape)
            m_b = build_model(cfg, mesh_info(mesh_b))
            specs = m_b.full_param_specs()
            sh = jax.tree.map(lambda s: NamedSharding(mesh_b, s), specs,
                              is_leaf=lambda x: isinstance(x, P))
            like = jax.eval_shape(lambda: m_b.init(jax.random.key(0)))
            out, _ = ck.load(like, shardings=sh)
            for (ka, va), (kb, vb) in zip(
                    sorted(jax.tree.leaves_with_path(params),
                           key=lambda t: str(t[0])),
                    sorted(jax.tree.leaves_with_path(out),
                           key=lambda t: str(t[0]))):
                np.testing.assert_array_equal(
                    np.asarray(va, np.float32), np.asarray(vb, np.float32))
        print("OK")
    """)
    assert "OK" in multidevice(code, n_devices=4)


def test_lp_solver_sharded_over_mesh(multidevice):
    """The paper's workload on a mesh: batch-sharded LP solve must match
    the single-device solution."""
    code = textwrap.dedent("""
        import jax, jax.numpy as jnp, numpy as np
        import warnings; warnings.filterwarnings("ignore")
        from repro.core import random_feasible_lp, \\
            normalize_batch, shuffle_batch
        from repro.solver import SolverSpec, get_solver
        from repro.launch.mesh import make_host_mesh
        from repro.launch import steps
        lp = shuffle_batch(jax.random.key(5), normalize_batch(
            random_feasible_lp(jax.random.key(0), 64, 24)))
        ref = get_solver(SolverSpec(backend="rgb", tile=32, chunk=0,
                                    normalize=False)).solve(lp)
        mesh = make_host_mesh(2, 2)
        prog = steps.make_lp_step(mesh, batch=64, m=24)
        out = prog.jit()({"A": lp.A, "b": lp.b, "c": lp.c,
                          "m_valid": lp.m_valid})
        np.testing.assert_allclose(np.asarray(ref.x), np.asarray(out["x"]),
                                   rtol=1e-5, atol=1e-5)
        print("OK")
    """)
    assert "OK" in multidevice(code, n_devices=4)
