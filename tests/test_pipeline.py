"""Pipeline-parallel engine correctness (subprocess multi-device)."""
import textwrap


def test_gpipe_matches_sequential(multidevice):
    code = textwrap.dedent("""
        import jax, jax.numpy as jnp, numpy as np, functools
        import warnings; warnings.filterwarnings("ignore")
        from jax.sharding import PartitionSpec as P
        from jax.experimental.shard_map import shard_map
        from repro.launch.pipeline import gpipe

        S, LPS, D, M, MB = 4, 2, 16, 8, 4  # stages, layers/stage, width
        L = S * LPS
        ks = jax.random.split(jax.random.key(0), 2)
        W = jax.random.normal(ks[0], (L, D, D)) * (1.0 / D ** 0.5)
        x = jax.random.normal(ks[1], (M, MB, D))

        def layer(w, h):
            return jax.nn.relu(h @ w)

        def stage_fn(ws, h):  # ws (LPS, D, D)
            def body(h, w):
                return layer(w, h), None
            h, _ = jax.lax.scan(body, h, ws)
            return h

        # sequential reference
        def seq(W, x):
            def body(h, w):
                return layer(w, h), None
            h, _ = jax.lax.scan(body, x.reshape(M * MB, D),
                                W)
            return h.reshape(M, MB, D)

        ref = seq(W, x)

        mesh = jax.make_mesh((S,), ("pipe",))
        Wr = W.reshape(S, LPS, D, D)

        def per_rank(Wl, xs):
            return gpipe(stage_fn, Wl[0], xs, n_stages=S)

        f = shard_map(per_rank, mesh=mesh,
                      in_specs=(P("pipe"), P()), out_specs=P())
        out = jax.jit(f)(Wr, x)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=2e-5, atol=2e-5)

        # gradients flow through the schedule (ppermute transpose)
        def loss_pp(Wr, x):
            return jnp.sum(f(Wr, x) ** 2)
        def loss_seq(W, x):
            return jnp.sum(seq(W, x) ** 2)
        g_pp = jax.jit(jax.grad(loss_pp))(Wr, x).reshape(L, D, D)
        g_seq = jax.jit(jax.grad(loss_seq))(W, x)
        np.testing.assert_allclose(np.asarray(g_pp), np.asarray(g_seq),
                                   rtol=2e-4, atol=2e-4)
        print("OK")
    """)
    assert "OK" in multidevice(code, n_devices=4)


def test_bubble_fraction():
    from repro.launch.pipeline import bubble_fraction
    assert bubble_fraction(4, 8) == 3 / 11
    assert bubble_fraction(1, 8) == 0.0
    # more microbatches -> smaller bubble
    assert bubble_fraction(4, 64) < bubble_fraction(4, 8)
