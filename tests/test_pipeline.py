"""Pipeline parallelism: GPipe schedule == sequential reference, forward
and gradients, on a forced multi-device mesh (subprocess)."""

import os
import subprocess
import sys
import textwrap

SNIPPET = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    import jax, jax.numpy as jnp, numpy as np
    from repro.sharding.pipeline import pipeline

    S, M, B, D = 4, 6, 8, 16
    mesh = jax.make_mesh((S,), ("stage",),
                         axis_types=(jax.sharding.AxisType.Auto,))
    key = jax.random.PRNGKey(0)
    ws = jax.random.normal(key, (S, D, D)) * (D ** -0.5)
    bs = jax.random.normal(jax.random.PRNGKey(1), (S, D)) * 0.1
    params = {"w": ws, "b": bs}
    mb = jax.random.normal(jax.random.PRNGKey(2), (M, B, D))

    def stage_fn(p, x):
        return jnp.tanh(x @ p["w"] + p["b"])

    def reference(params, mb):
        def apply_all(x):
            for s in range(S):
                x = stage_fn(jax.tree.map(lambda t: t[s], params), x)
            return x
        return jax.vmap(apply_all)(mb)

    piped = pipeline(stage_fn, mesh, "stage")

    with mesh:
        out_p = jax.jit(piped)(params, mb)
    out_r = reference(params, mb)
    np.testing.assert_allclose(np.asarray(out_p), np.asarray(out_r),
                               rtol=1e-5, atol=1e-5)

    # gradients flow through the ppermute pipeline (backward pipeline)
    def loss_p(params):
        with mesh:
            return (jax.jit(piped)(params, mb) ** 2).mean()

    def loss_r(params):
        return (reference(params, mb) ** 2).mean()

    gp = jax.grad(loss_p)(params)
    gr = jax.grad(loss_r)(params)
    for k in ("w", "b"):
        np.testing.assert_allclose(np.asarray(gp[k]), np.asarray(gr[k]),
                                   rtol=1e-4, atol=1e-5)
    print("PIPELINE_OK")
""")


def test_gpipe_pipeline_matches_reference_subprocess():
    env = dict(os.environ, PYTHONPATH="src")
    out = subprocess.run([sys.executable, "-c", SNIPPET],
                         capture_output=True, text=True, env=env,
                         cwd=os.path.dirname(os.path.dirname(__file__)))
    assert "PIPELINE_OK" in out.stdout, out.stdout + out.stderr[-3000:]
