"""Pipeline parallelism: gpipe over a 2-stage forced-host-device mesh,
validated against sequential stage application (subprocess so the 2-device
XLA flag cannot leak into other tests)."""

import os
import subprocess
import sys
import textwrap

import pytest


def subprocess_env():
    """Scrubbed env for hermetic subprocess lowerings, with the operator's
    jax backend pins passed through: without them the child falls into
    backend autodetection, which can hang for minutes (or grab a device)
    on hosts that pin JAX_PLATFORMS — the seed-failing env assumption."""
    env = {"PYTHONPATH": "src", "PATH": "/usr/bin:/bin"}
    for var in ("JAX_PLATFORMS", "JAX_PLATFORM_NAME", "HOME", "TMPDIR"):
        if var in os.environ:
            env[var] = os.environ[var]
    return env

SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
    import jax, jax.numpy as jnp, numpy as np
    from repro.parallel.pipeline import gpipe, bubble_fraction

    mesh = jax.make_mesh((2,), ("pod",))
    rng = np.random.default_rng(0)
    d = 16
    # two stages, each y = tanh(x @ w_s)
    w = jnp.asarray(rng.normal(size=(2, d, d)) / np.sqrt(d), jnp.float32)
    xs = jnp.asarray(rng.normal(size=(4, 3, d)), jnp.float32)  # 4 micro x 3

    def stage(params, x):
        return jnp.tanh(x @ params)

    out = gpipe(stage, w, xs, mesh=mesh, axis="pod")

    want = xs
    for s in range(2):
        want = jnp.tanh(want @ w[s])
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=1e-5, atol=1e-5)
    assert abs(bubble_fraction(4, 2) - 0.2) < 1e-9
    print("PIPELINE_OK")
""")


@pytest.mark.slow
def test_gpipe_two_stages_subprocess():
    r = subprocess.run([sys.executable, "-c", SCRIPT], capture_output=True,
                       text=True, timeout=300, env=subprocess_env())
    assert "PIPELINE_OK" in r.stdout, (r.stdout[-2000:], r.stderr[-2000:])
