"""Helpers over the installed JAX: `core/compat` mesh helpers and the
compile-cache location. `mesh_context` must set the abstract mesh that
`shard_hint` reads. A bare `with mesh:` does not, and under it every
`shard_hint` was a silent no-op."""

import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp

from repro.core.compat import get_ambient_mesh, make_compat_mesh, mesh_context
from repro.launch.mesh import make_serving_mesh


def test_mesh_context_sets_abstract_mesh():
    mesh = make_serving_mesh(1)
    assert get_ambient_mesh().axis_names == ()
    with mesh_context(mesh):
        assert jax.sharding.get_abstract_mesh().axis_names == ("model",)
        assert get_ambient_mesh().shape["model"] == 1
    assert get_ambient_mesh().axis_names == ()


def test_make_compat_mesh_axes_are_auto():
    mesh = make_compat_mesh((1,), ("model",), devices=jax.devices()[:1])
    assert mesh.axis_names == ("model",)
    assert mesh.axis_types == (jax.sharding.AxisType.Auto,)


SHARD_HINT_SCRIPT = textwrap.dedent("""
    import jax, jax.numpy as jnp
    from jax.sharding import PartitionSpec as P
    from repro.core.compat import mesh_context
    from repro.launch.mesh import make_serving_mesh
    from repro.models.layers import shard_hint

    assert jax.device_count() == 4, jax.device_count()
    mesh = make_serving_mesh(4)
    f = lambda x: shard_hint(x * 2.0, None, "model")
    x = jnp.ones((8, 16), jnp.float32)
    assert "sharding_constraint" not in str(jax.make_jaxpr(f)(x))
    with mesh_context(mesh):
        assert jax.sharding.get_abstract_mesh().axis_names == ("model",)
        assert "sharding_constraint" in str(jax.make_jaxpr(f)(x))
        y = jax.jit(f)(x)
    assert y.sharding.spec == P(None, "model"), y.sharding
    assert len(y.sharding.device_set) == 4
    print("SHARD_HINT_OK")
""")


def test_shard_hint_constrains_under_forced_4_device_mesh(tmp_path):
    """The no-op this fixes showed only with several devices, so the check
    runs in a child process with 4 forced CPU devices."""
    script = tmp_path / "shard_hint.py"
    script.write_text(SHARD_HINT_SCRIPT)
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS=(os.environ.get("XLA_FLAGS", "")
                          + " --xla_force_host_platform_device_count=4"
                          ).strip())
    env["PYTHONPATH"] = os.pathsep.join(
        [os.path.join(os.path.dirname(os.path.dirname(__file__)), "src"),
         env.get("PYTHONPATH", "")])
    r = subprocess.run([sys.executable, str(script)], capture_output=True,
                       text=True, timeout=300, env=env)
    assert "SHARD_HINT_OK" in r.stdout, r.stdout + r.stderr


def test_shard_hint_is_identity_without_mesh():
    from repro.models.layers import shard_hint
    x = jnp.ones((4, 4))
    assert "sharding_constraint" not in str(
        jax.make_jaxpr(lambda a: shard_hint(a, None, "model"))(x))


def test_compile_cache_location(monkeypatch):
    from repro.launch import compile_cache as cc
    keys = ("jax_compilation_cache_dir",
            "jax_persistent_cache_min_compile_time_secs")
    saved = {k: getattr(jax.config, k) for k in keys}
    try:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere/cache")
        assert cc.enable_compile_cache() == "/elsewhere/cache"
        assert jax.config.jax_compilation_cache_dir == \
            saved["jax_compilation_cache_dir"]   # the environment decides
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        path = cc.enable_compile_cache()
        root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        assert path == os.path.join(root, ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == path
    finally:
        for k, v in saved.items():
            jax.config.update(k, v)
