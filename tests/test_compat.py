"""compat layer: shard_map / axis_size / mesh helpers / donate_jit / cache.

The repo rule is "never import shard_map directly" — these tests pin the
behaviours the rest of the codebase relies on, on whatever jax is installed.
"""
import os
import pathlib
import subprocess
import sys
import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax
from jax.sharding import PartitionSpec as P

from repro import compat
from repro.launch import mesh as MM


def test_no_direct_shard_map_imports_outside_compat():
    import re

    # Catches every spelling: "from jax import lax, shard_map" (the seed
    # repo's exact bug), "from jax.experimental import shard_map",
    # "from jax.experimental.shard_map import ...", "jax.shard_map(...)".
    direct = re.compile(
        r"from\s+jax(\.[\w.]+)?\s+import\s+[^\n]*\bshard_map\b"
        r"|\bjax(\.\w+)*\.shard_map\b"
    )
    root = pathlib.Path(compat.__file__).parent
    offenders = []
    for path in root.rglob("*.py"):
        if path.name == "compat.py":
            continue
        if direct.search(path.read_text()):
            offenders.append(str(path))
    assert not offenders, f"import shard_map via repro.compat, not directly: {offenders}"


def test_jax_version_tuple():
    assert compat.JAX_VERSION[:2] == (0, 9), "support policy: the installed jax 0.9.0"


def test_shard_map_runs_with_check_vma_kwarg():
    mesh = MM.make_test_mesh(data=1, model=1)

    def local(x):
        return lax.psum(x, "data")

    fn = compat.shard_map(local, mesh=mesh, in_specs=P("data"), out_specs=P(), check_vma=False)
    x = jnp.arange(4, dtype=jnp.float32)
    np.testing.assert_allclose(np.asarray(fn(x)), np.asarray(x))


def test_axis_size_inside_shard_map():
    mesh = MM.make_test_mesh(data=1, model=1)

    def local(x):
        return x * compat.axis_size("data")

    fn = compat.shard_map(local, mesh=mesh, in_specs=P(), out_specs=P(), check_vma=False)
    assert int(fn(jnp.asarray(3))) == 3  # axis size is 1 on the test mesh


def test_mesh_axis_helpers():
    mesh = MM.make_test_mesh(data=1, model=1)
    assert compat.mesh_axis_sizes(mesh) == {"data": 1, "model": 1}
    assert compat.mesh_axis_size(mesh, "model") == 1
    assert compat.mesh_axis_size(mesh, "nonexistent") == 1
    assert compat.mesh_axis_size(mesh, "nonexistent", default=7) == 7


def test_donate_jit_matches_jit_and_stays_quiet():
    def f(x, y):
        return x + y

    x = jnp.arange(8, dtype=jnp.float32)
    y = jnp.ones(8, dtype=jnp.float32)
    fn = compat.donate_jit(f, donate_argnums=(0,))
    with warnings.catch_warnings():
        warnings.simplefilter("error")  # any donation warning would fail here
        got = fn(x, y)
    np.testing.assert_allclose(np.asarray(got), np.arange(8) + 1.0)


def test_donate_jit_decorator_form():
    @compat.donate_jit(donate_argnums=(0,))
    def g(x):
        return 2 * x

    assert int(g(jnp.asarray(21))) == 42


# --------------------------------------------------------------- distributed
def test_process_helpers_single_process():
    """Outside a jax.distributed group the process helpers report the
    1-process degenerate case every multi-host code path must handle."""
    assert compat.process_count() == 1
    assert compat.process_index() == 0


def test_enable_cpu_collectives_finds_a_knob():
    """Supported jax versions all have one spelling of the CPU-collectives
    knob; idempotent (initialize_from_env may race a user's own call)."""
    assert compat.enable_cpu_collectives() is True
    assert compat.enable_cpu_collectives() is True  # idempotent


def test_force_host_device_flags_builds_explicitly():
    from repro.launch.multihost import force_host_device_flags

    assert force_host_device_flags(8) == "--xla_force_host_platform_device_count=8"
    # Replaces an existing count instead of string-patching it — the exact
    # failure mode of .replace("8", "512") on a flag whose digits collide.
    got = force_host_device_flags(
        512, "--xla_dump_to=/tmp/d --xla_force_host_platform_device_count=8"
    )
    assert got == "--xla_dump_to=/tmp/d --xla_force_host_platform_device_count=512"
    assert force_host_device_flags(4, got).count("device_count") == 1


def test_put_global_and_local_shard_rows_degenerate_single_process():
    """put_global / local_shard_rows on a 1-device mesh: the degenerate case
    of the multi-host path (DESIGN.md §10) — same layout as device_put."""
    from jax.sharding import NamedSharding

    from repro.launch import multihost as MH

    mesh = MM.make_graph_mesh(1)
    arr = np.arange(12, dtype=np.int32).reshape(6, 2)
    committed = MH.put_global(arr, NamedSharding(mesh, P("graph", None)))
    np.testing.assert_array_equal(np.asarray(committed), arr)
    blocks = MH.local_shard_rows(committed)
    assert [(lo, hi) for lo, hi, _ in blocks] == [(0, 6)]
    np.testing.assert_array_equal(blocks[0][2], arr)
    np.testing.assert_array_equal(MH.host_read(committed), arr)


# ------------------------------------------------------------ compile cache
_CACHE_PROBE = """
import sys
import jax, jax.numpy as jnp
from repro import compat
print(compat.use_compile_cache(sys.argv[1]))
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
jax.jit(lambda x: x * 2 + 1)(jnp.arange(4)).block_until_ready()
"""


@pytest.mark.parametrize("env_set", [False, True])
def test_compile_cache_lands_in_checkout_or_env_dir(tmp_path, env_set):
    """Without JAX_COMPILATION_CACHE_DIR the entry points cache in
    <checkout>/.jax_cache; with it, in that directory and nowhere else."""
    checkout = tmp_path / "checkout"
    checkout.mkdir()
    env = {k: v for k, v in os.environ.items() if k != "JAX_COMPILATION_CACHE_DIR"}
    src = str(pathlib.Path(compat.__file__).parents[1])
    env.update(JAX_PLATFORMS="cpu", PYTHONPATH=src)
    want = checkout / ".jax_cache"
    if env_set:
        want = tmp_path / "env_cache"
        env["JAX_COMPILATION_CACHE_DIR"] = str(want)
    r = subprocess.run(
        [sys.executable, "-c", _CACHE_PROBE, str(checkout)],
        capture_output=True, text=True, env=env, cwd=tmp_path, timeout=120,
    )
    assert r.returncode == 0, r.stderr[-2000:]
    assert r.stdout.strip().splitlines()[-1] == str(want)
    written = sorted(p.parent for p in tmp_path.rglob("*-cache"))
    assert written and set(written) == {want}
