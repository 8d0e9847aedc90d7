"""The repo's one JAX import surface (support policy: the installed jax 0.9.0).

Repo rule: **never import shard_map directly** — always go through this
module, so that a later move or rename of a JAX entry point is absorbed in
one place.

The module also centralises helpers the repo used to re-derive ad hoc: mesh
axis-size lookup, a donation-safe ``jit`` wrapper (buffer donation is a
no-op-with-warning on CPU; the wrapper keeps programs identical across
backends without spamming warnings on host-only test runs), and the
persistent compilation cache location that entry points set.
"""
from __future__ import annotations

import functools
import os
import re
import warnings

import jax
from jax import lax

__all__ = [
    "JAX_VERSION",
    "shard_map",
    "axis_size",
    "mesh_axis_sizes",
    "mesh_axis_size",
    "donate_jit",
    "enable_cpu_collectives",
    "distributed_initialize",
    "process_index",
    "process_count",
    "array_from_process_local_data",
    "profiler_annotation",
    "use_compile_cache",
]


def _version_tuple(v: str) -> tuple:
    return tuple(int(x) for x in re.findall(r"\d+", v)[:3])


JAX_VERSION: tuple = _version_tuple(jax.__version__)


def shard_map(f, *, mesh, in_specs, out_specs, check_vma=None, **kwargs):
    """``jax.shard_map``; ``check_vma=None`` keeps jax's default."""
    if check_vma is not None:
        kwargs["check_vma"] = check_vma
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs, out_specs=out_specs, **kwargs)


def axis_size(axis_name: str):
    """Size of a mapped mesh axis from inside shard_map."""
    return lax.axis_size(axis_name)


def mesh_axis_sizes(mesh) -> dict:
    """{axis_name: size} for a Mesh (works for Mesh and AbstractMesh —
    ``mesh.shape`` exists on both; ``mesh.devices`` does not)."""
    return dict(mesh.shape)


def mesh_axis_size(mesh, axis: str, default: int = 1) -> int:
    """Size of one mesh axis; ``default`` for axes the mesh doesn't have."""
    return mesh_axis_sizes(mesh).get(axis, default)


# --------------------------------------------------------------- distributed
def enable_cpu_collectives(impl: str = "gloo") -> bool:
    """Turn on cross-process collectives for the CPU backend. Returns True
    once set. Must run before the CPU backend initializes — i.e. before the
    first jax.devices()/computation in the process."""
    jax.config.update("jax_cpu_collectives_implementation", impl)
    return True


def distributed_initialize(coordinator_address: str, num_processes: int, process_id: int) -> None:
    """``jax.distributed.initialize`` for an explicitly-specified process
    group (the repo never relies on cluster auto-detection, which varies by
    jax version and scheduler). On CPU backends the collectives implementation
    is enabled first — without it multi-process CPU meshes initialize but every
    cross-process transfer fails at run time."""
    enable_cpu_collectives()
    jax.distributed.initialize(
        coordinator_address=coordinator_address,
        num_processes=int(num_processes),
        process_id=int(process_id),
    )


def process_index() -> int:
    return int(jax.process_index())


def process_count() -> int:
    return int(jax.process_count())


def array_from_process_local_data(sharding, local_data, global_shape):
    """``jax.make_array_from_process_local_data``."""
    return jax.make_array_from_process_local_data(sharding, local_data, global_shape)


def profiler_annotation(name: str):
    """A ``jax.profiler.TraceAnnotation`` for ``name`` — makes host spans
    (obs/trace.py) visible inside a jax profiler capture so device program
    time can be correlated with them."""
    return jax.profiler.TraceAnnotation(name)


def use_compile_cache(checkout: str) -> str:
    """Point JAX's persistent compilation cache at ``<checkout>/.jax_cache``,
    unless ``JAX_COMPILATION_CACHE_DIR`` is set, in which case JAX's own
    reading of that variable stands. Returns the directory in use.

    For entry points only (``chip_smoke.py``, ``benchmarks/run.py``): the
    path is part of every cache key, so it is fixed inside the checkout, and
    importing the package never turns the cache on (tests stay uncached)."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    path = os.path.join(os.path.abspath(checkout), ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path


def donate_jit(fn=None, *, donate_argnums=(), **jit_kwargs):
    """``jax.jit`` with buffer donation that stays quiet on backends where
    donation is unimplemented (CPU): the XLA "buffers were not usable"
    warning is suppressed at call time, everything else passes through."""
    if fn is None:
        return functools.partial(donate_jit, donate_argnums=donate_argnums, **jit_kwargs)
    jitted = jax.jit(fn, donate_argnums=donate_argnums, **jit_kwargs)

    @functools.wraps(fn)
    def call(*args, **kwargs):
        with warnings.catch_warnings():
            warnings.filterwarnings(
                "ignore", message=".*[Dd]onat.*", category=UserWarning
            )
            return jitted(*args, **kwargs)

    call.lower = jitted.lower  # keep AOT inspection available
    return call
