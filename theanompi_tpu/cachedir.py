"""Where the persistent XLA compile cache lives, and the CPU rig's flags.

One rule, shared by every entry point (``chip_smoke.py``, the benches,
``launch.py``'s spawned ranks, ``tests/conftest.py``, the scripts):

- ``JAX_COMPILATION_CACHE_DIR`` set  → the program sets NOTHING in code.
  JAX reads the variable itself, and whoever exported it (a driver, a
  chip command that runs two processes against one cache) owns the
  location and the thresholds.
- unset → ``<checkout>/.jax_cache`` (git-ignored).  The path is part of
  the cache key's neighbourhood — a directory that moves (temp dir, pid,
  time) never hits — so it is fixed relative to this file and therefore
  identical across processes of one checkout.

Deliberately import-light (no jax): conftest calls :func:`cpu_xla_flags`
before jax is imported.
"""

import os

# XLA:CPU's collective rendezvous TERMINATES the process when its
# participants do not all arrive within its default timeout; several
# fake-device JAX processes sharing a few cores (pytest-xdist workers,
# spawned ranks) can starve each other past it.  1200 s survives any
# plausible starvation while a true deadlock still aborts diagnosably.
CPU_RENDEZVOUS_FLAG = (
    "--xla_cpu_collective_call_terminate_timeout_seconds=1200"
)

CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"


def repo_cache_dir() -> str:
    """The fixed in-checkout cache path used when ``CACHE_ENV`` is unset."""
    return os.path.abspath(
        os.path.join(
            os.path.dirname(os.path.abspath(__file__)), os.pardir,
            ".jax_cache",
        )
    )


def configure_compile_cache(jax_mod) -> str:
    """Apply the rule in the module docstring; returns the directory the
    cache will use.  Takes the caller's ``jax`` module so this file
    stays import-light."""
    env = os.environ.get(CACHE_ENV)
    if env:
        return env
    cache = repo_cache_dir()
    jax_mod.config.update("jax_compilation_cache_dir", cache)
    jax_mod.config.update("jax_persistent_cache_min_compile_time_secs", 0.5)
    jax_mod.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return cache


def cpu_xla_flags(existing: str = "", fake_devices=8) -> str:
    """The CPU entry points' shared XLA_FLAGS recipe: the fake-device
    mesh (``fake_devices=None`` to skip) plus the rendezvous guard.
    Idempotent: flags already present are not appended twice."""
    flags = existing or ""
    if fake_devices and "xla_force_host_platform_device_count" not in flags:
        flags = (
            f"{flags} --xla_force_host_platform_device_count={fake_devices}"
        ).strip()
    if "collective_call_terminate_timeout" not in flags:
        flags = f"{flags} {CPU_RENDEZVOUS_FLAG}".strip()
    return flags
