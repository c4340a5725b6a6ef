"""Test harness: force CPU with 8 virtual devices BEFORE jax backends init.

Sharded/pjit code paths are exercised deterministically on an 8-device CPU
mesh (SURVEY.md §5 item 5) — no pod required. The chip is reached only by
`chip_smoke.py`; `tests/test_tpu_compile.py` compiles for a described one.

`JAX_PLATFORMS=cpu` in the environment is enough (nothing preloads jax);
the `jax.config.update` below also covers a caller who forgot it.
XLA_FLAGS is read at (lazy) backend-init time, so setting it here works.
"""

import os

os.environ["JAX_PLATFORMS"] = "cpu"
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    _flags = (_flags + " --xla_force_host_platform_device_count=8").strip()
if "xla_cpu_max_isa" not in _flags:
    # Pin the CPU codegen ISA: XLA's per-process feature detection is not
    # stable on this box (AMX flags appear in some processes only), and
    # the persistent compile cache would otherwise load AOT executables
    # whose compile-time features the loading process doesn't report —
    # the loader warns about possible SIGILL. A fixed baseline makes
    # cache entries portable across processes.
    _flags = (_flags + " --xla_cpu_max_isa=AVX512").strip()
os.environ["XLA_FLAGS"] = _flags

import jax

jax.config.update("jax_platforms", "cpu")

# Persistent compilation cache across test PROCESSES: the suite's wall
# time is compile-dominated, and reruns recompile identical programs
# (a heavy compile replays in ~0.2 s vs 2.3 s). Keyed by jax/XLA version
# internally, so upgrades invalidate cleanly; delete the dir to force
# cold compiles. The directory follows the repo-wide rule
# (utils/compile_cache.py): $JAX_COMPILATION_CACHE_DIR, else
# <checkout>/.jax_cache.
from torched_impala_tpu.utils.compile_cache import configure_compile_cache

configure_compile_cache()
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.5)
jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)

assert jax.default_backend() == "cpu", (
    "tests require the CPU backend; jax backends were initialized before "
    "conftest could override the platform"
)
