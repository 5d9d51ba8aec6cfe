"""Test harness setup.

Tests run on the CPU platform with 8 virtual devices so multi-chip sharding
paths compile and execute without TPU hardware (the driver separately
dry-runs them via __graft_entry__.dryrun_multichip). Must run before jax
imports anywhere.
"""

import os

os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = flags + " --xla_force_host_platform_device_count=8"

# One persistent compilation cache for the whole suite and for every
# `python -m shadow1_tpu` child a test spawns (they inherit os.environ):
# the TCP round body is a large program, and sharing compiles is what keeps
# tier-1 inside its wall budget. Same rule as shadow1_tpu/__init__.py — an
# outer JAX_COMPILATION_CACHE_DIR wins, else the fixed in-checkout path.
os.environ.setdefault(
    "JAX_COMPILATION_CACHE_DIR",
    os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                 ".jax_cache"))
os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS", "1.0")

# Package import path comes from pyproject.toml [tool.pytest.ini_options]
# pythonpath — no sys.path surgery here.
import shadow1_tpu  # noqa: E402,F401  (enables x64 before any jax array exists)
import jax  # noqa: E402

# Belt and braces: the env var above only counts if nothing imported jax
# before this file ran (a pytest plugin may have); the config route holds
# either way.
jax.config.update("jax_platforms", "cpu")
