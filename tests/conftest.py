"""Test configuration.

Per SURVEY.md §4(d)'s rebuild test plan, CI needs no TPU: the JAX test
suite runs on the CPU backend with 8 fake devices so multi-chip sharding
logic (or-reduce, shard_map meshes) is exercised the same way
``__graft_entry__.dryrun_multichip`` validates it. These env vars MUST be
set before jax imports.
"""

import os

_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8"
    ).strip()

import jax  # noqa: E402  (after XLA_FLAGS above, by design)
import pytest  # noqa: E402

from tpuminter.xla_cache import enable_compilation_cache  # noqa: E402

# Tests run on the fake-8-device CPU mesh even where a chip is attached.
jax.config.update("jax_platforms", "cpu")

# The unrolled SHA-256 graphs are trace-heavy; cache compiled executables
# across test runs so only the first run pays the compile bill.
enable_compilation_cache()


@pytest.fixture(scope="module")
def v5e_2x2():
    """A TPU v5e 2x2 topology described, not attached: the chip's
    compiler lowers and compiles for its devices here, and nothing runs.
    Described inside a fixture, never at import: only one process at a
    time may load libtpu, and an import-time call would give the xdist
    workers different tests to collect. Skips where libtpu cannot
    describe one."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc

    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")
        try:
            topo = topologies.get_topology_desc(
                platform="tpu", topology_name="v5e:2x2"
            )
        except Exception as e:
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without the chip; keep it out, and keep
    # these non-interpret traces out of the in-memory caches other
    # tests in this worker read
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    jax.clear_caches()
    yield topo
    jax.clear_caches()
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


# Property tests: this box has a single CPU core (BASELINE.md), so a
# scheduling hiccup under load can blow hypothesis's default 200 ms
# per-example deadline on tests that are microseconds-fast when quiet.
# Deadlines guard against slow *examples*, not slow *hosts* — disable.
try:
    from hypothesis import settings

    settings.register_profile("tpuminter", deadline=None)
    settings.load_profile("tpuminter")
except ImportError:  # hypothesis is an optional test extra
    pass
