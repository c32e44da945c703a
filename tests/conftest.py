"""Test configuration: run the suite on a virtual 8-device CPU mesh.

Mirrors the reference's test strategy (SURVEY.md §4): multi-GPU comms tests
there run on a single node via LocalCUDACluster; here multi-chip sharding is
validated on `xla_force_host_platform_device_count=8` CPU devices. Pallas
kernels run in interpreter mode on CPU (handled inside the library).
"""
import os

# Single-thread the native math runtimes BEFORE any of them load: the
# suite ends up with XLA, torch (transitively), and sklearn's OpenMP in
# one process, and their competing thread pools both thrash the (often
# single-core) CI box and can SEGFAULT on teardown/first-use races
# (observed: flaky segv in stats entropy right after sklearn import).
os.environ.setdefault("OMP_NUM_THREADS", "1")
os.environ.setdefault("OPENBLAS_NUM_THREADS", "1")
os.environ.setdefault("MKL_NUM_THREADS", "1")

# XLA_FLAGS must be set before the CPU backend initializes. The platform
# itself is forced via jax.config below, so the suite runs on the CPU
# whatever JAX_PLATFORMS says.
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8"
    ).strip()

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_enable_x64", False)
# Persistent compile cache: the suite's wall-clock is dominated by XLA
# compiles (one per unique program; hundreds across the suite). A warm
# cache cuts repeat runs several-fold on 1-2 core boxes.
from raft_tpu.core.compile_cache import use_compile_cache  # noqa: E402

use_compile_cache()

import numpy as np  # noqa: E402
import pytest  # noqa: E402


@pytest.fixture
def rng():
    return np.random.default_rng(42)


def pytest_sessionfinish(session, exitstatus):
    """When the suite runs under the lock-witness
    (``RAFT_TPU_LOCKCHECK=1 pytest tests/test_mutable.py tests/test_serve.py``),
    any manifest-violating acquisition order observed *anywhere* in the
    run fails the session — the chaos suites double as dynamic
    validation of ``tools/graft_lint/lock_order.toml``. The same gate
    covers the guarded-field witness: a [[guards]] field touched on a
    shared instance without its declared lock fails the run, and so
    does a guard whose class was instantiated (armed) but whose lock
    was never once observed held at a guarded access (unexercised —
    a declaration the run cannot vouch for)."""
    from raft_tpu.utils import lockcheck

    if not lockcheck.is_enabled():
        return
    tr = session.config.pluginmanager.get_plugin("terminalreporter")

    def _fail(header, lines):
        session.exitstatus = 1
        if tr is not None:
            tr.write_line(header, red=True)
            for line in lines:
                tr.write_line("  " + line, red=True)

    if lockcheck.violations():
        _fail("lock-witness violations:", lockcheck.violations())
    if lockcheck.field_violations():
        _fail("guarded-field witness violations:", lockcheck.field_violations())
    unexercised = [
        cls for cls, st in lockcheck.field_coverage().items()
        if st["armed"] and not st["exercised"]
    ]
    if unexercised:
        _fail(
            "guards armed but never exercised (no guarded access observed "
            "with the declared lock held):",
            unexercised,
        )


@pytest.fixture(scope="session")
def eight_devices():
    devs = jax.devices()
    if len(devs) < 8:
        pytest.skip("needs 8 virtual devices")
    return devs[:8]
