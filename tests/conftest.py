"""Test configuration: force an 8-device virtual CPU mesh.

The sandbox has no accelerator; sharding tests run on a virtual 8-device
CPU mesh. The platform is pinned here, before any backend initialization.
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax

jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_num_cpu_devices", 8)


import shutil

import pytest

from yugabyte_tpu.utils import native_build


def pytest_configure(config):
    """Build the native libraries before xdist forks its workers (the
    controller has no `workerinput`), so six workers never compile; and
    arm the race sanitizer when the environment asks (`YBSAN=1
    pytest ...`): the vector-clock detector patches the sync vocabulary
    and every guarded-by / @ybsan.shadow class before any test runs."""
    if not hasattr(config, "workerinput"):
        for stem in native_build.LIBS:
            native_build.available(stem)  # a failure is the marked tests' to report
    from yugabyte_tpu.utils import ybsan as _shim
    if _shim.enabled():
        import tools.sanitizer
        tools.sanitizer.arm()


def pytest_sessionfinish(session, exitstatus):
    """The armed gate: any race report whose fingerprint is not
    justified in tools/analysis/baseline.txt fails the whole session
    (wrap_session returns session.exitstatus after this hook)."""
    from yugabyte_tpu.utils import ybsan as _shim
    if not _shim.armed():
        return
    import tools.sanitizer
    failures = tools.sanitizer.session_gate()
    if failures:
        print("\n=== ybsan: unbaselined race reports ===", file=sys.stderr)
        for f in failures:
            print(f, file=sys.stderr)
        session.exitstatus = 1


def pytest_runtest_setup(item):
    """`requires_native`: never a silent skip. A library that is absent
    beside a working compiler is a fault of the build, and the test says
    so with the reason native_build kept."""
    for mark in item.iter_markers("requires_native"):
        for stem in mark.args:
            if native_build.available(stem):
                continue
            if shutil.which("g++") is None:
                pytest.skip(f"no g++ on this machine: {stem} cannot be built")
            pytest.fail(f"native library unavailable with g++ present — "
                        f"{stem}: {native_build.unavailable()[stem]}",
                        pytrace=False)


@pytest.fixture(autouse=True)
def _fresh_bucket_health_board():
    """The bucket-health board is process-global by design (one routing
    memory per server). Between TESTS that memory is leakage: a cluster
    test that organically demotes a merge bucket (CPU device paths
    measure slower than native) would silently park the next test's
    device dispatches. Every test starts with a cold board."""
    from yugabyte_tpu.storage.bucket_health import health_board
    health_board().reset()
    yield
    health_board().reset()


@pytest.fixture(autouse=True)
def _fresh_timeseries_store():
    """The telemetry timebase is process-global too: a sampler thread
    left running by one test would scrape (and pin sources of) servers
    the next test already tore down. Every test starts storeless; the
    teardown stop also joins any sampler the test leaked."""
    from yugabyte_tpu.utils.timeseries import reset_timeseries_store
    reset_timeseries_store()
    yield
    reset_timeseries_store()


def pytest_collection_modifyitems(config, items):
    """Run the sync-point interleaving schedules FIRST: they pin exact
    thread timings, and by the end of a full-suite run hundreds of
    daemon threads from earlier cluster tests are still contending for
    the GIL on CI's single core — the dominant source of their flakes."""
    early = [i for i in items if "test_sync_interleavings" in i.nodeid]
    rest = [i for i in items if "test_sync_interleavings" not in i.nodeid]
    items[:] = early + rest
