"""Test harness configuration.

Sharding/collective tests run on a virtual 8-device CPU mesh — the same
trick the reference uses for cluster tests without a cluster
(`python/ray/cluster_utils.py`): everything runs on one host, but the code
paths exercised are the real multi-device ones.  Env vars must be set
before jax initializes its backends, hence this file sets them at import
time (conftest is imported before any test module).
"""

import os

os.environ["JAX_PLATFORMS"] = "cpu"
xla_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in xla_flags:
    os.environ["XLA_FLAGS"] = (
        xla_flags + " --xla_force_host_platform_device_count=8"
    ).strip()

import pytest  # noqa: E402


@pytest.fixture(autouse=True)
def _sanitize(request):
    """Tests carrying the `sanitize` marker run under the runtime
    sanitizer (ray_tpu/util/sanitizer.py): lock-order tracking, the
    loop-lag watchdog, and end-of-test leak audits, asserted clean at
    teardown.  RT_SANITIZE=1 propagates to spawned workers.  Being
    autouse and requested FIRST, its teardown runs LAST — after
    rt_start has shut the runtime down — so the audit sees final
    state, not mid-shutdown churn."""
    marker = request.node.get_closest_marker("sanitize")
    if marker is None:
        yield
        return
    from ray_tpu.util import sanitizer

    sanitizer.set_enabled(True)
    sanitizer.reset()
    try:
        yield
        sanitizer.check_clean()
    finally:
        sanitizer.set_enabled(False)


# a process may hold 65,530 memory mappings (`vm.max_map_count`), and
# every program XLA compiles for the CPU keeps some: an xdist worker that
# runs two long JAX files in a row (`test_paged_attention.py`, then
# `test_lfm2.py`, which reaches 61,000 alone) ran out, and XLA's next
# compile died of a segmentation fault.  Past this many, a test's
# teardown drops JAX's compiled programs; the next test compiles its own.
_MAPS_HIGH = 30_000


@pytest.fixture(autouse=True)
def _compiled_programs_stay_under_the_mapping_limit():
    yield
    try:
        with open("/proc/self/maps") as f:
            maps = sum(1 for _ in f)
    except OSError:
        return
    if maps > _MAPS_HIGH:
        import jax

        jax.clear_caches()


@pytest.fixture
def rt_start():
    """Start a fresh single-node runtime for a test, shut down after."""
    import ray_tpu as rt

    rt.init(num_workers=2, num_cpus=4, ignore_reinit_error=True)
    yield rt
    rt.shutdown()


@pytest.fixture
def rt_start_4():
    import ray_tpu as rt

    rt.init(num_workers=4, num_cpus=8, ignore_reinit_error=True)
    yield rt
    rt.shutdown()
