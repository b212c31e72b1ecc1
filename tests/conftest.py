"""Test configuration: 8 virtual CPU devices (the idiomatic JAX fake backend
for multi-device tests — SURVEY.md §4).  The tests run on the CPU platform
whatever the host has: the platform is set here, before jax is imported.
"""

import os
import sys

os.environ["JAX_PLATFORMS"] = "cpu"

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# the one place the multi-device host mesh is forced (the flag must land
# before jax initializes its backends; the helper refuses with a reason
# when that window has closed)
from defer_tpu.utils.compat import force_host_device_count  # noqa: E402

_DEVICES_OK, _DEVICES_WHY = force_host_device_count(8)

import jax  # noqa: E402
import pytest  # noqa: E402


def pytest_configure(config):
    config.addinivalue_line(
        "markers", "slow: long-running tests (subprocess CLI, big configs)")
    config.addinivalue_line(
        "markers", "timeout: per-test timeout (pytest-timeout compatible)")


@pytest.fixture(scope="session", autouse=True)
def _devices():
    assert len(jax.devices()) == 8, (jax.devices(), _DEVICES_WHY)
    yield


@pytest.fixture
def host_devices():
    """The forced multi-device host mesh, or a skip-with-reason when
    this process's jax initialized before the flag could land — the
    test vehicle for device-resident (ici) and sharding tests."""
    if not _DEVICES_OK:
        pytest.skip(_DEVICES_WHY)
    devs = jax.devices()
    if len(devs) < 2:
        pytest.skip(f"needs a multi-device host mesh, have {len(devs)}")
    return devs


#: per-test watchdog so one hung multi-process/socket test cannot eat the
#: whole 870 s tier-1 budget.  Generous: the slowest healthy tests (big
#: jit compiles on a 1-core host) finish well under 2 minutes.
PER_TEST_TIMEOUT_S = int(os.environ.get("DEFER_TEST_TIMEOUT_S", "300"))


def _pytest_timeout_active(config) -> bool:
    """True when the real pytest-timeout plugin is installed AND armed
    (``--timeout`` flag or ``timeout`` ini).  Merely having the plugin
    installed arms nothing — the fallback must still cover a plain
    ``pytest -m 'not slow'`` run, or one hung socket test eats the
    whole tier-1 budget."""
    if not config.pluginmanager.hasplugin("timeout"):
        return False
    for probe in (lambda: config.getoption("timeout"),
                  lambda: config.getini("timeout")):
        try:
            if probe():
                return True
        except (ValueError, KeyError):
            pass
    return False


@pytest.hookimpl(hookwrapper=True)
def pytest_runtest_call(item):
    """Fallback per-test timeout when pytest-timeout is not installed or
    not armed (CI installs and arms it; this container may not have
    it): a SIGALRM on the main thread aborts the test body with a
    TimeoutError.  Defers to the real plugin when it is active, and to
    a ``@pytest.mark.timeout(N)`` marker for per-test overrides."""
    import signal
    import threading

    if _pytest_timeout_active(item.config) \
            or not hasattr(signal, "SIGALRM") \
            or threading.current_thread() is not threading.main_thread():
        yield
        return
    marker = item.get_closest_marker("timeout")
    limit = int(marker.args[0]) if marker and marker.args \
        else PER_TEST_TIMEOUT_S
    if limit <= 0:
        yield
        return

    def on_alarm(signum, frame):  # noqa: ARG001 — signal signature
        raise TimeoutError(
            f"{item.nodeid} exceeded the {limit}s per-test timeout "
            f"(DEFER_TEST_TIMEOUT_S / @pytest.mark.timeout override)")

    old = signal.signal(signal.SIGALRM, on_alarm)
    signal.alarm(limit)
    try:
        yield
    finally:
        signal.alarm(0)
        signal.signal(signal.SIGALRM, old)


@pytest.fixture
def traced():
    """The process tracer, on and empty for one test."""
    from defer_tpu.obs import tracer
    tr = tracer()
    was = tr.enabled
    tr.clear()
    tr.enabled = True
    yield tr
    tr.enabled = was
    tr.clear()
