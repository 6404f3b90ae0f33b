"""Test configuration.

Multi-device sharding tests run on a virtual 8-device CPU mesh (no
multi-chip TPU hardware is available in CI): force the host platform and 8
virtual devices BEFORE jax initializes. This mirrors the reference's trick
of standing in for the network with its Memory transport — we stand in for
a TPU pod with virtual CPU devices (SURVEY.md §4).
"""

import os

os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8").strip()

import asyncio  # noqa: E402
import inspect  # noqa: E402

import pytest  # noqa: E402


def pytest_configure(config):
    # tier-1 CI runs `-m 'not slow'`; register the marker so the long
    # tiers (full chaos suite, big soak runs) deselect cleanly instead
    # of tripping unknown-marker warnings
    config.addinivalue_line(
        "markers", "slow: long-running tier excluded from tier-1 CI "
        "(run explicitly with -m slow)")


# Run `async def` tests on a fresh event loop (no pytest-asyncio needed).
@pytest.hookimpl(tryfirst=True)
def pytest_pyfunc_call(pyfuncitem):
    fn = pyfuncitem.function
    if inspect.iscoroutinefunction(fn):
        kwargs = {k: pyfuncitem.funcargs[k]
                  for k in pyfuncitem._fixtureinfo.argnames}
        asyncio.run(asyncio.wait_for(fn(**kwargs), timeout=120))
        return True
    return None


@pytest.fixture(autouse=True)
def _io_impl_env_stays_with_its_test():
    """``uring.set_io_impl`` writes ``PUSHCDN_IO_IMPL`` into the environment
    (for child processes): a test that selects io_uring must not hand it
    to whichever test the worker runs next (xdist's file order moves)."""
    saved = os.environ.get("PUSHCDN_IO_IMPL")
    yield
    if saved is None:
        os.environ.pop("PUSHCDN_IO_IMPL", None)
    else:
        os.environ["PUSHCDN_IO_IMPL"] = saved
