"""Test configuration: force an 8-device virtual CPU mesh.

Multi-chip hardware is not available in CI; shardings are validated on a
virtual CPU mesh (xla_force_host_platform_device_count), as the driver's
dryrun does.  The CPU selection is applied both via env and via
jax.config, before any backend initializes, so the suite never claims a
chip the machine may hold.
"""

import os

os.environ["JAX_PLATFORMS"] = "cpu"
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8").strip()

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")


def pytest_sessionstart(session):
    assert len(jax.devices()) == 8, \
        f"expected 8-device CPU mesh, got {jax.devices()}"


import threading  # noqa: E402
import time  # noqa: E402

import pytest  # noqa: E402


@pytest.fixture(autouse=True)
def _failure_domain_leak_guard():
    """Tier-1 leak guard (chip failure domains, PR 10):

    - no test may leave a mesh slice QUARANTINED behind — a later test
      sharing the module-scoped runner would silently route around a
      chip the earlier test condemned (the board is healed before
      failing, so one offender doesn't cascade);
    - no test may leak a NON-DAEMON worker thread — a stop() that
      doesn't join its workers turns every in-process server cycle
      into a thread leak (the graceful-drain contract: node.stop
      drains pools, TikvServer/PdServer join their gRPC executors).
    """
    before = {t.ident for t in threading.enumerate()}
    yield
    from tikv_tpu.device import supervisor as _sup
    leaked = [b for b in _sup.live_boards() if b.quarantined_set()]
    for b in leaked:
        b.reset()
    assert not leaked, (
        f"{len(leaked)} health board(s) left with quarantined slices "
        "— heal the fault and let the probe re-admit (or reset the "
        "board) before the test ends")

    def _leftover():
        return [t for t in threading.enumerate()
                if t.is_alive() and not t.daemon
                and t.ident not in before
                and t is not threading.current_thread()]

    # grace: executors whose shutdown was just requested finish
    # retiring their workers asynchronously
    deadline = time.monotonic() + 2.0
    while _leftover() and time.monotonic() < deadline:
        time.sleep(0.02)
    left = _leftover()
    assert not left, \
        f"non-daemon thread(s) leaked: {[t.name for t in left]}"
