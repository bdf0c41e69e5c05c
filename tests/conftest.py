import os
import sys

# Tests must see exactly ONE host device; fail fast if something polluted the
# env.
assert "xla_force_host_platform_device_count" not in os.environ.get("XLA_FLAGS", "")

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

# Opt-in runtime lock-discipline checker (TRUFFLE_LOCKCHECK=1): must install
# BEFORE any repro import so every runtime lock is created instrumented.
_LOCKCHECK = os.environ.get("TRUFFLE_LOCKCHECK") == "1"
if _LOCKCHECK:
    from repro.analysis import lockcheck as _lockcheck

    _lockcheck.install()

import pytest  # noqa: E402

try:                                     # nightly soak: --hypothesis-profile=ci
    from hypothesis import settings as _hyp_settings

    _hyp_settings.register_profile("ci", max_examples=200, deadline=None)
except ImportError:                      # fallback shim has no profiles
    pass

from repro.runtime.clock import Clock  # noqa: E402


def pytest_sessionfinish(session, exitstatus):
    """With TRUFFLE_LOCKCHECK=1: fail the run on any lock-order inversion."""
    if not _LOCKCHECK:
        return
    invs = _lockcheck.inversions()
    rep = _lockcheck.report()
    print("\n[lockcheck] %d order edges, %d inversions, %d long holds"
          % (rep["order_edges"], len(invs), len(rep["long_holds"])))
    for h in rep["long_holds"]:
        print("[lockcheck] long hold: %(site)s held %(held_s)ss (%(thread)s)"
              % h)
    if invs:
        print(_lockcheck.format_inversions(invs))
        session.exitstatus = 1


@pytest.fixture()
def fast_clock():
    """Simulated delays shrunk 100x — orderings preserved, tests fast."""
    return Clock(scale=0.01)


@pytest.fixture(scope="session")
def rng_key():
    import jax
    return jax.random.PRNGKey(0)
