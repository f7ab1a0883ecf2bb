"""Test configuration: JAX on a virtual 8-device CPU mesh.

The tests run on the CPU (``JAX_PLATFORMS=cpu``, pinned here too so a bare
``pytest`` cannot reach for an accelerator); all sharding/mesh tests run
against ``--xla_force_host_platform_device_count=8`` CPU devices.  The one
file that talks to the TPU's compiler, ``test_tpu_compile.py``, describes
its chip inside a fixture and needs no device.
"""

import os
import sys

_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (_flags + " --xla_force_host_platform_device_count=8").strip()
os.environ.setdefault("JAX_PLATFORMS", "cpu")

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, _REPO)

# One cache root (libs/cachedir): JAX's persistent compilation cache and
# the AOT executable cache (docs/warm-boot.md) both live under it, shared
# by the xdist workers and by spawned e2e node subprocesses, which inherit
# this environ.
from cometbft_tpu.libs import cachedir

cachedir.enable()
# The background warm-boot pass would compile the whole bucket matrix on
# this CPU host the moment any test activates the trusted backend — tests
# warm shapes on demand instead (test_warmboot drives the pass explicitly).
os.environ.setdefault("COMETBFT_TPU_WARMBOOT", "0")


def pytest_configure(config):
    config.addinivalue_line(
        "markers", "slow: long soak runs excluded from the tier-1 suite"
    )
    config.addinivalue_line(
        "markers",
        "warmcache(tag, ...): compile-heavy test that runs in tier-1 only "
        "when every named exec-cache tag is already warm on disk; demoted "
        "to the slow lane (which warms the cache) otherwise",
    )


def _exec_cache_warm(tags) -> bool:
    try:
        from cometbft_tpu.ops import aot_cache

        # loadable, not has: XLA-CPU's thunk runtime serializes entries it
        # cannot reload cross-process — those must stay in the slow lane
        return bool(tags) and all(aot_cache.loadable(t) for t in tags)
    except Exception:  # noqa: BLE001 — a cold probe must never break collection
        return False


def pytest_collection_modifyitems(config, items):
    """Compile-heavy tests return to tier-1 when the shared exec cache can
    serve their executables warm (a previous full-suite/nightly run stored
    them); cold entries keep them in the slow lane, which pays the compile
    ONCE and warms the cache for every later tier-1 run."""
    import pytest

    for item in items:
        m = item.get_closest_marker("warmcache")
        if m is not None and not _exec_cache_warm(m.args):
            item.add_marker(pytest.mark.slow)


def pytest_terminal_summary(terminalreporter, exitstatus, config):
    """Parseable summary lines in the tier-1 log —
    scripts/check_tier1_budget.py reads the compile-time share from the
    exec-cache line and the flight-recorder overhead share from the
    trace line.  Per-process counters: spawned node subprocesses keep
    their own, so both are lower bounds on suite-wide totals."""
    try:
        from cometbft_tpu.ops import warm_stats

        terminalreporter.write_line(warm_stats.summary_line())
    except Exception:  # noqa: BLE001
        pass
    try:
        from cometbft_tpu.libs import tracing

        terminalreporter.write_line(tracing.summary_line())
    except Exception:  # noqa: BLE001
        pass
