import os
import sys
import tempfile

import pytest

# tests run against src/ without installation
sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

# Smoke tests and benches must see the real single-CPU device topology.
# (Only launch/dryrun.py forces 512 host devices, in its own process.)
assert "xla_force_host_platform_device_count" not in \
    os.environ.get("XLA_FLAGS", ""), \
    "tests must not inherit the dry-run's 512-device override"

# Hermetic autotune cache: a fresh per-session file so kernel-dispatch tests
# never read (or pollute) the user's tile winners — a forced override, since
# a developer's exported REPRO_AUTOTUNE_CACHE must not leak into the suite.
os.environ["REPRO_AUTOTUNE_CACHE"] = os.path.join(
    tempfile.mkdtemp(prefix="repro_autotune_"), "autotune.json")


def pytest_collection_modifyitems(config, items):
    """The suite must stay green offline: anything marked `network` is
    skipped unless the caller explicitly opts in."""
    if os.environ.get("REPRO_ALLOW_NETWORK") == "1":
        return
    skip = pytest.mark.skip(
        reason="needs network (set REPRO_ALLOW_NETWORK=1 to enable)")
    for item in items:
        if "network" in item.keywords:
            item.add_marker(skip)
