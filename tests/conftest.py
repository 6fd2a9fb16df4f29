"""Test-session device setup.

The dist/ft tests need a handful of local devices; 8 is the conventional
unit-test topology. This is deliberately NOT the dry-run's 512 (that env is
confined to launch/dryrun.py, which must never be imported from tests), and
bench/run.py is a separate process that still sees the real device count.
"""
import os
import sys

os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")

# Dependency gate: slim containers may lack hypothesis; fall back to the
# deterministic stub so the property tests still execute (see
# repro.testing.hypothesis_stub). The real library wins when installed.
try:
    import hypothesis  # noqa: F401
except ModuleNotFoundError:
    from repro.testing import hypothesis_stub
    sys.modules["hypothesis"] = hypothesis_stub
    sys.modules["hypothesis.strategies"] = hypothesis_stub.strategies
