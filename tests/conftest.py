"""Test config: run on a virtual 8-device CPU mesh.

Mirrors the reference's localhost multi-process distributed testing
(SURVEY.md §4.4) — multi-chip sharding semantics are validated on
XLA's host-platform device partitioning, no TPU pod required.
"""

import atexit
import os
import shutil
import tempfile

# The suite runs on the CPU backend whatever the machine holds: JAX
# honours JAX_PLATFORMS from the environment, and the host-device-count
# flag must precede the CPU backend's first use.
os.environ["JAX_PLATFORMS"] = "cpu"
xla_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in xla_flags:
    os.environ["XLA_FLAGS"] = (
        xla_flags + " --xla_force_host_platform_device_count=8").strip()
# One empty compile cache per session (shared with every child process
# the tests start): a test must not depend on what an earlier run left
# in the checkout's default cache.
_cache = tempfile.mkdtemp(prefix="mxtpu_test_cache_")
atexit.register(shutil.rmtree, _cache, ignore_errors=True)
os.environ["JAX_COMPILATION_CACHE_DIR"] = _cache

import numpy as np  # noqa: E402
import pytest  # noqa: E402


def pytest_addoption(parser):
    parser.addoption(
        "--graftsan", action="store", nargs="?", const="all",
        default=None, metavar="COMPONENTS",
        help="enable the graftsan runtime sanitizers for the whole "
             "run (sets MXNET_SAN before tests import mxnet_tpu): "
             "comma list of race,recompile,donation,transfer, or "
             "'all' when given bare.  Any sanitizer report fails the "
             "session at the end.")


def pytest_configure(config):
    spec = config.getoption("--graftsan")
    if spec:
        # before collection imports mxnet_tpu, so module-level locks
        # are created through the instrumented factories
        os.environ["MXNET_SAN"] = spec


@pytest.fixture(autouse=True)
def _graftsan_reports(request):
    """With --graftsan, any sanitizer report left behind by a test
    fails THAT test (tests that deliberately provoke reports consume
    them with graftsan.clear())."""
    if not request.config.getoption("--graftsan"):
        yield
        return
    import tools.graftsan as graftsan
    before = len(graftsan.reports())
    yield
    found = graftsan.reports()[before:]
    if found:
        msgs = "\n".join(graftsan.format_report(r) for r in found)
        graftsan.clear()
        pytest.fail("graftsan: %d sanitizer report(s) during this "
                    "test:\n%s" % (len(found), msgs), pytrace=False)


@pytest.fixture(autouse=True)
def _seed_rng():
    """Reproducible per-test seeding (reference:
    tests/python/unittest/common.py with_seed)."""
    import mxnet_tpu as mx
    mx.random.seed(0)
    np.random.seed(0)
    yield


@pytest.fixture
def dq_accumulates_in():
    """``place("hbm")``: the flash backward as a sequence too long for
    dq's VMEM accumulator gets it (`ops/attention.py` `_VMEM_DQ`), at a
    test's size; ``place("vmem")`` leaves the plan alone.  The choice is
    the plan's, from shapes: no argument reaches it."""
    from mxnet_tpu.ops import attention
    patch = pytest.MonkeyPatch()

    def place(where):
        assert where in ("vmem", "hbm")
        if where == "hbm":
            patch.setattr(attention, "_VMEM_DQ", 0)
            attention._flash_bwd_pallas.clear_cache()

    yield place
    patch.undo()
    attention._flash_bwd_pallas.clear_cache()


@pytest.fixture
def interpreted_headrope(monkeypatch):
    """Steers `ops/lm_blocks.py` `_head_norm_rotary` onto its TPU branch on
    this CPU host, the two kernels interpreted, at tiles small enough for a
    test (32 rows forward, 16 backward, 8 heads a grid step); every other
    choice by platform (the attention's, the selection's) stays the
    CPU's."""
    import jax
    from mxnet_tpu.ops import lm_blocks
    real = jax.lax.platform_dependent
    mine = (lm_blocks._headrope_fwd_pallas, lm_blocks._headrope_bwd_pallas)

    def choose(*args, tpu, default):
        if getattr(tpu, "func", None) in mine:
            return tpu(*args, interpret=True)
        return real(*args, tpu=tpu, default=default)

    monkeypatch.setattr(lm_blocks, "HEADROPE_TILES",
                        {"fwd": 32, "bwd": 16, "heads": 8})
    monkeypatch.setattr(jax.lax, "platform_dependent", choose)
    # an operator traced before is not traced again, and one traced here
    # must not serve a later test
    jax.clear_caches()
    yield
    jax.clear_caches()
