"""Test configuration.

Tests run on a virtual 8-device CPU mesh so multi-chip sharding logic
(jax.sharding.Mesh / shard_map) is exercised without TPU hardware, mirroring how
the reference tests multi-node behavior in one process
(/root/reference/testing/simulator/src/local_network.rs:107).
The benchmark (benchmark/run.py) runs on the TPU chip instead.
"""
import os
import sys
import threading
import traceback

import pytest

os.environ["JAX_PLATFORMS"] = "cpu"
xla_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in xla_flags:
    os.environ["XLA_FLAGS"] = (
        xla_flags + " --xla_force_host_platform_device_count=8"
    ).strip()

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from lighthouse_tpu.utils import compile_cache  # noqa: E402

compile_cache.configure()


# -- uncaught-thread-exception recorder --------------------------------------
# Round-5 review found unhandled thread exceptions in GREEN runs: daemon
# threads raced service shutdown and blew up into closed sockets, and pytest
# only printed them as noise. Record every uncaught thread exception and fail
# the session — a green run must mean no thread died screaming.

_THREAD_EXCEPTIONS: list = []


def _install_recorder():
    """Chain-wrap whatever excepthook is current (pytest's own
    threadexception plugin installs one in its pytest_configure, so this
    must run both at import time and again at sessionstart)."""
    inner = threading.excepthook
    if getattr(inner, "_lhtpu_recorder", False):
        return

    def _recording_excepthook(args):
        _THREAD_EXCEPTIONS.append(args)
        inner(args)

    _recording_excepthook._lhtpu_recorder = True
    threading.excepthook = _recording_excepthook


_install_recorder()


def pytest_sessionstart(session):
    _install_recorder()


@pytest.fixture
def thread_exceptions():
    """Tests that deliberately crash a thread can consume the record."""
    return _THREAD_EXCEPTIONS


def _locksan_reports(config):
    if not config.getoption("--sanitize-locks", default=False):
        return []
    from lighthouse_tpu.analysis import locksan
    return locksan.REPORTS


def pytest_sessionfinish(session, exitstatus):
    if _THREAD_EXCEPTIONS and session.exitstatus == 0:
        session.exitstatus = 1
    if _locksan_reports(session.config) and session.exitstatus == 0:
        session.exitstatus = 1


def pytest_terminal_summary(terminalreporter):
    reports = _locksan_reports(terminalreporter.config)
    if reports:
        terminalreporter.section(
            "graftrace lock sanitizer reports (session FAILED)")
        for r in reports:
            terminalreporter.write_line("  " + r.render())
    elif terminalreporter.config.getoption("--sanitize-locks",
                                           default=False):
        armed = getattr(terminalreporter.config, "_locksan_armed", [])
        terminalreporter.write_line(
            f"graftrace lock sanitizer: 0 reports "
            f"({len(armed)} armed classes)")
    if not _THREAD_EXCEPTIONS:
        return
    terminalreporter.section("uncaught thread exceptions (session FAILED)")
    for args in _THREAD_EXCEPTIONS:
        name = args.thread.name if args.thread is not None else "<unknown>"
        terminalreporter.write_line(f"thread {name!r}:")
        for line in traceback.format_exception(
                args.exc_type, args.exc_value, args.exc_traceback):
            terminalreporter.write_line("  " + line.rstrip())


# -- --sanitize: strict-numerics mode for the kernel tests -------------------

def pytest_addoption(parser):
    parser.addoption(
        "--sanitize", action="store_true", default=False,
        help="run kernel tests with jax_debug_nans and "
             "jax_numpy_rank_promotion='raise' (slower, catches silent "
             "NaNs and accidental broadcasts)")
    parser.addoption(
        "--sanitize-locks", action="store_true", default=False,
        help="arm the graftrace lock sanitizer: every attribute the "
             "static data-race model proves lock-guarded is checked at "
             "runtime — a cross-thread write without the guard held "
             "fails the session (analysis/locksan.py)")


def pytest_configure(config):
    if config.getoption("--sanitize"):
        # set before any test module imports jax so the config sticks;
        # also update in-process in case a plugin imported jax already
        os.environ["JAX_DEBUG_NANS"] = "True"
        os.environ["JAX_NUMPY_RANK_PROMOTION"] = "raise"
        if "jax" in sys.modules:
            import jax
            jax.config.update("jax_debug_nans", True)
            jax.config.update("jax_numpy_rank_promotion", "raise")
    if config.getoption("--sanitize-locks"):
        # configure runs before any test module imports product code,
        # so the lock-factory patch catches every instance the tests
        # will create; arming installs the descriptors on the classes
        # the static model proved guarded
        from lighthouse_tpu.analysis import locksan
        locksan.install_lock_tracking()
        repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        config._locksan_armed = locksan.arm_repo(repo)
