"""Test harness: 8-device CPU-sim mesh, one compile cache, a limit a test.

The reference tests distributed behavior by spawning N processes over local GPUs
(``tests/unit/common.py DistributedExec``).  On TPU/JAX the equivalent — and
simpler — harness is a single process with 8 virtual CPU devices
(``--xla_force_host_platform_device_count``): every collective and sharding path
is exercised for real by XLA's CPU backend, no hardware needed (SURVEY §4).

The suite's time is XLA compiling the same tiny programs for the CPU, so the
run keeps JAX's persistent compilation cache in ONE directory that the xdist
workers, the child processes a test starts and the next run share
(``tests/README.md``: how it is named, what a hit does to the compile
counters, how to clear it).  And every phase of every test (set-up, call,
tear-down) runs under a limit of its own, so that a test that waits fails
alone, by name, and the run goes on.
"""

import contextlib
import faulthandler
import os
import signal
import sys
import tempfile
import traceback

# The CPU here stands in for the chip: the suite checks WHAT a program
# computes, never how fast XLA:CPU's code for it runs, and half of tier-1's
# time is XLA:CPU compiling — a third to a half of that LLVM's optimisation
# passes, which the two last flags leave out (tests/README.md has the
# measurement).
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") +
                           " --xla_force_host_platform_device_count=8"
                           " --xla_backend_optimization_level=0"
                           " --xla_llvm_disable_expensive_passes=true")
os.environ.setdefault("JAX_ENABLE_X64", "0")

import jax
import jaxlib

jax.config.update("jax_platforms", "cpu")

import pytest

from deepspeed_tpu.utils.platform import enable_compile_cache

# Outside the checkout (the driver copies the tree), under the system's temp
# directory, named by what compiled the entries and by nothing that changes
# from run to run.  Exported, so that a child process a test starts
# (``chip_smoke.py``, ``chipbench/run.py --rehearse``: both call
# ``enable_compile_cache``, where the variable wins) shares it too.
os.environ["JAX_COMPILATION_CACHE_DIR"] = enable_compile_cache(os.path.join(
    tempfile.gettempdir(),
    f"deepspeed_tpu-tier1-jax{jax.__version__}-jaxlib{jaxlib.__version__}"))

#: seconds a phase of a test may take; ``@pytest.mark.limit(seconds)`` raises
#: it for a test that is sound and needs more
DEFAULT_LIMIT_S = 120.0
#: ``tests/chipbench/`` is the benchmark's and carries no marker of this
#: harness: its rehearsals are child processes under time-outs of their own
#: (300-600 s), which this limit only stands behind
CHIPBENCH_LIMIT_S = 660.0


class OverItsLimit(BaseException):
    """A test's set-up, call or tear-down ran past its limit.  Not an
    ``Exception``: code under test that catches those and goes on (a
    server's loop, the smoke's phases) must not swallow the test's end."""


@contextlib.contextmanager
def time_limit(seconds: float, what: str):
    """Raise :class:`OverItsLimit`, naming ``what`` and carrying the main
    thread's stack, in the main thread once ``seconds`` have passed inside
    the block (``SIGALRM``: the handler runs between two bytecodes of the
    main thread, which is where pytest and an xdist worker run the tests).
    A main thread held inside native code that never comes back to the
    interpreter cannot be raised in: for that case ``faulthandler`` dumps
    every thread's stack to stderr at the same moment, from a thread of its
    own.  Threads the test started are not stopped — the dump shows where
    each was."""

    def over(signum, frame):
        raise OverItsLimit(
            f"{what} ran past its limit of {seconds:g} s; the main thread "
            "was at:\n" + "".join(traceback.format_stack(frame)))

    handler = signal.signal(signal.SIGALRM, over)
    signal.setitimer(signal.ITIMER_REAL, seconds)
    faulthandler.dump_traceback_later(seconds, exit=False,
                                      file=sys.__stderr__)
    try:
        yield
    finally:
        signal.setitimer(signal.ITIMER_REAL, 0)
        faulthandler.cancel_dump_traceback_later()
        signal.signal(signal.SIGALRM, handler)


def _limited(item, phase):
    marker = item.get_closest_marker("limit")
    if marker:
        seconds = float(marker.args[0])
    elif item.nodeid.startswith("tests/chipbench/"):
        seconds = CHIPBENCH_LIMIT_S
    else:
        seconds = DEFAULT_LIMIT_S
    return time_limit(seconds, f"{item.nodeid} ({phase})")


@pytest.hookimpl(wrapper=True)
def pytest_runtest_setup(item):
    with _limited(item, "set-up"):
        return (yield)


@pytest.hookimpl(wrapper=True)
def pytest_runtest_call(item):
    with _limited(item, "call"):
        return (yield)


@pytest.hookimpl(wrapper=True)
def pytest_runtest_teardown(item):
    with _limited(item, "tear-down"):
        return (yield)


@pytest.fixture(autouse=True)
def _reset_comm_state():
    """Each test gets a fresh module-level topology."""
    yield
    from deepspeed_tpu import comm

    comm.reset_topology()
    comm.comms_logger.reset()


@pytest.fixture
def eight_devices():
    devs = jax.devices()
    assert len(devs) == 8, f"expected 8 CPU-sim devices, got {len(devs)}"
    return devs
