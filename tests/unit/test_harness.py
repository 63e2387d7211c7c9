"""The harness itself (``tests/conftest.py``): a test's limit and the run's
compile cache."""

import os
import tempfile
import threading
import time

import jax
import numpy as np
import pytest


@pytest.mark.limit(0.3)
def test_a_test_that_waits_past_its_limit_is_failed_by_name(request):
    """Armed at 0.3 s around a sleep of 30: the sleep is cut, in THIS test,
    by an exception that names the test and the line it waited at."""
    started = time.monotonic()
    with pytest.raises(BaseException) as caught:
        time.sleep(30)
    assert time.monotonic() - started < 5
    assert type(caught.value).__name__ == "OverItsLimit"
    said = str(caught.value)
    assert request.node.nodeid in said and "limit of 0.3 s" in said
    assert "time.sleep(30)" in said


@pytest.mark.limit(0.3)
def test_a_wait_on_a_thread_is_cut_and_the_thread_is_left_to_the_test():
    """The main thread waiting on a thread the test started is raised in
    (a join is a wait the signal interrupts); the thread itself is not
    stopped, so the test that started it ends it."""
    release, ended = threading.Event(), threading.Event()

    def wait():
        release.wait(30)
        ended.set()

    worker = threading.Thread(target=wait, daemon=True)
    worker.start()
    try:
        with pytest.raises(BaseException) as caught:
            worker.join()
        assert type(caught.value).__name__ == "OverItsLimit"
        assert "worker.join()" in str(caught.value)
        assert not ended.is_set()
    finally:
        release.set()
    assert ended.wait(5)


def test_inside_a_test_the_clock_runs_and_rings_the_harness():
    import signal

    left, _ = signal.getitimer(signal.ITIMER_REAL)
    assert 0 < left <= 120
    assert signal.getsignal(signal.SIGALRM).__qualname__.startswith(
        "time_limit")


def test_the_run_compiles_into_one_directory_outside_the_checkout():
    """One cache for the workers, the children and the next run: named by
    the jax / jaxlib version, under the system's temp directory unless
    ``JAX_COMPILATION_CACHE_DIR`` was given, and never inside the tree the
    driver copies."""
    import jaxlib

    where = jax.config.jax_compilation_cache_dir
    assert where == os.environ["JAX_COMPILATION_CACHE_DIR"]
    assert jax.config.jax_persistent_cache_min_compile_time_secs == 0
    root = os.path.abspath(os.path.join(os.path.dirname(__file__),
                                        os.pardir, os.pardir))
    assert not os.path.abspath(where).startswith(root + os.sep)
    ours = os.path.join(tempfile.gettempdir(), "deepspeed_tpu-tier1-")
    if where.startswith(ours):           # (a directory that was given wins)
        assert where == os.path.join(
            f"{ours}jax{jax.__version__}-jaxlib{jaxlib.__version__}",
            ".jax_cache")


def test_greedy_generate_does_not_depend_on_how_many_tokens_were_asked_for(
        tiny_engine):
    """What ``tiny.sequential`` rests on: the first n tokens of a longer
    greedy ``generate`` are the n-token one's, with and without an eos."""
    engine, cfg = tiny_engine
    prompt = np.random.default_rng(9).integers(0, cfg.vocab_size, (1, 11))
    long = engine.generate(prompt, max_new_tokens=32)
    eos = int(long[0, 11 + 4])
    stopped = engine.generate(prompt, max_new_tokens=32, eos_token_id=eos)
    for n in (1, 5, 13):
        np.testing.assert_array_equal(
            engine.generate(prompt, max_new_tokens=n)[0], long[0, :11 + n])
        np.testing.assert_array_equal(
            engine.generate(prompt, max_new_tokens=n, eos_token_id=eos)[0],
            stopped[0, :11 + n])
