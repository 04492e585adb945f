"""The test harness itself (``tests/conftest.py``): the CPU backend dispatches
inline, and a test that overruns its deadline prints its stacks, is reported
failed once and is not run again, while the rest of its file still runs."""
import os
import subprocess
import sys
import textwrap
import time

import jax

TESTS = os.path.dirname(os.path.abspath(__file__))


def test_the_cpu_backend_dispatches_inline():
    assert jax.config.values["jax_cpu_enable_async_dispatch"] is False


def test_a_test_past_its_deadline_fails_once_and_its_file_goes_on(tmp_path):
    (tmp_path / "pytest.ini").write_text("[pytest]\n")
    (tmp_path / "conftest.py").write_text(textwrap.dedent(f"""\
        import sys
        sys.path.insert(0, {TESTS!r})
        import deadline
        import pytest


        @pytest.hookimpl(wrapper=True)
        def pytest_runtest_protocol(item):
            return (yield from deadline.armed(item, 2))
        """))
    (tmp_path / "test_waits.py").write_text(textwrap.dedent("""\
        import threading
        import time


        def test_deadlocks():
            lock = threading.Lock()
            lock.acquire()
            lock.acquire()


        def test_sleeps():
            time.sleep(60)


        def test_passes():
            assert True
        """))
    t = time.monotonic()
    run = subprocess.run(
        [sys.executable, "-m", "pytest", "-q", "-p", "no:cacheprovider",
         "-p", "xdist", "-n", "2", "--dist", "loadfile", "-p", "no:randomly",
         str(tmp_path / "test_waits.py")],
        cwd=tmp_path, capture_output=True, text=True, timeout=120)
    took = time.monotonic() - t
    out = run.stdout + run.stderr
    # each ended test fails once, and its resent copy is skipped
    assert out.count("FAILED test_waits.py::test_deadlocks") == 1, out
    assert out.count("FAILED test_waits.py::test_sleeps") == 1, out
    assert "2 failed, 1 passed, 2 skipped" in out, out
    assert out.count("Timeout (0:00:02)!") == 2, out
    assert "in test_deadlocks" in out and "in test_sleeps" in out, out
    assert took < 60, out
