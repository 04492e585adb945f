"""A hard deadline for each test (``tests/conftest.py``).

A test whose setup, call and teardown take longer than the deadline prints
every thread's stack and ends its process: ``faulthandler``'s own thread
does both, since a wait inside C++ (a deadlock in XLA's dispatch) runs no
Python signal handler. Under xdist the controller then reports the test
failed and hands the rest of its file to a new worker, the ended test among
them; the workers of one run list the tests they start in one file, and a
test already listed is skipped, not run a second time."""
import faulthandler
import functools
import os
import tempfile

import pytest


@functools.cache
def _stderr() -> int:
    """This process's stderr, taken between tests: while a test runs, pytest
    points fd 2 at its capture."""
    return os.dup(2)


def armed(item, seconds: float):
    """The body of a ``pytest_runtest_protocol`` wrapper:
    ``return (yield from armed(item, seconds))``."""
    run = getattr(item.config, "workerinput", None)
    if run is not None:             # without xdist nothing is sent twice
        path = os.path.join(tempfile.gettempdir(),
                            f"pytest-started-{run['testrunuid']}")
        with open(path, "a+") as f:
            f.seek(0)
            if item.nodeid in f.read().splitlines():
                item.add_marker(pytest.mark.skip(reason="an earlier worker "
                                                 "ended while it ran: past its "
                                                 "deadline, or crashed"))
            else:
                f.write(item.nodeid + "\n")
    faulthandler.dump_traceback_later(seconds, exit=True, file=_stderr())
    try:
        return (yield)
    finally:
        faulthandler.cancel_dump_traceback_later()
