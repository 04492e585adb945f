"""What the repository says about its own speed, and the tools its
texts send a reader to.

``BENCHMARK.json`` and the driver's ``PERF_LEDGER.jsonl`` are the only
performance records at the root: a number from a CPU run is not written
there under a device metric's name. The recorded artifacts the evidence
tests ingest live under ``tests/data/evidence/``.
"""
import glob
import os
import re

import pytest

from paddle_tpu.profiler import evidence

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_root_holds_no_cpu_record():
    assert evidence.scan_repo(REPO) == []
    assert glob.glob(os.path.join(REPO, "MULTICHIP_*")) == []


@pytest.mark.parametrize("text", [
    "README.md",
    "paddle_tpu/serving/__init__.py",
    "paddle_tpu/serving/scheduler.py",
    "paddle_tpu/serving/resilience.py",
    "paddle_tpu/serving/engine.py",
])
def test_cited_tools_exist(text):
    with open(os.path.join(REPO, text)) as f:
        cited = set(re.findall(r"(?<![\w/])tools/\w+\.py\b", f.read()))
    missing = sorted(t for t in cited
                     if not os.path.isfile(os.path.join(REPO, t)))
    assert not missing, f"{text} names tools that are not files: {missing}"
