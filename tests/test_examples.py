"""Every runnable example executes end-to-end: the user-facing entry
points must not rot. Reference pattern: the demo scripts under the
reference's test dirs are executed, not just imported.

Slow tier (~1 min for all five on CPU): each runs as its own process
with JAX_PLATFORMS=cpu, so an example never takes a chip from the run
that started it."""
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

EXAMPLES = ["train_mnist_cnn.py", "train_llama_hybrid.py",
            "serve_generate.py", "export_and_infer.py",
            "train_static_amp.py"]


@pytest.mark.slow
@pytest.mark.parametrize("name", EXAMPLES)
def test_example_runs(name, tmp_path):
    env = dict(os.environ)
    env.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")
    # the examples import paddle_tpu from the repo root (cwd is tmp_path
    # to keep any artifacts they write out of the tree)
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    env["JAX_PLATFORMS"] = "cpu"
    r = subprocess.run(
        [sys.executable, os.path.join(REPO, "examples", name)],
        capture_output=True, text=True, timeout=900, cwd=str(tmp_path),
        env=env)
    assert r.returncode == 0, (r.stdout[-1500:], r.stderr[-1500:])
