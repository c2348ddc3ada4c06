"""``bench/run.py`` refuses to run off a TPU: a non-zero exit and no result
line."""
import os
import subprocess
import sys

import _paths  # noqa: F401

from bench import run


def test_exits_nonzero_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run(
        [sys.executable, str(run.BENCH / "run.py"), "--workload",
         "olmo1b.chat_tiers", "--seed", str(2**31 + 3), "--seconds", "1",
         "--trace", "0"],
        cwd=run.ROOT, env=env, capture_output=True, text=True, timeout=120)
    assert out.returncode != 0
    assert out.stdout == ""
    assert "needs a TPU" in out.stderr


def test_unknown_workload_is_refused():
    import json

    import pytest

    bm = json.loads((run.ROOT / "BENCHMARK.json").read_text())
    with pytest.raises(KeyError):
        run.Cell(bm, "no.such_cell")
