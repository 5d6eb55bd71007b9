"""The comparison that decides ``correct``, driven end to end at rehearsal
size on the CPU: a sound run passes, and the control and each fault the
cells can have fail it.

Run with ``python -m pytest bench/tests``.  Each case builds a tiny copy
of its cell (``--rehearse``), so the harness's look for a chip is skipped
and everything else runs as on the chip.  Faults are planted in the
program when the window opens, after the warm-up:

* an answer altered where it is produced (``IndexView.search``);
* half of each batch left out (its rows answered with nothing);
* a write path that returns without changing the index.

The exchange between chips is not a fault these cells can have: every
cell runs on one chip.
"""

import os
import shutil
import subprocess
import sys

import jax.numpy as jnp
import numpy as np
import pytest

from bench import loadgen
from bench import run as harness

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
SEED = 3000000011  # wider than 32 bits, as benchmark seeds may be


def run_cell(workload, *extra):
    return harness.main(["--workload", workload, "--seed", str(SEED), "--seconds", "3",
                         "--trace", "0", "--rehearse", *extra])


@pytest.fixture
def at_window(monkeypatch):
    """Plant ``wrap(original)`` as ``target.name`` when the window opens."""

    def plant(target, name, wrap):
        orig_run = loadgen.Traffic.run

        def run(self, *a, **k):
            monkeypatch.setattr(target, name, wrap(getattr(target, name)))
            return orig_run(self, *a, **k)

        monkeypatch.setattr(loadgen.Traffic, "run", run)

    return plant


@pytest.mark.parametrize("workload", ["hydra-rw256.open", "rw128-stream.ingest", "hydra-rw256.bulk"])
def test_sound_run_passes_and_control_fails(workload):
    out = run_cell(workload, "--control")
    checks = out["checks"]
    assert out["correct"], checks
    assert all(c["value"] <= c["limit"] for c in checks.values())
    assert checks["dist_gap"]["value"] < 1e-5
    assert out["control"]["dist_gap"] > checks["dist_gap"]["limit"]
    assert out["device"]["platform"] == "cpu"
    assert list(out)[-1] == "checks"


def test_answer_altered(at_window):
    from repro.serve_index.view import IndexView

    def wrap(orig):
        def search(self, Q, **kw):
            d, i = orig(self, Q, **kw)
            return d.at[:, 0].multiply(1.01), i

        return search

    at_window(IndexView, "search", wrap)
    out = run_cell("hydra-rw256.open")
    assert not out["correct"]
    assert out["checks"]["dist_gap"]["value"] > out["checks"]["dist_gap"]["limit"]


def test_half_of_each_batch_left_out(at_window):
    from repro.serve_index.view import IndexView

    def wrap(orig):
        def search(self, Q, q_valid=None, **kw):
            d, i = orig(self, Q, q_valid=q_valid, **kw)
            n = int(np.asarray(q_valid).sum())
            gone = (jnp.arange(d.shape[0]) >= n - n // 2)[:, None]
            return jnp.where(gone, jnp.inf, d), jnp.where(gone, -1, i)

        return search

    at_window(IndexView, "search", wrap)
    out = run_cell("hydra-rw256.bulk")
    assert not out["correct"]
    assert out["checks"]["bad"]["value"] > 0


def test_writes_left_unapplied(at_window):
    from repro.index.streaming import StreamingIndex

    def wrap(orig):
        def insert(self, X, ids=None):
            return np.asarray(ids, np.int32)

        return insert

    at_window(StreamingIndex, "insert", wrap)
    out = run_cell("rw128-stream.ingest")
    assert not out["correct"]
    assert out["checks"]["missed"]["value"] > 0


def _cli(cwd, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **(env_extra or {}))
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "hydra-rw256.open", "--seed", "1",
         "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300,
    )


def test_no_tpu_no_result():
    p = _cli(ROOT)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "not a TPU" in p.stderr


def test_benchmark_alone_no_result(tmp_path):
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(ROOT, "bench"), tmp_path / "bench",
                    ignore=shutil.ignore_patterns(".jax_cache", ".runs", "__pycache__"))
    p = _cli(tmp_path)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
