"""``chip_smoke.py`` on the CPU: the parts that must hold without a chip.

The smoke itself runs only on a TPU. Here: it refuses to run anywhere
else (no CPU fallback, no result line), its executor-fallback check fails
a run whose plan was demoted even though the server kept answering, and
its tie-aware parity check accepts tie swaps and nothing else.
"""

import importlib.util
import os
import shutil
import subprocess
import sys

import numpy as np
import pytest

from repro import fault, obs
from repro.core import IndexBuildConfig, Retriever, build_index
from repro.data import make_corpus, make_queries
from repro.fault import FaultPlan

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SCRIPT = os.path.join(ROOT, "chip_smoke.py")


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke", SCRIPT)
    mod = importlib.util.module_from_spec(spec)
    sys.modules["chip_smoke"] = mod  # its dataclasses resolve through it
    try:
        spec.loader.exec_module(mod)
        yield mod
    finally:
        sys.modules.pop("chip_smoke", None)


def _run(cwd, env_updates, drop=()):
    env = {k: v for k, v in os.environ.items() if k not in drop}
    env.update(env_updates)
    return subprocess.run(
        [sys.executable, "chip_smoke.py"], cwd=cwd, env=env,
        capture_output=True, text=True, timeout=120,
    )


def test_refuses_cpu_without_result():
    out = _run(ROOT, {"JAX_PLATFORMS": "cpu"})
    assert out.returncode != 0
    assert '"ok"' not in out.stdout
    assert "no TPU" in out.stderr


def test_refuses_outside_checkout(tmp_path):
    shutil.copy(SCRIPT, tmp_path / "chip_smoke.py")
    out = _run(tmp_path, {"JAX_PLATFORMS": "cpu"}, drop=("PYTHONPATH",))
    assert out.returncode != 0
    assert '"ok"' not in out.stdout


def test_fallback_check_fails_demoted_run(smoke):
    """A kernel failure demotes the plan to the reference executor and the
    server still answers every query — the smoke must fail that run."""
    corpus = make_corpus(n_docs=200, mean_doc_len=12, seed=3)
    index = build_index(
        corpus.emb, corpus.token_doc_ids, corpus.n_docs,
        IndexBuildConfig(n_centroids=64, kmeans_iters=2),
    )
    q, qmask, _ = make_queries(corpus, n_queries=3, seed=4)
    registry = obs.enable_metrics(obs.MetricsRegistry())
    try:
        with fault.active(FaultPlan(rates={"engine.kernel_call": 1.0})):
            with pytest.warns(UserWarning, match="reference executor"):
                run = smoke.serve_variant(
                    "materialize-dense", Retriever.from_index(index),
                    q[:2], qmask[:2], q[2], qmask[2],
                    **smoke.VARIANTS["materialize-dense"],
                )
        assert len(run.answers) == 2
        for scores, docs in run.answers:
            assert np.all(np.isfinite(scores)) and np.all(docs >= 0)
        with pytest.raises(smoke.SmokeFailure, match="fall"):
            smoke.check_no_fallback(run.server.plan, registry)
    finally:
        obs.disable_metrics()


def test_parity_check_accepts_only_ties(smoke):
    scores = np.array([5.0, 4.0, 4.0, 3.0], np.float32)
    docs = np.array([10, 11, 12, 13])
    assert smoke.check_parity("v", 0, (scores, docs), (scores, docs)) == 0
    swapped = np.array([10, 12, 11, 13])
    assert smoke.check_parity("v", 0, (scores, swapped), (scores, docs)) == 2
    # A different doc at the cutoff is a tie only if it scores like the k-th.
    tail = np.array([10, 11, 12, 99])
    assert smoke.check_parity("v", 0, (scores, tail), (scores, docs)) == 1
    with pytest.raises(smoke.SmokeFailure, match="does not tie"):
        smoke.check_parity("v", 0, (scores, np.array([11, 10, 12, 13])),
                           (scores, docs))
    off = scores * np.float32(1 + 1e-4)
    with pytest.raises(smoke.SmokeFailure, match="score at rank"):
        smoke.check_parity("v", 0, (off, docs), (scores, docs))
