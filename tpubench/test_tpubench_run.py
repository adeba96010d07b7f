"""Whole runs of a tiny cell on the CPU, with the look for a chip skipped:
a sound run is correct, a run with the timed path broken is not, and the
control (the reference in bfloat16) fails the limit the sound run meets.
Also: ``run.py`` refuses a CPU, and a directory without the program."""

from __future__ import annotations

import os
import shutil
import subprocess
import sys

import numpy as np
import pytest

from repro.core import Retriever, TopKResult
from repro.core.retriever import SearchPlan
from repro.serving import RetrievalServer
from tpubench import control, run, spec
from tpubench.tiny import tiny_cell

ROOT = spec.HERE.parent
SECONDS = 0.5
LIMIT = 1e-4


@pytest.fixture
def cell(tmp_path):
    return tiny_cell(tmp_path, "offline", limits={"score_gap": LIMIT})


def _run(cell, seed=2**32 + 5, trace=False):
    return run.run_cell(cell, seed, SECONDS, trace, chip=False)


def test_sound_run_is_correct(cell):
    res = _run(cell)
    assert res["correct"] and res["failed"] == 0 and res["attempted"] > 0
    assert list(res)[-1] == "checks"
    assert res["checks"]["score_gap"]["value"] < LIMIT / 10
    assert set(res["metrics"]) == {"qps", "peak_hbm_gb", "setup_s"}
    assert res["device"]["count"] == 1


def test_sound_steady_run_is_correct(tmp_path):
    res = _run(tiny_cell(tmp_path, "steady", limits={"score_gap": LIMIT}), trace=True)
    assert res["correct"] and res["failed"] == 0
    assert {"queue_wait_ms.steady", "batch_fill.steady"} <= set(res["metrics"])


def _broken_batch(monkeypatch, alter):
    real = SearchPlan.retrieve_batch

    def retrieve_batch(self, q, qmask=None):
        out = real(self, q, qmask)
        s, d = alter(np.asarray(out.scores), np.asarray(out.doc_ids))
        return TopKResult(scores=s, doc_ids=d)

    monkeypatch.setattr(SearchPlan, "retrieve_batch", retrieve_batch)


def test_an_answer_altered_where_it_is_produced_is_caught(cell, monkeypatch):
    n_docs = cell.config["n_docs"]

    def alter(s, d):
        d = d.copy()
        d[:, 0] = (d[:, 0] + 1) % n_docs
        return s, d

    _broken_batch(monkeypatch, alter)
    assert not _run(cell)["correct"]


def test_half_the_batch_left_out_is_caught(cell, monkeypatch):
    """The program scores half of each batch and hands those answers to
    the other half too."""

    def alter(s, d):
        h = s.shape[0] // 2
        return np.concatenate([s[:h], s[:h]]), np.concatenate([d[:h], d[:h]])

    _broken_batch(monkeypatch, alter)
    assert not _run(cell)["correct"]


def test_replies_that_never_come_are_caught(cell, monkeypatch):
    real = RetrievalServer.step

    def step(self, *, force=False):
        served = real(self, force=force)
        for rid in list(self._results)[::2]:  # dropped: pending for ever
            self._results.pop(rid)
            self._inflight.add(rid)
        return served

    monkeypatch.setattr(RetrievalServer, "step", step)
    monkeypatch.setattr(run.traffic, "GRACE_S", 1.0)
    res = _run(cell)
    assert not res["correct"] and res["checks"]["missing_replies"]["value"] > 0


def test_the_bfloat16_control_fails_the_limit(cell):
    row = control.readings(cell, 11, SECONDS, True, chip=False)
    assert row["program"] < LIMIT < row["control"]


def test_the_reference_is_independent_of_the_program(cell):
    """The reference's top-k equals the program's own reference executor
    on one query (a second witness), without importing the program."""
    from repro.core import WarpIndex, WarpSearchConfig
    from tpubench import reference, synth

    c = cell.config
    index, sizes = synth.make_index(c, 4, WarpIndex)
    qs, ms = synth.make_queries(c, index, sizes, 4, 2, stream=0, active=8)
    plan = Retriever.from_index(index).plan(WarpSearchConfig(
        nprobe=c["nprobe"], k=c["k"], k_impute=c["k_impute"], t_prime=c["t_prime"], executor="reference",
    ))
    res = plan.retrieve_batch(qs, ms)
    replies = list(zip(np.asarray(res.scores), np.asarray(res.doc_ids)))
    refs = reference.run_reference(index, c, list(zip(qs, ms)), replies)
    for (s, d), (rs, rd, _) in zip(replies, refs):
        np.testing.assert_allclose(s, rs, rtol=1e-5)
    assert "repro" not in open(reference.__file__).read()


def test_the_look_for_the_kernel_finds_the_pallas_call(cell):
    """On the CPU the kernel runs interpreted, so the look counts no
    compiled kernel; it must still find the call in the plan's program."""
    import jax

    from repro.core import WarpIndex
    from tpubench import checks, synth

    index, sizes = synth.make_index(cell.config, 1, WarpIndex)
    server = run.make_server(cell, index)
    q, m = synth.make_queries(cell.config, index, sizes, 1, 8, stream=0, active=8)
    calls = list(checks._pallas_calls(jax.make_jaxpr(server.plan.retrieve_batch)(q, m).jaxpr))
    assert len(calls) >= 1 and all(c.params["interpret"] for c in calls)
    assert checks.kernel_calls(server, q, m) == 0
    with pytest.raises(checks.RunFailure):
        checks.check_kernel_in_program(server, q, m)


def _cli(cwd, env_extra=None):
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env.update(JAX_PLATFORMS="cpu", **(env_extra or {}))
    return subprocess.run(
        [sys.executable, "tpubench/run.py", "--workload", "lifestyle.offline", "--seed", "3",
         "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=120,
    )


def test_run_refuses_a_cpu():
    out = _cli(ROOT)
    assert out.returncode != 0 and out.stdout.strip() == ""
    assert "no TPU" in out.stderr


def test_run_refuses_a_directory_without_the_program(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(spec.HERE, tmp_path / "tpubench", ignore=shutil.ignore_patterns("__pycache__"))
    out = _cli(tmp_path)
    assert out.returncode != 0 and out.stdout.strip() == ""


def test_peak_hbm_counts_program_scratch():
    """A program's scratch is not a live buffer; the peak adds the largest
    scratch among the programs loaded on the device."""
    import jax
    import jax.numpy as jnp

    f = jax.jit(lambda x: jnp.sort(x * 2.0)[::-1].cumsum())
    x = jnp.ones((1 << 16,), jnp.float32)
    f(x).block_until_ready()
    temp = f.lower(x).compile().memory_analysis().temp_size_in_bytes
    total, buffers, scratch = run.peak_hbm_bytes(jax.devices()[0])
    assert temp > 0 and scratch >= temp and total == buffers + scratch
