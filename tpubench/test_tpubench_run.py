"""Whole runs of a tiny cell on the CPU, with the look for a chip skipped:
a sound run is correct, a run with the timed path broken is not, and the
control (the reference in bfloat16) fails the limit the sound run meets.
A document-sharded cell runs the same way on four virtual CPU devices,
in a process of its own (``four_shards``), and each fault of the sharded
path is caught. Also: ``run.py`` refuses a CPU, and a directory without
the program."""

from __future__ import annotations

import contextlib
import json
import os
import shutil
import subprocess
import sys
import tempfile
from pathlib import Path
from unittest import mock

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


def reference_against_program(cell) -> float:
    """The largest relative gap between the reference's top-k scores and
    the program's own reference executor's (a second witness), on two
    queries of ``cell``'s corpus."""
    import jax

    from repro.core import WarpSearchConfig
    from tpubench import reference, synth

    c = cell.config
    shards, index, mesh = run.make_corpus(cell, 4, jax.devices()[: cell.chips])
    qs, ms = synth.make_queries(c, shards, 4, 2, stream=0, active=8)
    plan = Retriever.from_index(index, mesh=mesh).plan(WarpSearchConfig(
        nprobe=c["nprobe"], k=c["k"], k_impute=c["k_impute"], t_prime=c["t_prime"], executor="reference",
    ))
    res = plan.retrieve_batch(qs, ms)
    replies = list(zip(np.asarray(res.scores), np.asarray(res.doc_ids)))
    refs = reference.run_reference(shards, c, list(zip(qs, ms)), replies)
    return max(float(np.max(np.abs(s - rs) / np.abs(rs))) for (s, _), (rs, _, _) in zip(replies, refs))


@contextlib.contextmanager
def _three_of_four_shards():
    """Shard 3's candidates never reach the merge."""
    import jax
    import jax.numpy as jnp

    from repro.core import distributed

    real = distributed.score_and_reduce

    def score_and_reduce(*args, **kw):
        top = real(*args, **kw)
        gone = jax.lax.axis_index("data") == 3
        return top._replace(scores=jnp.where(gone, -jnp.inf, top.scores), doc_ids=jnp.where(gone, -1, top.doc_ids))

    with mock.patch.object(distributed, "score_and_reduce", score_and_reduce):
        yield


@contextlib.contextmanager
def _per_shard_mse():
    """Each shard imputes m_i from its own k_impute pairs alone."""
    import jax

    from repro.core import distributed

    real = distributed.impute_mse

    def impute_mse(scores, sizes, t_prime, qmask=None):
        kk, i = scores.shape[-1] // 4, jax.lax.axis_index("data")
        own = (jax.lax.dynamic_slice_in_dim(x, i * kk, kk, axis=x.ndim - 1) for x in (scores, sizes))
        return real(*own, t_prime, qmask)

    with mock.patch.object(distributed, "impute_mse", impute_mse):
        yield


@contextlib.contextmanager
def _no_doc_start():
    """The program's index has every shard start at doc 0: its replies
    carry local doc ids."""
    import dataclasses

    import jax.numpy as jnp

    real = run.make_server

    def make_server(cell, index, mesh=None):
        return real(cell, dataclasses.replace(index, doc_start=jnp.zeros_like(index.doc_start)), mesh)

    with mock.patch.object(run, "make_server", make_server):
        yield


SHARD_FAULTS = {
    "three_of_four_shards": _three_of_four_shards,
    "per_shard_mse": _per_shard_mse,
    "no_doc_start": _no_doc_start,
}


def four_shard_readings() -> dict:
    """What the sharded tests check, read in a process with four CPU
    devices: a sound run of a four-shard tiny cell, a run under each of
    ``SHARD_FAULTS``, the reference against the program's reference
    executor, and the control's readings."""
    out = {}
    with tempfile.TemporaryDirectory() as tmp:
        # 32 centroids a shard: a shard's own k_impute best pairs cross t'
        # later than the merged ones, and documents miss query tokens.
        cell = tiny_cell(Path(tmp), "offline", shards=4, n_centroids=128, limits={"score_gap": LIMIT})
        for name, fault in {"sound": contextlib.nullcontext, **SHARD_FAULTS}.items():
            with fault():
                res = _run(cell)
            out[name] = {
                "correct": res["correct"], "attempted": res["attempted"], "failed": res["failed"],
                "count": res["device"]["count"], "score_gap": res["checks"]["score_gap"]["value"],
            }
        out["reference"] = reference_against_program(cell)
        out["control"] = control.readings(cell, 11, SECONDS, True, chip=False)
    return out


@pytest.fixture(scope="module")
def four_shards():
    env = dict(os.environ, JAX_PLATFORMS="cpu", XLA_FLAGS="--xla_force_host_platform_device_count=4")
    env["PYTHONPATH"] = os.pathsep.join([str(ROOT / "src"), str(ROOT)])
    out = subprocess.run(
        [sys.executable, "-c",
         "import json; from tpubench import test_tpubench_run as t; print(json.dumps(t.four_shard_readings()))"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=900,
    )
    assert out.returncode == 0, out.stderr[-4000:]
    return json.loads(out.stdout.strip().splitlines()[-1])


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


@pytest.mark.parametrize("n_shards", [1, 4])
def test_the_bfloat16_control_fails_the_limit(cell, n_shards, request):
    if n_shards == 1:
        row = control.readings(cell, 11, SECONDS, True, chip=False)
    else:
        row = request.getfixturevalue("four_shards")["control"]
    assert row["program"] < LIMIT < row["control"]


@pytest.mark.parametrize("n_shards", [1, 4])
def test_the_reference_is_independent_of_the_program(cell, n_shards, request):
    """The reference's top-k scores equal the program's own reference
    executor's on two queries (a second witness), one shard or four,
    without importing the program."""
    from tpubench import reference

    if n_shards == 1:
        gap = reference_against_program(cell)
    else:
        gap = request.getfixturevalue("four_shards")["reference"]
    assert gap < 1e-5
    assert "repro" not in open(reference.__file__).read()


def test_a_sound_sharded_run_is_correct(four_shards):
    res = four_shards["sound"]
    assert res["correct"] and res["failed"] == 0 and res["attempted"] > 0 and res["count"] == 4
    assert res["score_gap"] < LIMIT / 10


@pytest.mark.parametrize("fault", SHARD_FAULTS)
def test_a_fault_of_the_sharded_path_is_caught(four_shards, fault):
    assert not four_shards[fault]["correct"], four_shards[fault]


def test_the_look_for_the_kernel_finds_the_pallas_call(cell):
    """On the CPU the kernel runs interpreted, so the look counts no
    compiled kernel; it must still find the call in the plan's program."""
    import jax

    from tpubench import checks, synth

    shards, index, mesh = run.make_corpus(cell, 1, jax.devices()[:1])
    server = run.make_server(cell, index, mesh)
    q, m = synth.make_queries(cell.config, shards, 1, 8, stream=0, active=8)
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
    total, buffers, scratch = run.peak_hbm_bytes(jax.devices()[:1])
    assert temp > 0 and scratch >= temp and total == buffers + scratch
