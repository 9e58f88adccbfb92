"""A whole benchmark run on the CPU at test sizes: the served path driven by
the harness's clients, then the comparison that decides ``correct``.

The harness's look for a chip is skipped (``require_tpu=False``); the rest
of a run is the one the chip runs. A sound run must come out correct; a
run with the float8 control in the program's place must come out not
correct, by the logit-gap limit alone; and a run whose timed path
is broken underneath (a token altered where the megastep produces it, or
a step that leaves the KV pools unchanged) must come out not correct.
"""
import json
import os
import shutil
import subprocess
import sys
import time

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path[:0] = [REPO, os.path.join(REPO, "src")]

from bench import harness  # noqa: E402

DATA = os.path.join(REPO, "tests", "bench", "data")
SECONDS = 2.0


def _cell():
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        spec = json.load(f)
    metrics = [dict(m) for m in spec["end_to_end"]]
    for m in metrics:
        m.pop("workloads", None)
    spec = {"workloads": [{"name": "tiny.agents", "config": "tiny",
                           "traffic": "tiny-agents", "chips": 1,
                           "why": "test"}],
            "end_to_end": metrics, "per_layer": []}
    return harness.load_cell("tiny.agents", spec,
                             roots=(DATA, harness.BENCH))


def _run(seed, **kw):
    return harness.run_cell(_cell(), seed, SECONDS, False,
                            t_start=time.perf_counter(), require_tpu=False,
                            **kw)


def _alter_tokens(engine):
    """A token altered where it is produced: every sampled id is shifted."""
    import jax.numpy as jnp
    mega, vocab = engine._mega, engine.cfg.vocab_size

    def altered(*args):
        ids, pools = mega(*args)
        return jnp.where(ids >= 0, (ids + 1) % vocab, ids), pools
    engine._mega = altered


def _state_unchanged(engine):
    """A step that returns its state unchanged: the KV pools it wrote are
    dropped, and the next step reads the pools as they were."""
    import jax
    import jax.numpy as jnp
    mega = engine._mega

    def unchanged(params, pools, *rest):
        keep = jax.tree.map(jnp.copy, pools)
        ids, _ = mega(params, pools, *rest)
        return ids, keep
    engine._mega = unchanged


@pytest.fixture(scope="module")
def sound():
    return _run(21)


def test_a_sound_run_is_correct_and_reports_its_metrics(sound):
    assert sound["correct"], sound["compared"]
    c = sound["compared"]
    assert c["served_checked"]["value"] >= 32
    assert c["compiles_in_window"]["value"] == 0
    assert sound["attempted"] > 0 and sound["failed"] == 0
    assert set(sound["metrics"]) >= {"out_tok_s", "ttft_p50_s", "itl_p95_s",
                                     "setup_s"}
    assert sound["device"]["platform"] == "cpu"
    assert list(sound)[-1] == "compared"


def test_the_float8_control_fails_the_gap_limit():
    """The control in the program's place: the run is not correct, by the
    same comparison a sound run passes."""
    out = _run(21, control=True)
    assert out["correct"] is False
    c = out["compared"]
    limit = c["logit_gap"]["limit"]
    assert out["program_logit_gap"] <= limit < c["logit_gap"]["value"]
    assert c["logit_gap"]["value"] >= 3 * out["program_logit_gap"]
    assert all(v["value"] <= v["limit"] for k, v in c.items()
               if v["rule"] == "<=" and k != "logit_gap")


@pytest.mark.parametrize("fault", [_alter_tokens, _state_unchanged],
                         ids=["token_altered", "state_unchanged"])
def test_a_broken_timed_path_is_not_correct(fault):
    out = _run(22, break_path=fault)
    assert out["correct"] is False
    assert out["compared"]["logit_gap"]["value"] > \
        out["compared"]["logit_gap"]["limit"]


def _cli(cwd, env_extra=None):
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env.update({"JAX_PLATFORMS": "cpu", **(env_extra or {})})
    return subprocess.run(
        [sys.executable, "-m", "bench.run", "--workload",
         "chatglm3-6b.short-chat", "--seed", "1", "--seconds", "1",
         "--trace", "0"], cwd=cwd, env=env, capture_output=True, text=True,
        timeout=300)


def test_the_command_refuses_a_machine_without_a_tpu():
    r = _cli(REPO)
    assert r.returncode != 0
    assert "no TPU" in r.stderr
    assert not r.stdout.strip()


def test_the_command_refuses_a_tree_of_only_the_benchmark(tmp_path):
    shutil.copy(os.path.join(REPO, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(REPO, "bench"), tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    r = _cli(str(tmp_path))
    assert r.returncode != 0
    assert not r.stdout.strip()
