"""A whole fleet cell on the CPU at test sizes: two one-chip engines
behind the fleet router, on two host devices that a child process asks
XLA for, driven by the harness's clients with the look for a chip skipped
(``require_tpu=False``), then the comparison that decides ``correct``.

A sound run must come out correct and record each engine's part of every
step; the float8 control and a token altered where one member's megastep
produces it must come out not correct."""
import json
import os
import subprocess
import sys
import time

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
DATA = os.path.join(REPO, "tests", "bench", "data")
SECONDS = 2.0


def _cell(harness):
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        spec = json.load(f)
    e2e = [dict(m) for m in spec["end_to_end"]]
    per = [{"name": n, "unit": "frac", "moves": "out_tok_s"}
           for n in ("fleet.tokens_imbalance", "fleet.tenant_colocation",
                     "kv_in_use_frac")]
    for m in e2e + per:
        m["workloads"] = ["tiny.fleet"]
    spec = {"workloads": [{"name": "tiny.fleet", "config": "tiny",
                           "traffic": "tiny-fleet", "chips": 2,
                           "why": "test"}],
            "end_to_end": e2e, "per_layer": per}
    return harness.load_cell("tiny.fleet", spec,
                             roots=(DATA, harness.BENCH))


def _alter_member_1(engine):
    """A token altered where member 1's megastep produces it."""
    if engine.name != "engine1":
        return
    import jax.numpy as jnp
    mega, vocab = engine._mega, engine.cfg.vocab_size

    def altered(*args):
        ids, pools = mega(*args)
        return jnp.where(ids >= 0, (ids + 1) % vocab, ids), pools
    engine._mega = altered


def child():
    """Three runs in one process; one JSON line of what the tests read."""
    sys.path[:0] = [REPO, os.path.join(REPO, "src")]
    from bench import harness
    recs = []
    init = harness.Recorder.__init__

    def keep(self, *a, **k):
        init(self, *a, **k)
        recs.append(self)
    harness.Recorder.__init__ = keep
    cell = _cell(harness)

    def run(seed, **kw):
        return harness.run_cell(cell, seed, SECONDS, kw.pop("trace", False),
                                t_start=time.perf_counter(),
                                require_tpu=False, **kw)
    out = {"sound": run(31, trace=True)}
    out["members"] = sorted({s.get("member") for s in recs[0].steps})
    out["control"] = run(31, control=True)
    out["altered"] = run(32, break_path=_alter_member_1)
    print(json.dumps(out), flush=True)


@pytest.fixture(scope="module")
def runs():
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env.update({"JAX_PLATFORMS": "cpu",
                "XLA_FLAGS": (env.get("XLA_FLAGS", "") + " --xla_force_"
                              "host_platform_device_count=2").strip()})
    r = subprocess.run([sys.executable, os.path.abspath(__file__)],
                       cwd=REPO, env=env, capture_output=True, text=True,
                       timeout=600)
    assert r.returncode == 0, r.stderr[-4000:]
    return json.loads(r.stdout.strip().splitlines()[-1])


def test_a_sound_fleet_run_is_correct_and_records_each_member(runs):
    out = runs["sound"]
    assert out["correct"], out["compared"]
    assert out["compared"]["served_checked"]["value"] >= 32
    assert out["failed"] == 0 and out["attempted"] > 0
    assert out["device"]["count"] == 2
    assert runs["members"] == [0, 1]
    m = out["metrics"]
    assert m["fleet.tokens_imbalance"]["value"] >= 1.0
    assert 0.5 <= m["fleet.tenant_colocation"]["value"] <= 1.0
    assert "kv_in_use_frac" in m


def test_the_float8_control_fails_in_a_fleet_cell(runs):
    out = runs["control"]
    c = out["compared"]
    assert out["correct"] is False
    assert out["program_logit_gap"] <= c["logit_gap"]["limit"] \
        < c["logit_gap"]["value"]


def test_a_token_altered_on_one_member_is_not_correct(runs):
    out = runs["altered"]
    assert out["correct"] is False
    assert out["compared"]["logit_gap"]["value"] > \
        out["compared"]["logit_gap"]["limit"]


if __name__ == "__main__":
    child()
