"""Tensor-parallel sharded megastep (DESIGN.md §13): end-to-end parity.

Every device-backed check runs in ONE subprocess
(``tests/_sharded_driver.py``) because XLA's virtual device count must be
forced before jax initialises — and this pytest process imported jax long
ago. The driver emits a single JSON report; the tests here are assertions
over it, plus in-process mesh-validation checks that need no devices.

Contracts under test:
  * TP=1 mesh is BITWISE identical to the single-device engine (tokens and
    live pool contents) — the head permutation is the identity at tp=1 and
    a 1-shard psum is the identity;
  * TP=2/4 reproduce the single-device greedy tokens exactly at f32 over a
    multi-turn (submit+retain, extend) session;
  * still exactly ONE jitted dispatch per step, with the per-step host
    transfer unchanged (one int32 per batch row — logits reduce in-jit);
  * hibernation payloads are mesh-shape-agnostic: hibernate at TP=2, wake
    at TP=4, continue bit-exactly;
  * the budget pack's pow2 recompile guard holds under a mesh;
  * mesh-shape mistakes surface as ValueError/SystemExit, never shard_map
    tracebacks.
"""
import argparse

import pytest


@pytest.fixture(scope="module")
def report(sharded_report):
    # the driver run is session-scoped (tests/conftest.py) so test_fleet's
    # cross-mesh failover assertions share the same subprocess
    return sharded_report


def test_driver_forced_four_devices(report):
    assert report["devices"] == 4


def test_tp1_bitwise_identical_to_single_device(report):
    assert report["tp1"]["tokens_equal"], (
        report["tp1"]["tokens"], report["ref_tokens"])
    assert report["tp1"]["pools_bitwise"], \
        "TP=1 mesh must leave bit-identical KV pools (excluding the null " \
        "block) — the head permutation is the identity at tp=1"


def test_tp2_tp4_token_parity(report):
    for tp in (2, 4):
        row = report[f"tp{tp}"]
        assert row["tp"] == tp
        assert row["tokens_equal"], (tp, row["tokens"],
                                     report["ref_tokens"])


def test_one_dispatch_and_flat_host_transfer(report):
    base = report["tp1"]["host_transfer_bytes_per_step"]
    for tp in (1, 2, 4):
        row = report[f"tp{tp}"]
        assert row["jit_dispatches_per_step"] == 1.0, (tp, row)
        # one sampled int32 per batch row, regardless of mesh width
        assert row["host_transfer_bytes_per_step"] == base == 4 * 4, (
            tp, row)


def test_hibernate_tp2_wake_tp4_bit_exact(report):
    h = report["hibernate"]
    assert h["stored_after_hibernate"] == 1, \
        "hibernate must land in the SHARED swap store (SwapManager must " \
        "not truthiness-test an empty KVSwapStore into a private one)"
    assert h["turn1_equal"]
    assert h["turn2_equal"], (h["turn2"], report["ref_tokens"][8:])


def test_bucket_recompile_guard_under_mesh(report):
    g = report["bucket_guard"]
    assert g["within"], (g["trace_buckets"], g["bucket_set"])
    assert g["jit_dispatches_per_step"] == 1.0


# ---------------------------------------------------------------------------
# Mesh validation: in-process, no devices needed — these must raise BEFORE
# any shard_map traces, as ValueError (engine) / SystemExit (CLI).
# ---------------------------------------------------------------------------

class FakeMesh:
    axis_names = ("tp",)

    def __init__(self, shape):
        self.shape = shape


def _smoke_cfg():
    from repro.configs import get_smoke_config
    return get_smoke_config("gemma-2b").replace(remat=False)


def test_engine_rejects_mesh_without_tp_axis():
    from repro.serving import PagedInferenceEngine
    with pytest.raises(ValueError, match="'tp' axis"):
        PagedInferenceEngine(_smoke_cfg(), None,
                             mesh=FakeMesh({"model": 2}))


def test_engine_rejects_indivisible_tp():
    from repro.serving import PagedInferenceEngine
    # smoke gemma-2b is MQA (hkv=1): nothing above tp=1 divides it
    with pytest.raises(ValueError, match="n_kv_heads"):
        PagedInferenceEngine(_smoke_cfg(), None, mesh=FakeMesh({"tp": 2}))


def test_serve_cli_mesh_errors_are_systemexit():
    from repro.launch.serve import build_mesh, parse_mesh_spec

    assert parse_mesh_spec("tp=4") == 4
    with pytest.raises(ValueError, match="expected tp=N"):
        parse_mesh_spec("dp=2")
    with pytest.raises(ValueError, match="integer"):
        parse_mesh_spec("tp=two")

    cfg = _smoke_cfg()
    with pytest.raises(SystemExit, match="requires --paged"):
        build_mesh(cfg, argparse.Namespace(mesh="tp=2", paged=False))
    with pytest.raises(SystemExit, match="invalid --mesh"):
        build_mesh(cfg, argparse.Namespace(mesh="dp=2", paged=True))
    with pytest.raises(SystemExit, match="invalid --mesh"):
        # hkv=1: tp=2 can't divide it — still a CLI error, not a traceback
        build_mesh(cfg, argparse.Namespace(mesh="tp=2", paged=True))
    # no --mesh at all (and Namespaces predating the flag): no mesh
    assert build_mesh(cfg, argparse.Namespace(mesh=None, paged=True)) is None
    assert build_mesh(cfg, argparse.Namespace(paged=True)) is None
