"""The harness's ``correct`` at a size a CPU test can hold: a sound run
passes, and the control (the float8 reference) and faults planted in the
timed path fail.  The look for a chip is skipped; the rest of a run is
driven as on the chip (Pallas kernels in interpret mode).

    JAX_PLATFORMS=cpu python -m pytest -q chipbench/tests/test_correct.py
"""

import json
import os
import pathlib
import subprocess
import sys

import jax
import jax.numpy as jnp
import pytest

import run

DATA = pathlib.Path(__file__).resolve().parent / "data"
ROOT = DATA.parents[2]


@pytest.fixture(autouse=True)
def no_chip_needed(monkeypatch, tmp_path):
    monkeypatch.setattr(run, "require_chip",
                        lambda chips: jax.devices()[:chips])
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "jc"))


def smoke(workload="smoke.sat", seed=2**33 + 5, **kw):
    return run.run_cell(workload, seed, 3.0, False,
                        bench_file=DATA / "BENCHMARK.json", base=DATA, **kw)


@pytest.mark.parametrize("workload", ["smoke.sat", "smoke-llama.sat"])
def test_sound_run_is_correct_and_control_is_not(workload):
    res = smoke(workload, control=True)
    c = res["checks"]
    assert res["program"]["correct"], res["program"]
    assert res["program"]["max_logit_gap"] <= c["max_logit_gap"]["limit"]
    assert c["moved_requests_compared"]["value"] >= 1
    # the control in the program's place: the harness's own verdict
    assert not res["correct"], c
    assert c["max_logit_gap"]["value"] > c["max_logit_gap"]["limit"], c


def test_token_altered_where_produced_is_not_correct():
    def hook(eng):
        dec = eng._decode

        def altered(*a):
            out, *rest = dec(*a)
            return (out.at[0, 0].set((out[0, 0] + 1) % eng.cfg.vocab_size),
                    *rest)
        eng._decode = altered
    res = smoke(engine_hook=hook)
    assert not res["correct"], res["checks"]


def test_page_move_that_moves_nothing_is_not_correct():
    def hook(eng):
        move = eng._move

        def lost(k, v, src, dst, *, use_pallas):
            return move(k, v, dst, dst, use_pallas=use_pallas)
        eng._move = lost
    res = smoke(engine_hook=hook)
    assert not res["correct"], res["checks"]


def test_open_loop_cell_is_correct():
    res = smoke("smoke.rate")
    assert res["correct"], res["checks"]
    assert {"tpot_p90_ms", "setup_s"} <= set(res["metrics"])


def test_tpot_counts_every_token_after_each_requests_first():
    from chipbench import drive, measure
    steps = [drive.Step(0.0, 1.0, 3, [], [], gaps=[(0.5, 2)]),
             drive.Step(1.0, 2.0, 2, [], [], gaps=[(0.25, 1), (1.0, 1)])]
    drv = type("D", (), {"win": drive.Window(0.0, 2.0, steps)})()
    run_ = measure.Run(cfg={}, mix={"loop": "open"}, peak={}, setup_s=0.0,
                       driver=drv)
    assert sorted(run_.tpot_s()) == [0.25, 0.5, 0.5, 1.0]


def test_without_a_chip_the_command_fails_and_prints_nothing():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, "chipbench/run.py", "--workload",
         "qwen3-1.7b.docqa-sat", "--seed", "1", "--seconds", "1",
         "--trace", "0"], cwd=ROOT, env=env, capture_output=True, text=True,
        timeout=300)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "TPU" in p.stderr


def test_result_line_is_json_with_the_contract_keys():
    res = smoke()
    json.dumps(res)
    assert {"correct", "attempted", "failed", "metrics", "device"} <= set(res)
    assert list(res)[-1] == "checks"
    assert jnp.isfinite(res["checks"]["max_logit_gap"]["value"])
