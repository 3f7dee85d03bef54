"""The traffic generator and the benchmark's files, on the CPU.

    JAX_PLATFORMS=cpu python -m pytest -q chipbench/tests/test_spec.py
"""

import json
import pathlib
import re

import numpy as np

from chipbench import traffic

HERE = pathlib.Path(__file__).resolve().parents[1]
ROOT = HERE.parent
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


def bench():
    return json.loads((ROOT / "BENCHMARK.json").read_text())


def test_every_seed_serves_the_same_sizes_in_each_cycle():
    mix = json.loads((HERE / "traffic" / "chat-rate.json").read_text())
    n = mix["cycle"]
    seen = set()
    for seed in (1, 2**33 + 7):
        r = traffic.Requests(mix, seed, 1000, rate=2.0)
        for _ in range(2):
            draws = [r.next() for _ in range(n)]
            seen.add((tuple(sorted(len(p) for p, _, _ in draws)),
                      tuple(sorted(o for _, o, _ in draws)),
                      tuple(sorted(round(g, 9) for _, _, g in draws))))
    assert len(seen) == 1
    prompts, outs, gaps = seen.pop()
    assert abs(np.mean(gaps) - 0.5) < 0.01


def test_quantiles_stay_in_range():
    q = traffic.quantiles({"dist": "uniform", "lo": 768, "hi": 1024}, 64)
    assert q.min() >= 768 and q.max() <= 1024
    assert abs(q.mean() - 896) < 3
    q = traffic.quantiles({"dist": "lognormal", "median": 48, "sigma": 0.7,
                           "lo": 8, "hi": 256}, 64)
    assert q.min() >= 8 and q.max() <= 256 and np.median(q) in (47, 48, 49)


def test_benchmark_names_files_and_readers():
    b = bench()
    assert set(b) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    names = [x["name"] for k in ("configs", "workloads", "end_to_end",
                                 "per_layer") for x in b[k]]
    assert len(names) == len(set(names))
    for n in names:
        assert NAME.match(n), n
    for c in b["configs"]:
        f = json.loads((ROOT / c["file"]).read_text())
        assert f["reduced"] == c["reduced"]
    cells = {w["name"] for w in b["workloads"]}
    for w in b["workloads"]:
        assert w["name"] == f"{w['config']}.{w['traffic']}"
        assert (HERE / "traffic" / f"{w['traffic']}.json").exists()
        assert (HERE / "cells" / f"{w['name']}.json").exists()
        assert w["chips"] == 1 and len(w["why"]) <= 200
    e2e = {m["name"] for m in b["end_to_end"]}
    for m in b["end_to_end"] + b["per_layer"]:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
        assert (HERE / "metrics" / f"{m['name']}.py").exists(), m["name"]
        assert set(m.get("workloads", cells)) <= cells
    for m in b["per_layer"]:
        assert m["moves"] in e2e
        moved = next(x for x in b["end_to_end"] if x["name"] == m["moves"])
        assert set(m["workloads"]) <= set(moved.get("workloads", cells))
