"""Chip benchmark of the paged serving engine.

    python chipbench/run.py --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1>

A cell (``BENCHMARK.json`` ``workloads``) is one configuration
(``chipbench/configs/<config>.json``: the published sizes, the program's
arch and overrides, the engine's pool geometry) under one traffic mix
(``chipbench/traffic/<mix>.json``).  Its limits for ``correct``, and an
open-loop cell's request rate, are in ``chipbench/cells/<cell>.json``;
each metric is read by
``chipbench/metrics/<metric>.py``.  A new cell, configuration, mix or
metric is a new file plus an entry in ``BENCHMARK.json``.

The run needs a TPU with as many chips as the cell asks for, and exits
non-zero with no result on any other backend.  It makes the weights from
the seed on the device, builds ``PagedServingEngine`` (Mosaic kernels, the
engine's own AOT warm-up, plus the page-move buckets the cleaner can
dispatch), serves warm-up traffic, then measures ``--seconds`` of traffic.
With ``--trace 1`` the window is traced by the JAX profiler and the
per-layer metrics are printed instead of the end-to-end ones.  After the
window the engine is freed and a sample of the served requests is checked
against the plain reference (``chipbench/check.py``).  The last line of
standard output is one JSON object; the numbers compared, each with its
limit, are the last lines of standard error and the result's last key.
``CHIPBENCH_KEEP_TRACE=<dir>`` keeps a ``--trace 1`` run's profile there
(``chipbench/tests/data/trace`` was recorded so).
"""

from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import pathlib  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

HERE = pathlib.Path(__file__).resolve().parent
ROOT = HERE.parent
sys.path.insert(0, str(ROOT))
sys.path.insert(0, str(ROOT / "src"))


class NoChip(SystemExit):
    pass


def load_json(path: pathlib.Path) -> dict:
    with open(path) as f:
        return json.load(f)


def load_cell(workload: str, bench_file: pathlib.Path = ROOT /
              "BENCHMARK.json", base: pathlib.Path = HERE) -> dict:
    """The cell's entry with its configuration, traffic mix and limits
    (mixes and limits are looked up under ``base``)."""
    bench = load_json(bench_file)
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise SystemExit(f"chipbench: no workload {workload!r} in "
                         f"BENCHMARK.json")
    cell = cells[workload]
    conf = {c["name"]: c for c in bench["configs"]}[cell["config"]]
    return dict(
        bench=bench, cell=cell,
        cfg=load_json(ROOT / conf["file"]),
        mix=load_json(base / "traffic" / f"{cell['traffic']}.json"),
        limits=load_json(base / "cells" / f"{workload}.json"))


def metrics_of(bench: dict, workload: str, kind: str) -> list[dict]:
    return [m for m in bench[kind]
            if workload in m.get("workloads", [workload])]


def reader(name: str):
    spec = importlib.util.spec_from_file_location(
        f"chipbench.metrics.{name}", HERE / "metrics" / f"{name}.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def setup_jax():
    """Persistent compile cache at a fixed path in the checkout (or where
    ``JAX_COMPILATION_CACHE_DIR`` says), caching every executable."""
    import jax
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(
        ROOT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return jax


def require_chip(chips: int):
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu" or len(devs) < chips:
        raise NoChip(f"chipbench: the cell needs {chips} TPU chip(s); JAX "
                     f"found {len(devs)} {devs[0].platform!r} device(s). "
                     f"Nothing was run.")
    return devs[:chips]


def build_model(cfg: dict):
    from repro.configs import get_config
    from repro.models import Model
    prog = cfg["program"]
    mc = get_config(prog["arch"]).with_(**prog.get("overrides", {}))
    want = dict(d_model=cfg["hidden_size"],
                n_heads=cfg["num_attention_heads"],
                n_kv_heads=cfg["num_key_value_heads"],
                hd=cfg.get("head_dim") or cfg["hidden_size"]
                // cfg["num_attention_heads"],
                d_ff=cfg["intermediate_size"], vocab_size=cfg["vocab_size"],
                n_layers=cfg["num_hidden_layers"],
                tie_embeddings=bool(cfg.get("tie_word_embeddings", False)),
                qk_norm=cfg.get("model_type") == "qwen3",
                rope_theta=float(cfg["rope_theta"]))
    bad = {k: (getattr(mc, k), v) for k, v in want.items()
           if getattr(mc, k) != v}
    if bad:
        raise SystemExit(f"chipbench: the program's config departs from the "
                         f"configuration file: {bad}")
    return Model(mc)


def check_tree(model, w) -> None:
    import jax
    want = jax.tree.map(lambda s: (s.shape, str(s.dtype)), model.abstract())
    got = jax.tree.map(lambda a: (a.shape, str(a.dtype)), w)
    if want != got:
        raise SystemExit("chipbench: the weights' tree does not match the "
                         "model's parameters")


def warm_shapes(eng) -> None:
    """Compile in set-up what the engine's own warm-up leaves to the first
    request that needs it: the page-move buckets a synchronous compaction
    can dispatch (plans are padded to powers of two, at most
    ``compact_batch`` victim slabs of pages each, twice over for plans that
    chain; the engine warms them only with asynchronous compaction), and
    the eager reshape of each prompt bucket (``toks[None]``) before its
    prefill.  Trash to trash moves write nothing served."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    if not eng.async_compaction:
        b = 1
        while b <= 2 * eng.pool.S * eng.pool.compact_batch:
            t = np.full(b, eng.trash_page, np.int32)
            eng.k_pools, eng.v_pools = eng._move(
                eng.k_pools, eng.v_pools, eng._put_rep(t), eng._put_rep(t),
                use_pallas=eng.use_pallas)
            b *= 2
    tb = eng.page_T
    while tb < 2 * eng.max_pages_per_seq * eng.page_T:
        jnp.zeros(tb, jnp.int32)[None].block_until_ready()
        tb *= 2
    jax.block_until_ready(eng.k_pools)


def build_engine(cfg: dict, seed: int):
    """The system under test as every run of a configuration sets it up:
    the seeded weights on the device and ``PagedServingEngine`` over them,
    warmed for every shape the traffic can reach.  Returns (engine,
    weights)."""
    from chipbench import weights
    from repro.serving import PagedServingEngine
    model = build_model(cfg)
    w = weights.make_weights(cfg, seed)
    check_tree(model, w)
    eng = PagedServingEngine(model, params=w, warmup=True, **cfg["engine"])
    warm_shapes(eng)
    return eng, w


def verdict(checks: dict) -> bool:
    return all((c["value"] <= c["limit"]) if c["at_most"]
               else (c["value"] >= c["limit"]) for c in checks.values())


def run_cell(workload: str, seed: int, seconds: float, trace: bool, *,
             control: bool = False, t_start: float | None = None,
             engine_hook=None, bench_file: pathlib.Path | None = None,
             base: pathlib.Path = HERE) -> dict:
    """One run of a cell; returns the result object.  With ``control`` the
    float8 reference takes the program's place in the comparison, so
    ``correct`` is the control's verdict; the program's own reading and
    verdict are kept under ``program``."""
    t_start = T_PROCESS if t_start is None else t_start
    spec = load_cell(workload, bench_file or ROOT / "BENCHMARK.json", base)
    bench, cell, cfg, mix, lim = (spec[k] for k in
                                  ("bench", "cell", "cfg", "mix", "limits"))
    jax = setup_jax()
    devs = require_chip(int(cell["chips"]))

    from chipbench import check, drive, measure, trace as tr

    peaks = load_json(base / "peaks.json")
    kind = devs[0].device_kind
    if kind not in peaks:
        raise SystemExit(f"chipbench: no peaks for device kind {kind!r}")
    eng, w = build_engine(cfg, seed)
    if engine_hook is not None:
        engine_hook(eng)
    setup_s = time.perf_counter() - t_start

    rate = float(lim.get("rate_per_s", 0.0))
    if mix["loop"] == "open" and rate <= 0:
        raise SystemExit(f"chipbench: open-loop cell {workload!r} states "
                         f"no rate_per_s in its cell file")
    drv = drive.Driver(eng, mix, seed, rate)
    tdir = tempfile.mkdtemp(prefix="chipbench-trace-") if trace else None
    win = drv.run(float(mix["warmup_s"]), seconds, tdir)

    mem = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
              for d in devs)
    reduced = None
    if trace:
        reduced = tr.reduce_trace(tdir, load_json(base / "kernels.json"))
        keep = os.environ.get("CHIPBENCH_KEEP_TRACE")
        if keep:
            shutil.copytree(tdir, keep, dirs_exist_ok=True)
        shutil.rmtree(tdir, ignore_errors=True)

    finished = dict(eng.finished)
    pick = check.sample(finished, drv.by_rid, drv.moved, win.t0, win.t1,
                        seed, int(lim["min_tokens"]),
                        int(lim["max_requests"]))
    reqs = [(drv.by_rid[r].prompt, finished[r]) for r in pick]
    bad = sum(len(out) != drv.by_rid[r].n_out
              or not all(0 <= t < cfg["vocab_size"] for t in out)
              for r, (_, out) in zip(pick, reqs))
    n_moved = sum(r in drv.moved for r in pick)
    run = measure.Run(cfg=cfg, mix=mix, peak=peaks[kind], setup_s=setup_s,
                      driver=drv, trace=reduced)
    kinds = "per_layer" if trace else "end_to_end"
    out = {}
    for m in metrics_of(bench, workload, kinds):
        v = reader(m["name"])(run)
        if v is not None:
            out[m["name"]] = {"value": float(v), "unit": m["unit"]}
    arrivals = (run.window_arrivals() if run.open_loop else
                [q for q in drv.by_rid.values()
                 if win.t0 <= q.submit < win.t1])
    failed = sum(q.first is None for q in arrivals) if run.open_loop else 0
    counts = dict(window_tokens=run.tokens,
                  window_dispatches=run.counter("dispatches"),
                  window_compactions=run.counter("compactions"),
                  window_blocks_moved=run.counter("blocks_moved"),
                  window_compiles=drv.window_compiles,
                  window_step_max_s=max((s.t1 - s.t0 for s in run.steps),
                                        default=0.0),
                  window_gc_s=drv.window_gc_s,
                  window_gc_max_s=drv.window_gc_max_s,
                  requests_moved=len(drv.moved))

    # the program's state goes before the reference runs
    drv.eng = None
    del eng, drv.reqs
    gc.collect()
    cmp = check.compare(w, cfg, reqs, control=control)
    checks = {
        "max_logit_gap": {"value": cmp["max_logit_gap"],
                          "limit": lim["max_logit_gap"], "at_most": True},
        "tokens_compared": {"value": sum(len(o) for _, o in reqs),
                            "limit": int(lim["min_tokens"]) // 2,
                            "at_most": False},
        "wrong_length_or_id": {"value": bad, "limit": 0, "at_most": True},
    }
    if lim.get("expect_moves"):
        checks["moved_requests_compared"] = {"value": n_moved, "limit": 1,
                                             "at_most": False}
    program = None
    if control:
        program = {"max_logit_gap": cmp["max_logit_gap"],
                   "correct": verdict(checks)}
        checks["max_logit_gap"] = dict(checks["max_logit_gap"],
                                       value=cmp["control_gap"])
    correct = verdict(checks)
    device = {"platform": devs[0].platform, "kind": kind,
              "count": len(devs), "memory_peak_bytes": int(mem)}
    res = {"correct": bool(correct), "attempted": len(arrivals),
           "failed": int(failed), "metrics": out, "device": device}
    if reduced is not None:
        device["busy_s"] = reduced["busy_s"]
        device["window_s"] = reduced["window_s"]
        res["breakdown"] = {"device_ops": reduced["device_ops"],
                            "idle_gaps": reduced["idle_gaps"]}
        res["trace_kernels"] = {k: [reduced["kernel_s"][k],
                                    reduced["kernel_events"][k]]
                                for k in reduced["kernel_s"]}
    res["counts"] = counts
    if program is not None:
        res["program"] = program
    res["checks"] = {k: {"value": c["value"], "limit": c["limit"]}
                     for k, c in checks.items()}
    return res


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        res = run_cell(args.workload, args.seed, args.seconds,
                       bool(args.trace))
    except NoChip as e:
        print(e, file=sys.stderr)
        return 3
    for k, c in res["checks"].items():
        print(f"check {k} {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(res), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
