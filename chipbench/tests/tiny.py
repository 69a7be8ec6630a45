"""Tiny cells for the CPU tests: the benchmark's drivers at sizes a test
run can hold, with the harness's look for a chip skipped."""
from __future__ import annotations

import argparse
import json
import time

from chipbench.lib import gen, harness, reference

SIGMA, MAX_LEN, DB = 6, 3, 60


def config(tmp_path, name="table3") -> dict:
    """A configuration like ``name``'s, cut to ``DB`` sequences, with a
    reference bank written under ``tmp_path``."""
    cfg = harness.load_json(harness.BENCH / "configs" / f"{name}.json")
    cfg["data"]["params"][cfg["data"]["size_key"]] = DB
    cfg["sigma"], cfg["max_len"] = SIGMA, MAX_LEN
    db = gen.database(cfg, cfg["data"]["seed"])
    bank = reference.mine(db, SIGMA, MAX_LEN)
    path = tmp_path / f"{name}.bank.json"
    path.write_text(json.dumps({"patterns": [[c, s]
                                             for c, s in bank.items()]}))
    cfg["bank"] = str(path)
    cfg["check"]["sample"] = 8
    return cfg


MIXES = {
    "serve": {"kind": "serve", "arrivals": "poisson", "rate_per_s": 8.0},
    "mine": {"kind": "mine", "expect_job_s": 1.0},
    "stream": {"kind": "stream", "window": DB, "batch": 5,
               "refresh_every": 2, "expect_cycle_s": 1.0},
}


# each kind's end-to-end metrics, as a cell of that kind reports them
END_TO_END = {
    "serve": [("serve_p95_s", "s"), ("serve_qps", "queries/s")],
    "mine": [("mine_s", "s")],
    "stream": [("stream_arrivals_per_s", "arrivals/s")],
}


def run(cfg, kind, seconds=1.0, seed=2 ** 31 + 7, control=None,
        trace=0) -> dict:
    """One run of a tiny cell on the CPU; returns the result line."""
    import jax
    name = f"tiny.{kind}"
    spec = {"workload": {"name": name, "config": cfg["name"],
                         "traffic": kind, "chips": 1},
            "config": cfg, "mix": dict(MIXES[kind]),
            "end_to_end": [{"name": n, "unit": u} for n, u in
                           END_TO_END[kind] + [("setup_s", "s")]],
            "per_layer": []}
    args = argparse.Namespace(workload=name, seed=seed,
                              seconds=seconds, trace=trace, control=control)
    return harness.run(args, time.perf_counter(), jax=jax,
                       devices=jax.devices(), spec=spec)
