"""Closed-loop mining: whole ``AcceleratedMiner(db).mine_rs(sigma,
max_len)`` jobs back to back, each on a database of its own.

A run mines ``round(seconds / expect_job_s)`` jobs, a fixed amount of
work that lasts about ``--seconds``.  Their databases, at the
configuration's sizes, are the same for every run: how much a job
mines depends on the patterns its database happens to hold (one
database gives 270 frequent patterns, the next 218), so databases drawn
from each run's seed would make ``mine_s`` swing with the seed.  The
seed draws the order of the queue.  The miner's device programs take
their shapes from each database (its longest sequence), so warm-up
mines each of them once and then empties the program's memos: the
window starts with compiled programs and with the memos of a fresh
process, which then persist from job to job as in a service working
through a queue.

The window mines the queue through; ``mine_s`` is its time over the
number of jobs.

Check: one completed job, drawn from the seed, against the reference
miner: every pattern with its support.
"""
from __future__ import annotations

import time

import numpy as np

from chipbench.lib import gen, memo, program, reference, traffic


def run(ctx) -> dict:
    from repro.mining.driver import AcceleratedMiner

    cfg, mix = ctx.config, ctx.mix
    sigma, max_len = int(cfg["sigma"]), int(cfg["max_len"])
    n_jobs = max(1, int(round(ctx.seconds / float(mix["expect_job_s"]))))
    order = np.random.default_rng(
        traffic.derive(ctx.seed, "order")).permutation(n_jobs)
    with ctx.phase("generate"):
        plain = [gen.database(cfg, traffic.derive(cfg["data"]["seed"],
                                                  "job", int(j)))
                 for j in order]
        dbs = [[program.seq(s) for s in db] for db in plain]
    if ctx.control == "drop_one":
        # the control: every job loses its last sequence
        dbs = [db[:-1] for db in dbs]

    def mine(db):
        m = AcceleratedMiner(db)
        with ctx.span("mine_rs"):
            r = m.mine_rs(sigma, max_len=max_len)
        return r, m.n_device_calls

    with ctx.phase("warmup"):
        for db in dbs:
            mine(db)
    cleared = memo.clear_program_memos()
    ctx.setup_done()

    rec = {"kind": "mine", "seconds": ctx.seconds}
    results, calls = [], 0
    with ctx.window(rec):
        t1 = time.perf_counter()
        for db in dbs:
            r, c = mine(db)
            results.append(r.patterns)
            calls += c
        elapsed = time.perf_counter() - t1
    n = len(results)
    rec.update(jobs=n, elapsed_s=elapsed, device_calls=calls,
               attempted=n, failed=0)
    rec["notes"] = [
        f"[mine] warm-up mined {n} databases and cleared {cleared} "
        f"memos; the window ran them again in {elapsed:.3f}s, "
        f"{calls} device calls, patterns per job "
        f"{[len(p) for p in results]}"]

    # ---- check one job, drawn from the seed, against the reference
    rng = np.random.default_rng(traffic.derive(ctx.seed, "check"))
    j = int(rng.integers(n)) if n else 0
    t2 = time.perf_counter()
    want = reference.mine(plain[j], sigma, max_len) if n else {}
    got = program.mined(results[j]) if n else {}
    rec["notes"].append(f"[check] job {j}: {len(got)} patterns against "
                        f"the reference's {len(want)} in "
                        f"{time.perf_counter() - t2:.3f}s")
    rec["checks"] = {
        "jobs_done": {"value": int(n == 0), "limit": 0},
        "missing_patterns": {"value": len(set(want) - set(got)),
                             "limit": 0},
        "extra_patterns": {"value": len(set(got) - set(want)), "limit": 0},
        "wrong_supports": {"value": sum(got[c] != want[c]
                                        for c in set(got) & set(want)),
                           "limit": 0},
    }
    return rec
