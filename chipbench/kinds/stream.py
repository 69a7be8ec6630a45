"""Closed-loop streaming: ``StreamingBank`` over a sliding window, fed
fresh arrivals in batches of ``batch`` with ``refresh()`` after every
``refresh_every`` batches, as fast as the bank absorbs them.

A run streams ``round(seconds / expect_cycle_s)`` cycles, a fixed
amount of work that lasts about ``--seconds``.  Set-up seeds the window
with the configuration's database (``StreamingBank.from_db``: the
program mines it) and draws the arrivals from the seed.  Which programs
a refresh compiles depends on what the window holds, so warm-up streams
all of them through a second bank built the same way; the program's
memos are then emptied and a fresh bank is built for the window.

The window runs the cycles through; ``stream_arrivals_per_s`` is the
arrivals they covered over their time.

Check: after the last refresh, the window holds the last ``window``
sequences, and the frequent map equals the reference miner's on them.
"""
from __future__ import annotations

import time

from chipbench.lib import gen, memo, program, reference, traffic


def run(ctx) -> dict:
    from repro.serving.streaming import StreamingBank

    cfg, mix = ctx.config, ctx.mix
    sigma, max_len = int(cfg["sigma"]), int(cfg["max_len"])
    window, batch, every = (int(mix["window"]), int(mix["batch"]),
                            int(mix["refresh_every"]))
    per_cycle = batch * every
    n_cycles = max(1, int(round(ctx.seconds / float(mix["expect_cycle_s"]))))
    with ctx.phase("generate"):
        seed_db = gen.database(cfg, cfg["data"]["seed"])[-window:]
        arrivals = traffic.sequences(cfg, n_cycles * per_cycle, ctx.seed)
        p_seed = [program.seq(s) for s in seed_db]
        p_arr = [program.seq(s) for s in arrivals]

    def bank():
        return StreamingBank.from_db(p_seed, minsup=sigma, window=window,
                                     max_len=max_len)

    def cycle(sb, c, prev=None):
        lo = c * per_cycle
        for b in range(every):
            with ctx.span("observe"):
                sb.observe(p_arr[lo + b * batch: lo + (b + 1) * batch])
        with ctx.span("refresh"):
            t = time.perf_counter()
            if ctx.control == "stale":
                # the control: no reconcile; the map of the last one
                out = prev if prev is not None else sb.frequent()
            else:
                out = sb.refresh()
            return out, time.perf_counter() - t

    with ctx.phase("warmup"):
        warm = bank()
        for c in range(n_cycles):
            cycle(warm, c)
        del warm
    with ctx.phase("bank"):
        sb = bank()
    cleared = memo.clear_program_memos()
    ctx.setup_done()

    rec = {"kind": "stream", "seconds": ctx.seconds}
    done, refresh_s, last = 0, 0.0, None
    with ctx.window(rec):
        t1 = time.perf_counter()
        for done in range(1, n_cycles + 1):
            last, dt = cycle(sb, done - 1, last)
            refresh_s += dt
        elapsed = time.perf_counter() - t1
    covered = done * per_cycle
    rec.update(arrivals=covered, elapsed_s=elapsed, refresh_s=refresh_s,
               attempted=covered, failed=0)
    rec["notes"] = [
        f"[stream] warm-up ran {n_cycles} cycles and cleared {cleared} memos; "
        f"the window ran {done} cycles of {per_cycle} arrivals in "
        f"{elapsed:.3f}s, refresh {refresh_s:.3f}s; stats "
        f"{dict(sb.stats)}"]

    # ---- check the last refresh against the reference
    expect = (list(seed_db) + list(arrivals[:covered]))[-window:]
    seqs_off = int([program.plain(s) for s in sb.window_seqs] != expect)
    t2 = time.perf_counter()
    want = reference.mine(expect, sigma, max_len) if done else {}
    got = program.mined(last) if done else {}
    rec["notes"].append(f"[check] {len(got)} frequent against the "
                        f"reference's {len(want)} in "
                        f"{time.perf_counter() - t2:.3f}s")
    rec["checks"] = {
        "cycles_done": {"value": int(done == 0), "limit": 0},
        "window_off": {"value": seqs_off, "limit": 0},
        "missing_patterns": {"value": len(set(want) - set(got)),
                             "limit": 0},
        "extra_patterns": {"value": len(set(got) - set(want)), "limit": 0},
        "wrong_supports": {"value": sum(got[c] != want[c]
                                        for c in set(got) & set(want)),
                           "limit": 0},
    }
    return rec
