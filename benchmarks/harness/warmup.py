"""Warm-up: walk the shapes a cell's traffic can reach, before the window.

``serve`` compiles one program per (token bucket, sequence bucket) of a
prefill or mixed step and one K-step decode window per sequence bucket
(``runtime/batch.py`` ``BucketSpec``; the buckets are rebuilt here from
``serve``'s flags because the server does not report them).

Admission races with the step loop: ``LocalRunner`` takes one plain lock
for ``submit`` and for a whole step, and the loop re-takes it a few
microseconds after releasing it, so while the engine is busy a new
request waits for a lucky gap - seconds on average, minutes at worst
(chip call 2 of PR 24: 33 sequential admissions against running decodes
took 0.2-11 s each and one 164 s). Only an *idle* engine admits at once.
So the walk keeps the engine idle between waves: a "blocker" prompt is
admitted alone and holds the lock for one long step, the wave's ``s``
requests of ``t / s`` tokens queue up behind it and are let in together
when it ends, run one step of ``t`` tokens x ``s`` rows and are gone
(``max_tokens`` 1). A wave that the race splits lands in two smaller
buckets of the same lattice, which have waves of their own. What the
window still has to build or load is counted (``compiles_in_window``).
"""

from __future__ import annotations

import asyncio

from benchmarks.harness import loadgen

DECODE_K = 8   # engine.ADAPTIVE_DECODE_LOOKAHEAD


def buckets(max_value: int, floor: int = 8) -> list[int]:
    out, b = [], floor
    while b < max_value:
        out.append(b)
        b *= 2
    out.append(max_value)
    return out


def seq_buckets(max_batch_size: int) -> list[int]:
    seq = buckets(max_batch_size)
    tail = seq[-1]
    if tail & (tail - 1):
        pow2 = 1 << (tail - 1).bit_length()
        if pow2 <= tail + tail // 4:
            seq[-1] = pow2
    return seq


def plan(traffic: dict, sizes: dict) -> list[dict]:
    """The waves of a cell: ``{"n", "length", "max_tokens"}`` each."""
    hint = traffic.get("warmup") or {}
    s_all = seq_buckets(sizes["max_batch_size"])
    t_all = buckets(sizes["max_num_tokens_per_batch"])
    s_list = [s for s in s_all if s in hint.get("seq_buckets", s_all)] or s_all
    t_lo = hint.get("token_bucket_min", 0)
    waves = []
    for s in s_list:
        n = min(s, sizes["max_batch_size"])
        for t in t_all:
            if t < max(t_lo, n) or t // n > sizes["prefill_chunk_size"]:
                continue
            waves.append({"n": n, "length": t // n, "max_tokens": 1})
        # The decode window of this sequence bucket: rows that start
        # together stay together for two windows, then the engine is idle.
        waves.append({"n": n, "length": max(1, max(t_lo, 8 * n) // n),
                      "max_tokens": 2 * DECODE_K + 1})
    return waves


async def _wave(http, base: str, traffic: loadgen.Traffic, w: dict,
                blocker_tokens: int) -> list[loadgen.Result]:
    def job(length, max_tokens):
        r = loadgen.Req(due=0.0, prompt=traffic._tokens(length),
                        max_tokens=max_tokens, seed=traffic._seed())
        res = loadgen.Result(req=r)
        return asyncio.ensure_future(loadgen.send_one(
            http, base, traffic.body(r), res, timeout_s=600.0))

    jobs = [job(blocker_tokens, 1)]
    await asyncio.sleep(0.015)
    jobs += [job(w["length"], w["max_tokens"]) for _ in range(w["n"])]
    return list(await asyncio.gather(*jobs))


async def walk(base: str, traffic: loadgen.Traffic, sizes: dict) -> dict:
    """Send every wave of the plan; all of it must come back whole."""
    import aiohttp

    waves = plan(traffic.spec, sizes)
    blocker = min(sizes["prefill_chunk_size"],
                  sizes["max_num_tokens_per_batch"])
    sent = failed = 0
    conn = aiohttp.TCPConnector(limit=0)
    async with aiohttp.ClientSession(connector=conn) as http:
        for w in waves:
            for res in await _wave(http, base, traffic, w, blocker):
                sent += 1
                failed += 0 if res.ok else 1
    return {"waves": len(waves), "requests": sent, "failed": failed}
