#!/usr/bin/env python3
"""Run one cell of the benchmark once.

    python3 benchmarks/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1|2>

The parent (this process) never touches the chip: it reads the cell's
files, starts the child that holds the chip
(``benchmarks/harness/server_child.py``: ``serve`` with weights made from
the seed), checks it against the plain reference, warms it up, offers the
cell's traffic for ``--seconds`` over HTTP, and does all the arithmetic.
The LAST line of standard output is one JSON object (``correct``,
``attempted``, ``failed``, ``metrics``, ``device``, with ``--trace 1``
or ``2`` ``breakdown``, and last ``compared``: every number that decides
``correct`` beside its limit, which are the last lines of standard error
too). A run whose replay of the reference fails opens no window and ends
in that line with ``"correct": false``. Without a TPU holding the chips
the cell asks for, the exit code is not 0 and no result is printed.

``--trace 0`` measures the end-to-end metrics with the profiler off.
``--trace 1`` traces a span inside the window and prints the per-layer
metrics only (its rates are not used). ``--trace 2`` is ``--trace 0`` to
the letter until the window closes - same spawn, checks, walk, warm
phase and requests, end-to-end numbers from what was recorded up to
then - followed by a tail in which the same traffic goes on: the
profiler is started and stopped once for nothing (the cost of its first
start falls into no number), then a few seconds are traced, and the one
last line holds end-to-end and per-layer metrics side by side.

``--rehearse`` is the CPU dress rehearsal (toy widths, traffic cut to
size): it says ``"platform": "cpu"`` and prints no device metric.

Phases: spawn -> ready -> reference check -> warm-up (lattice walk, then
the traffic's own warm phase) -> window -> (``--trace 2``: tail) -> drain
-> stop. ``setup_s`` runs from process start to the opening of the window.

Where a probe's ramp is not whole when the window opens (a traffic file's
``"ramp_whole"``; ``loadgen.RampSplit``) the child is stopped and all of
it is done once more on a new child, set-up running on; a second split
ends the run with no result.
"""

from __future__ import annotations

import argparse
import asyncio
import json
import os
import shutil
import sys
import time

T_START = time.monotonic()

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks.harness import loadgen, metrics, spec, warmup, work  # noqa: E402
from benchmarks.harness.server import (  # noqa: E402
    BenchFailed,
    Server,
    check,
    compared,
    holds,
    repeat_agrees,
    replay_reference,
)

# The traced span: a few seconds (a trace of the whole window would be
# hundreds of MB and slow the host throughout), ``--trace 1``: inside
# the window; ``--trace 2``: in the tail after it, once the load has
# settled from the scrapes at the window's end and the profiler has been
# started and stopped once. The tail's traffic ends with the span, and
# after ``TRACE2_TAIL_S`` at the latest (an open loop's schedule is
# drawn that far: room for that first start and the span).
TRACE_OFFSET_S = 2.0
TRACE_SECONDS = 4.0
TRACE2_SETTLE_S = 0.5
TRACE2_TAIL_S = 12.0


def log(**record) -> None:
    print(json.dumps(record), file=sys.stderr, flush=True)


async def http_json(http, method: str, url: str, body=None, timeout=120.0):
    import aiohttp

    async with http.request(
        method, url, json=body, timeout=aiohttp.ClientTimeout(total=timeout),
    ) as r:
        text = await r.text()
        check(r.status == 200, f"{method} {url} -> {r.status}", body=text[:300])
        return text


async def measure(srv: Server, traffic: loadgen.Traffic, trace: int,
                  trace_dir: str, hold_back_s: float = 0.0) -> dict:
    """The window (and ``--trace 2``'s tail). Returns the run's raw
    material."""
    got: dict = {}

    def scrape(tag: str):
        async def fn():
            text = await http_json(run.http, "GET", srv.base + "/metrics")
            got["scrape_" + tag] = metrics.parse_prometheus(text)
            got["t_" + tag] = time.monotonic()
            if tag in ("w0", "w1"):
                got["status_" + tag] = json.loads(await http_json(
                    run.http, "GET", srv.base + "/cluster/status_json"))
        return fn

    async def trace_start():
        await scrape("t0")()
        await http_json(run.http, "POST", srv.base + "/profile/start",
                        {"dir": trace_dir, "max_seconds": 60})
        got["t_trace0"] = time.monotonic()

    async def trace_stop():
        got["t_trace1"] = time.monotonic()
        await http_json(run.http, "POST", srv.base + "/profile/stop", {},
                        timeout=300.0)
        await scrape("t1")()

    async def profile(what: str, body: dict) -> dict:
        return json.loads(await http_json(
            run.http, "POST", srv.base + "/profile/" + what, body,
            timeout=300.0))

    async def trace_tail():
        """After the window: the profiler's first start and stop, thrown
        away; then the traced span, while the traffic goes on. The
        Python-call tracer and the HLO protos stay out: the program's
        own spans say what the host does, the reducers find operations
        by name, and with the first in the host packed a visit a third
        slower and the trace was half as large again (PERF.md, PR 25)."""
        await asyncio.sleep(TRACE2_SETTLE_S)
        t = time.monotonic()
        quiet = {"max_seconds": 60, "profiler_options": {
            "python_tracer_level": 0, "enable_hlo_proto": False}}
        await profile("start", dict(quiet, dir=trace_dir + ".first"))
        await profile("stop", {})
        shutil.rmtree(trace_dir + ".first", ignore_errors=True)
        got["first_profile_s"] = time.monotonic() - t
        run.note_in_flight("trace0")
        await scrape("t0")()
        got["profile_start"] = await profile(
            "start", dict(quiet, dir=trace_dir))
        got["t_trace0"] = time.monotonic()
        await asyncio.sleep(span)
        run.note_in_flight("trace1")
        await scrape("t1")()
        got["t_trace1"] = time.monotonic()
        # The span is over: nothing more need be sent while it is written.
        run.end_tail()
        got["profile_stop"] = await profile("stop", {})
        got["tail_s"] = time.monotonic() - run.w1

    span = min(TRACE_SECONDS, max(0.5, traffic.seconds / 3))
    hooks = {"w0": scrape("w0"), "w1": scrape("w1"), "at": []}
    if trace == 1:
        off = traffic.warm + min(TRACE_OFFSET_S, traffic.seconds / 4)
        hooks["at"] = [(off, trace_start), (off + span, trace_stop)]
    elif trace == 2:
        hooks["at"] = [(traffic.warm + traffic.seconds, trace_tail)]
    run = loadgen.Run(traffic, srv.base, hooks, hold_back_s=hold_back_s)
    await run.go()
    got["run"] = run
    return got


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1, 2), default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="CPU dress rehearsal at toy widths (no chip run)")
    ap.add_argument("--benchmark-json", default=None, help=argparse.SUPPRESS)
    ap.add_argument("--keep-work", action="store_true", help=argparse.SUPPRESS)
    # Tests: in the first attempt one client sends this much later.
    ap.add_argument("--split-first-ramp", type=float, default=0.0,
                    help=argparse.SUPPRESS)
    args = ap.parse_args()

    bench = spec.load(args.benchmark_json)
    check(args.workload in bench["cells"], f"unknown workload {args.workload}")
    cell = bench["cells"][args.workload]
    config = bench["configs"][cell["config"]]
    traffic_spec = cell["traffic_spec"]
    hf = dict(config["hf"])
    flags = list(config["bench"]["serve_flags"])
    if args.rehearse:
        from benchmarks.harness.server_child import REHEARSE_FLAGS

        hf.update(config["bench"]["rehearse"])
        flags += REHEARSE_FLAGS
    sizes = spec.serve_sizes(flags)

    work_dir = os.path.join(ROOT, ".bench_work", args.workload)
    trace_dir = os.path.join(work_dir, "trace")

    def serve_once(hold_back_s: float) -> dict:
        """One child, from its spawn to its stop: the run's material."""
        shutil.rmtree(work_dir, ignore_errors=True)
        os.makedirs(work_dir)
        traffic = loadgen.Traffic(
            traffic_spec, hf["vocab_size"], args.seed, args.seconds,
            rehearse=args.rehearse,
            tail=TRACE2_TAIL_S if args.trace == 2 else 0.0)
        srv = Server(work_dir, spec.config_path(cell["config"]), args.seed,
                     cell["chips"], args.rehearse)
        phases = {}
        try:
            phases["ready_s"] = srv.wait_ready(1100)
            device = srv.device()
            if not args.rehearse:
                check(device["platform"] == "tpu"
                      and device["count"] == cell["chips"],
                      "not the chips the cell asks for", device=device)
                peaks = spec.peaks_for(device["kind"])
            else:
                peaks = None

            t = time.monotonic()
            with open(os.path.join(work_dir, "reference.json")) as f:
                ref = json.load(f)
            ref_check = replay_reference(srv, ref["rows"])
            repeat_ok = got = None
            if ref_check["failed"] is None:
                repeat_ok = repeat_agrees(
                    srv, traffic._tokens(3 * 64 + 5), 2 * warmup.DECODE_K)
            phases["reference_s"] = time.monotonic() - t
            log(phase="reference", **ref_check, repeat_identical=repeat_ok,
                reference_seconds=ref["seconds"])

            # A replay that failed opens no window: the run ends in its
            # line, ``correct`` false, with what was compared.
            if ref_check["failed"] is None:
                t = time.monotonic()
                walked = asyncio.run(warmup.walk(srv.base, traffic, sizes))
                phases["walk_s"] = time.monotonic() - t
                log(phase="warmup", **walked)
                check(walked["failed"] == 0, "warm-up requests failed",
                      **walked)

                got = asyncio.run(measure(srv, traffic, args.trace,
                                          trace_dir, hold_back_s))
            status = srv.status()
        except BaseException:
            srv.close()
            raise
        log(phase="stopped", server_exit=srv.close())
        return {"got": got, "phases": phases, "device": device,
                "peaks": peaks, "ref_check": ref_check,
                "repeat_ok": repeat_ok, "status": status}

    splits = []
    try:
        for attempt in (1, 2):
            try:
                once = serve_once(
                    args.split_first_ramp if attempt == 1 else 0.0)
                break
            except loadgen.RampSplit as e:
                # Not the cell's traffic: once more, on a new child.
                splits.append(e.detail)
                log(phase="ramp_split", attempt=attempt, **e.detail)
                check(attempt == 1, "the ramp split twice", splits=splits)
    except BenchFailed as e:
        print(f"benchmark FAILED: {e}", file=sys.stderr, flush=True)
        return 1
    got, phases, device, peaks, ref_check, repeat_ok, status = (
        once[k] for k in ("got", "phases", "device", "peaks", "ref_check",
                          "repeat_ok", "status"))
    hw = status["hardware"]
    peak = max((d.get("peak_bytes_in_use") or 0) for d in hw["devices"])
    device_out = {"platform": device["platform"], "kind": device["kind"],
                  "count": device["count"], "memory_peak_bytes": peak}

    def finish(result: dict, numbers: dict) -> int:
        """The run's last words: each number ``correct`` compared beside
        its limit as the last lines of standard error, and the result's
        line, which carries them under ``compared``, its last key."""
        for name, n in numbers.items():
            limit = (f"limit: {n['rule']} {n['limit']}" if n["rule"]
                     else "counted, no limit of its own")
            print(f"compared {name}: {n['value']} ({limit})",
                  file=sys.stderr, flush=True)
        print(json.dumps(dict(result, compared=numbers)), flush=True)
        return 0

    if got is None:
        log(phase="summary", phases=phases, not_correct=ref_check["failed"])
        return finish({"correct": False, "attempted": 0, "failed": 0,
                       "metrics": {}, "device": device_out,
                       "ramp_attempts": len(splits) + 1},
                      compared(ref_check))
    run = got["run"]
    setup_s = run.w0 - T_START

    client = metrics.end_to_end(run.results, run.w0, run.w1, cell["chips"])
    # How much of the preallocated KV pool the traffic's live context
    # fills (the allocator's peak counts the whole pool, used or not).
    live = {k: metrics.live_context_tokens(run.results, t)
            for k, t in (("w0", run.w0), ("w1", run.w1))}
    pool_tokens = status["stages"][0]["num_pages"] * sizes["page_size"]
    client["kv_live_share"] = 100.0 * live["w1"] / pool_tokens
    not_ok = [r for r in run.results if not r.ok]
    numbers = compared(ref_check, repeat_ok, client)

    values: dict[str, float] = {}
    breakdown = None
    # A rehearsal reports counts only: a time or a rate from the CPU is
    # never written under a device metric's name.
    def reported(m):
        return args.workload in m["cells"] and (
            not args.rehearse or m["source"] == "program_counter")

    if args.trace != 1:
        client["setup_s"] = setup_s
        for name, m in bench["end_to_end"].items():
            if reported(m) and client.get(name) is not None:
                values[name] = client[name]
    if args.trace:
        red = None
        if not args.rehearse:
            from benchmarks.harness import host_spans, trace_reduce

            # ``--trace 2``: the span as the program's own clock saw it
            # (its two ``/profile`` readings). The reduction cuts the
            # device's events to it, so ``busy_s`` cannot pass
            # ``window_s`` whatever the readings bracket.
            window_ns = None
            if args.trace == 2:
                t0, t1 = (got[k].get("perf_counter_ns")
                          for k in ("profile_start", "profile_stop"))
                if t0 and t1:
                    window_ns = (t0, t1)
            red = trace_reduce.reduce_trace(trace_dir, window_ns=window_ns)
            check(red is not None, "the trace holds no device operation")
            device_out["busy_s"] = red["busy_s"]
            device_out["window_s"] = (
                (window_ns[1] - window_ns[0]) * 1e-9 if window_ns
                else got["t_trace1"] - got["t_trace0"])
            breakdown = trace_reduce.breakdown(red)
            if args.trace == 2:
                # The idle gaps by the host span that covers them.
                by_span = host_spans.idle_gaps(red["file"])
                if by_span is not None:
                    breakdown["idle_gaps"] = by_span
        stage = work.load_stage(hf, config["work"]["path"])
        ctx = {
            "scrape_w0": got.get("scrape_w0"), "scrape_w1": got.get("scrape_w1"),
            "scrape_t0": got.get("scrape_t0"), "scrape_t1": got.get("scrape_t1"),
            "status_w0": got.get("status_w0"), "status_w1": got.get("status_w1"),
            "client": client, "trace": red, "model": hf, "work": stage,
            "peaks": peaks,
            "span_work": work.span_work(
                run.results, got.get("t_trace0", 0), got.get("t_trace1", 0),
                stage) if red is not None else None,
        }
        for name, m in bench["per_layer"].items():
            if not reported(m):
                continue
            v = metrics.read_layer_metric(m["reader"], ctx)
            if v is not None:
                values[name] = v
    units = {**{k: v["unit"] for k, v in bench["end_to_end"].items()},
             **{k: v["unit"] for k, v in bench["per_layer"].items()}}
    if args.keep_work:
        with open(os.path.join(work_dir, "requests.jsonl"), "w") as f:
            for r in run.results:
                f.write(json.dumps({
                    "due": r.due_t - run.w0, "sent": r.sent_t - run.w0,
                    "first": r.first_t and r.first_t - run.w0,
                    "last": r.last_t and r.last_t - run.w0,
                    "n": r.n_tokens, "want": r.req.max_tokens,
                    "prompt": len(r.req.prompt), "judged": r.req.judged,
                    "error": r.error}) + "\n")
    else:
        shutil.rmtree(trace_dir, ignore_errors=True)
        shutil.rmtree(os.path.join(work_dir, "model"), ignore_errors=True)

    tail = None
    if args.trace == 2:
        # What the tail cost, and the tokens a second the clients saw
        # inside the traced span (the on-cost of tracing; stderr only).
        t0, t1 = got["t_trace0"], got["t_trace1"]
        tail = {"first_profile_s": got["first_profile_s"],
                "tail_s": got["tail_s"],
                "stop_seconds": got["profile_stop"].get("stop_seconds"),
                "span_out_tok_s": sum(
                    metrics.tokens_in_window(r.chunks, t0, t1)
                    for r in run.results) / (t1 - t0) / cell["chips"],
                # A probe's margin: how long after the traced span its
                # first row ended (PERF.md, Cells).
                "first_end_after_span_s": min(
                    (r.last_t - t1 for r in
                     metrics.live_in_window(run.results, t0, t1)
                     if r.usage is not None), default=None)}
    log(phase="summary", phases=phases, setup_s=setup_s, client=client,
        live_context_tokens=live,
        in_flight=run.in_flight_at, not_ok=len(not_ok),
        ramp=run.ramp, ramp_splits=splits, trace2=tail,
        first_errors=[r.error for r in not_ok if r.error][:3],
        kv_pages=status["stages"][0]["num_pages"],
        kv_occupancy={k: got["scrape_" + k].get("parallax_kv_page_occupancy")
                      for k in ("w0", "w1")},
        compile=status["device"]["compile"]["compiles_total"],
        cache_hits=status["device"]["compile"]["cache_hits_total"])
    result = {
        "correct": holds(numbers),
        "attempted": client["attempted"],
        "failed": client["failed"],
        "metrics": {k: {"value": v, "unit": units[k]}
                    for k, v in values.items()},
        "device": device_out,
        "ramp_attempts": len(splits) + 1,
    }
    if breakdown is not None:
        result["breakdown"] = breakdown
    return finish(result, numbers)


if __name__ == "__main__":
    sys.exit(main())
