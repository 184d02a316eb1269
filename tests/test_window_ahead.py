"""One decode window ahead of the host: under the one-in-flight loop a
steady batch's window N+1 is enqueued from window N's device-resident
carry before window N is read back (``StageEngine._dispatch_multistep``,
docs/decode_loop.md). Streams stay bit-identical to the synchronous K=1
engine, and every batch the hand-over does not cover takes the old path.

Lives beside ``tests/test_multistep_decode.py`` (whose ``_drive`` is the
loop) in a module of its own: that module is in conftest's SLOW_MODULES,
and these cases are tier-1.
"""

import jax
import jax.numpy as jnp
import pytest

from parallax_tpu.runtime.engine import EngineConfig, StageEngine, drive_step
from parallax_tpu.runtime.request import Request, SamplingParams
from tests.test_multistep_decode import _build_engine

K = 4
GREEDY = [([3, 14, 15, 92], 0.0, None), ([7, 21, 108], 0.0, None),
          ([42] * 5, 0.0, None)]
SEEDED = [([3, 14, 15, 92], 0.8, 5), ([7, 21, 108], 0.9, 7),
          ([42] * 5, 1.3, 11)]
MIXED = [([3, 14, 15, 92], 0.0, None), ([7, 21, 108], 0.9, 7),
         ([42] * 5, 1.3, 11)]


def _watch(eng):
    """Record what every ``dispatch`` returned: ("window", chained),
    ("empty",) for a ticket that resolved inside dispatch, ("step",)."""
    log = []
    orig = eng.dispatch

    def dispatch():
        t = orig()
        if t.ms_windows is not None:
            log.append(("window", t.chained))
        elif t.outputs is not None:
            log.append(("empty",))
        else:
            log.append(("step",))
        return t

    eng.dispatch = dispatch
    return log


def _ahead(eng):
    """(sum, count) of the engine's parallax_visit_window_ahead child."""
    snap = eng._h_window_ahead.snapshot()
    return snap["sum"], snap["count"]


def _requests(specs, max_new, **sp_kw):
    out = []
    for i, (prompt, temp, seed) in enumerate(specs):
        n = max_new[i] if isinstance(max_new, (list, tuple)) else max_new
        kw = dict(temperature=temp, seed=seed, max_new_tokens=n,
                  ignore_eos=True)
        kw.update(sp_kw)
        out.append(Request(f"r{i}", prompt_ids=list(prompt),
                           sampling_params=SamplingParams(**kw)))
    return out


def _drive(eng, reqs, on_iter=None, max_iters=3000):
    """The one-in-flight loop; ``on_iter(i)`` runs before iteration i."""
    for r in reqs:
        eng.submit(r)
    outs_all, pending, i = [], None, 0
    while (eng.has_work() or pending is not None) and i < max_iters:
        if on_iter is not None:
            on_iter(i)
        i += 1
        outs, pending = drive_step(eng, pending)
        outs_all.extend(outs)
    assert pending is None and not eng._inflight
    return outs_all


def _baseline(specs, max_new, on_iter=None, eos=None, **kw):
    """The synchronous K=1 engine on the same requests."""
    eng = _build_engine(1, overlap=False, **kw.pop("cfg", {}))
    reqs = _requests(specs, max_new, **kw)
    for r in reqs:
        if eos is not None:
            r.eos_token_ids = eos
    _drive(eng, reqs, on_iter=on_iter and (lambda i: on_iter(i, eng)))
    return reqs, eng


def _same_streams(base, got):
    for b, g in zip(base, got):
        assert g.output_ids == b.output_ids, (
            b.request_id, b.output_ids, g.output_ids)
        assert g.status == b.status


def _settled(reqs, eng, base_eng=None):
    """Nothing past a stop: computed KV one short of the stream, pages
    back in the pool, and (digest plane on) the same donated prefixes as
    the K=1 run — a phantom commit would mint extra block digests."""
    for r in reqs:
        if r.status.value != "finished_abort":
            assert r.num_computed_tokens == len(r.all_token_ids) - 1, (
                r.request_id, r.num_computed_tokens, len(r.all_token_ids))
        assert r.window_pending == 0 or r.status.is_finished
    assert not eng.scheduler.running and not eng.scheduler.wait_queue
    if base_eng is not None:
        assert eng.cache.num_free_pages == base_eng.cache.num_free_pages
        bp = base_eng.cache_digest_payload(full=True)
        mp = eng.cache_digest_payload(full=True)
        if bp is not None:
            assert sorted(bp["full"]) == sorted(mp["full"])


# -- the cases ---------------------------------------------------------------


def case_steady(specs):
    """(a) A batch that is steady for many windows: every window after
    the first starts from the one before it, no empty dispatch between."""
    base, _ = _baseline(specs, 41)
    eng = _build_engine(K)
    log = _watch(eng)
    s0, c0 = _ahead(eng)
    reqs = _requests(specs, 41)
    outs = _drive(eng, reqs)
    _same_streams(base, reqs)
    windows = [e for e in log if e[0] == "window"]
    assert len(windows) == 10            # 40 tokens after the prefill's one
    assert [w[1] for w in windows] == [False] + [True] * 9
    first = log.index(("window", False))
    last = len(log) - 1 - log[::-1].index(("window", True))
    assert ("empty",) not in log[first:last + 1]
    full = [o for o in outs if o.num_tokens == K * len(specs)]
    assert len(full) >= 9 and all(o.overlapped for o in full[:-1])
    s1, c1 = _ahead(eng)
    assert (s1 - s0, c1 - c0) == (9.0, 10)
    assert len(eng._jit_multistep) == 1
    (fn,) = eng._jit_multistep.values()
    assert fn._cache_size() == 1         # the carry costs no second program
    _settled(reqs, eng)


def case_budget_and_eos(kind):
    """(b) A row stops inside window N (budget, or EOS) while N+1 is in
    flight: nothing commits past the stop, the pages come back, the
    prefix cache sees exactly the K=1 run's blocks; the others go on."""
    cfg = dict(cache_digests=True, enable_prefix_cache=True)
    if kind == "budget":
        max_new, eos = [10, 23, 15], None     # mid-window: 1+2*4+1, ...
        base, beng = _baseline(MIXED, max_new, cfg=dict(cfg))
    else:
        max_new = 30
        probe, _ = _baseline(MIXED, max_new)
        # The greedy row's 7th token, first seen there: mid-window 2.
        row = probe[0].output_ids
        stop_idx = next(i for i in range(5, 12) if row[i] not in row[:i])
        eos = (row[stop_idx],)
        base, beng = _baseline(MIXED, max_new, eos=eos, ignore_eos=False,
                               cfg=dict(cfg))
        assert base[0].status.value == "finished_eos"
        assert len(base[0].output_ids) == stop_idx + 1
    eng = _build_engine(K, **cfg)
    log = _watch(eng)
    reqs = _requests(MIXED, max_new, **(
        {} if eos is None else {"ignore_eos": False}))
    for r in reqs:
        if eos is not None:
            r.eos_token_ids = eos
    _drive(eng, reqs)
    _same_streams(base, reqs)
    assert ("window", True) in log
    _settled(reqs, eng, beng)


def case_race(kind):
    """(c) An abort or a stop-string finish lands while a window (and the
    one enqueued behind it) is in flight: the row commits nothing more,
    the others keep their streams, no ticket and no page is left."""
    base, _ = _baseline(MIXED, 33)
    eng = _build_engine(K)
    log = _watch(eng)
    reqs = _requests(MIXED, 33)

    def hit(i):
        if i == 6:
            assert len(eng._inflight) == 1 and eng._inflight[0].chained
            if kind == "abort":
                reqs[1].abort("client")
            else:
                eng.stop_request("r1")

    _drive(eng, reqs, on_iter=hit)
    for b, g in zip(base, reqs):
        if g.request_id == "r1":
            n = len(g.output_ids)
            assert 0 < n < 33 and g.output_ids == b.output_ids[:n]
            assert g.status.value == (
                "finished_abort" if kind == "abort" else "finished_stop")
        else:
            assert g.output_ids == b.output_ids
    # The chain ended at the race and a new one formed over two rows.
    assert log.count(("window", False)) >= 2
    free = eng.cache.num_free_pages
    _settled(reqs, eng)
    assert free == eng.cache.num_free_pages


def case_arrival():
    """(d) A request arrives mid-chain: the next dispatch plans the
    arrival (its prefill) and hands nothing over; the window in flight
    resolves, and windows resume over the larger batch."""
    late = Request("late", prompt_ids=[99, 98, 97, 96],
                   sampling_params=SamplingParams(
                       temperature=0.0, max_new_tokens=14, ignore_eos=True))

    def arrive(i, e):
        if i == 6:
            e.submit(Request(
                "late", prompt_ids=[99, 98, 97, 96],
                sampling_params=SamplingParams(
                    temperature=0.0, max_new_tokens=14, ignore_eos=True)))

    base, beng = _baseline(MIXED, 37, on_iter=arrive)
    eng = _build_engine(K)
    log = _watch(eng)
    reqs = _requests(MIXED, 37)
    at = {}

    def arrive_here(i):
        if i == 6:
            assert eng._inflight and eng._inflight[0].chained
            eng.submit(late)
            at["log"] = len(log)

    _drive(eng, reqs, on_iter=arrive_here)
    _same_streams(base, reqs)
    assert late.status.value == "finished_length"
    assert len(late.output_ids) == 14
    # The dispatch right after the arrival is its prefill, not a window.
    assert log[at["log"]] == ("step",)
    after = log[at["log"]:]
    assert ("window", False) in after and ("window", True) in after
    _settled(reqs + [late], eng)


def case_context_room():
    """(e) Rows cross page boundaries (page 8, K=4) up to the clamp at
    max_model_len: the hand-over stops where the next window has no
    room, K=1 finishes the row, streams match."""
    cfg = dict(max_model_len=64, num_pages=64)
    specs = [(list(range(1, 25)), 0.0, None), (list(range(30, 50)), 0.7, 3)]
    base, _ = _baseline(specs, 100, cfg=dict(cfg))
    eng = _build_engine(K, **cfg)
    log = _watch(eng)
    reqs = _requests(specs, 100)
    _drive(eng, reqs)
    _same_streams(base, reqs)
    assert all(r.status.value == "finished_length" for r in reqs)
    assert max(r.total_len for r in reqs) == 64
    assert ("window", True) in log and ("step",) in log[-8:]
    _settled(reqs, eng)


def case_features():
    """(f) Penalty counts and logprobs ride the carry across windows."""
    kw = dict(repetition_penalty=1.3, frequency_penalty=0.2, logprobs=True)
    base, _ = _baseline(MIXED, 29, **kw)
    eng = _build_engine(K)
    log = _watch(eng)
    reqs = _requests(MIXED, 29, **kw)
    _drive(eng, reqs)
    _same_streams(base, reqs)
    for b, g in zip(base, reqs):
        assert len(g.output_logprobs) == 29
        assert g.output_logprobs == pytest.approx(b.output_logprobs,
                                                  abs=1e-5)
    assert log.count(("window", True)) >= 5
    assert list(eng._jit_multistep) == [(K, True, False, ("lp", "pen"))]
    _settled(reqs, eng)


def case_hybrid():
    """(f) A hybrid (linear-state) batch: the recurrence runs on from
    the carried window, except over a window that ends where resolve
    snapshots the state (a page boundary)."""
    from parallax_tpu.models.registry import create_stage_model
    from tests.test_linear_prefix_cache import CONFIG as HYBRID_CFG

    def build(lookahead, overlap):
        m = create_stage_model(HYBRID_CFG, 0, 4, use_pallas=False)
        return StageEngine(
            m, m.init_params(jax.random.key(0), dtype=jnp.float32),
            EngineConfig(page_size=8, num_pages=128, max_model_len=256,
                         kv_dtype="float32", decode_lookahead=lookahead,
                         overlap_steps=overlap),
        )

    # A 4-token prompt: every other K=4 window ends on a page boundary.
    specs = [([3, 14, 15, 92], 0.0, None), ([7, 21, 108], 0.9, 7)]
    base = _requests(specs, 30)
    _drive(build(1, False), base)
    eng = build(K, True)
    log = _watch(eng)
    reqs = _requests(specs, 30)
    _drive(eng, reqs)
    _same_streams(base, reqs)
    windows = [e for e in log if e[0] == "window"]
    assert ("window", True) in windows and windows.count(
        ("window", False)) >= 2          # boundary windows hand nothing over
    _settled(reqs, eng)


def case_old_paths(kind):
    """Engines and batches the hand-over must leave alone: streams are
    the synchronous engine's, and the series reads 0 for every window
    (with a finish every round: for every window after a finish)."""
    base, _ = _baseline(MIXED, 21)
    if kind == "overlap_off":
        eng = _build_engine(K, overlap=False)
    elif kind == "speculative":
        eng = _build_engine(K, speculative_tokens=2)
    else:                                   # a finish every round
        eng = _build_engine(K)
    log = _watch(eng)
    s0, c0 = _ahead(eng)
    if kind == "finish_every_round":
        # Each window is some row's last: the batch never repeats.
        specs = MIXED + [([9, 8, 7], 0.0, None), ([5] * 4, 0.6, 2)]
        max_new = [5, 9, 13, 17, 21]
        base, _ = _baseline(specs, max_new)
        reqs = _requests(specs, max_new)
    else:
        reqs = _requests(MIXED, 21)
    _drive(eng, reqs)
    _same_streams(base, reqs)
    s1, c1 = _ahead(eng)
    assert c1 - c0 >= 5
    if kind == "finish_every_round":
        # A window may start behind one whose stop the host has not
        # read yet (the row rides it frozen); the window after resolve
        # found that finish never does.
        chained = [e[1] for e in log if e[0] == "window"]
        assert chained.count(False) >= 3
        assert not any(a and b for a, b in zip(chained, chained[1:]))
    else:
        assert s1 - s0 == 0.0
        assert ("window", True) not in log
    _settled(reqs, eng)


def case_pipeline_chain():
    """``decode_pipeline`` = 2 chains two windows inside one dispatch and
    the hand-over chains dispatches: a row then holds 2*K tokens."""
    base, _ = _baseline(MIXED, 41)
    eng = _build_engine(K, decode_pipeline=2)
    log = _watch(eng)
    reqs = _requests(MIXED, 41)
    _drive(eng, reqs)
    _same_streams(base, reqs)
    assert [e for e in log if e[0] == "window"] == (
        [("window", False)] + [("window", True)] * 4)
    _settled(reqs, eng)


CASES = {
    "steady-greedy": lambda: case_steady(GREEDY),
    "steady-seeded": lambda: case_steady(SEEDED),
    "steady-mixed": lambda: case_steady(MIXED),
    "stop-budget": lambda: case_budget_and_eos("budget"),
    "stop-eos": lambda: case_budget_and_eos("eos"),
    "race-abort": lambda: case_race("abort"),
    "race-stop-request": lambda: case_race("stop"),
    "arrival-mid-chain": case_arrival,
    "page-boundary-context-room": case_context_room,
    "features-penalties-logprobs": case_features,
    "hybrid": case_hybrid,
    "old-path-overlap-off": lambda: case_old_paths("overlap_off"),
    "old-path-speculative": lambda: case_old_paths("speculative"),
    "old-path-finish-every-round": lambda: case_old_paths(
        "finish_every_round"),
    "decode-pipeline-2": case_pipeline_chain,
}


@pytest.mark.parametrize("case", list(CASES))
def test_window_ahead_bit_identical(case):
    CASES[case]()


# -- why a window was not ahead -----------------------------------------------


def _miss_ledger():
    """The process's ``parallax_window_not_ahead_total`` by reason, the
    zeros of ``parallax_visit_window_ahead`` and (sum, count) of the
    avoidable-miss series, each summed over its label sets."""
    from parallax_tpu.obs import names as mnames
    from parallax_tpu.obs.registry import get_registry

    reg = get_registry()
    reasons = {}
    for line in reg.render().splitlines():
        if line.startswith(mnames.WINDOW_NOT_AHEAD_TOTAL + "{"):
            labels, value = line.split("{")[1].split("} ")
            reason = dict(p.split("=") for p in labels.split(","))["reason"]
            reasons[reason.strip('"')] = (
                reasons.get(reason.strip('"'), 0) + float(value))
    snaps = reg.histogram_snapshots()

    def total(name):
        s = snaps.get(name, {}).values()
        return sum(x["sum"] for x in s), sum(x["count"] for x in s)

    ahead, windows = total(mnames.VISIT_WINDOW_AHEAD)
    return reasons, windows - ahead, total(
        mnames.VISIT_WINDOW_AHEAD_AVOIDABLE_MISS), windows


# case -> the reasons its misses must name, and those they may besides.
MISS_CASES = {
    "steady-greedy": ({"no_window_in_flight"}, set()),
    "stop-budget": ({"no_window_in_flight", "row_ended"}, {"budget_ends"}),
    "race-abort": ({"no_window_in_flight", "row_ended"}, {"budget_ends"}),
    "arrival-mid-chain": ({"no_window_in_flight", "row_joined"},
                          {"row_ended", "budget_ends"}),
    "page-boundary-context-room": ({"no_window_in_flight", "no_pages"},
                                   {"row_ended", "budget_ends"}),
    "hybrid": ({"no_window_in_flight", "snapshot_due"},
               {"row_ended", "budget_ends"}),
    "old-path-overlap-off": ({"no_window_in_flight", "overlap_off"}, set()),
    "old-path-speculative": ({"no_window_in_flight", "speculation"},
                             {"budget_ends"}),
    "old-path-finish-every-round": ({"no_window_in_flight", "row_ended"},
                                    {"budget_ends"}),
}


@pytest.mark.parametrize("case", list(MISS_CASES))
def test_every_miss_of_the_hand_over_has_exactly_one_reason(case):
    """On a toy dense engine and the toy hybrid: the reasons sum to the
    zeros of ``parallax_visit_window_ahead``; a snapshot that is due (or
    a window that found no room) is avoidable, a finished, aborted or
    arriving row and a configuration that hands nothing over are not."""
    from parallax_tpu.runtime.engine import (
        WINDOW_MISS_AVOIDABLE,
        WINDOW_MISS_INHERENT,
        WINDOW_MISS_REASONS,
    )

    r0, zeros0, (av0, n0), w0 = _miss_ledger()
    CASES[case]()
    r1, zeros1, (av1, n1), w1 = _miss_ledger()
    assert set(r1) == set(WINDOW_MISS_REASONS)      # all there, from 0
    got = {k: r1[k] - r0.get(k, 0) for k in r1 if r1[k] != r0.get(k, 0)}
    must, may = MISS_CASES[case]
    assert must <= set(got) <= must | may, got
    # Exactly one reason a miss, one observation a window.
    assert sum(got.values()) == zeros1 - zeros0 > 0
    assert n1 - n0 == w1 - w0
    avoidable = sum(v for k, v in got.items() if k in WINDOW_MISS_AVOIDABLE)
    assert av1 - av0 == avoidable
    assert (avoidable > 0) == bool(set(got) & {"snapshot_due", "no_pages"})
    assert {"row_ended", "row_joined", "budget_ends", "speculation",
            "overlap_off", "no_window_in_flight"} == set(WINDOW_MISS_INHERENT)


def test_a_dropped_plan_leaves_its_reason_for_the_window_after_it():
    """The rows of the window in flight planned without the one that
    finished meanwhile: the plan is dropped (the rows wait for the
    resolve), and the window after it names the reason. What the engine
    keeps of the newest window is its rows' ids and that reason, not
    the ticket."""
    r0 = _miss_ledger()[0]
    eng = _build_engine(K)
    log = _watch(eng)
    reqs = _requests(GREEDY, [10, 23, 15])
    _drive(eng, reqs)
    r1 = _miss_ledger()[0]
    assert r1["row_ended"] - r0.get("row_ended", 0) >= 1
    assert ("empty",) in log            # the dropped plan's dispatch
    assert eng._window_rows <= {r.request_id for r in reqs}
    assert eng._window_miss in (None, "row_ended", "budget_ends")
