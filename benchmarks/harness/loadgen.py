"""The one traffic generator and the driver that sends what it makes.

A traffic mix is a data file (``benchmarks/traffic/<name>.json``); this
module reads its parameters and nothing about any particular mix.

Steadiness rule: every ``--seed`` gets the *same set* of prompt lengths,
output lengths and arrival gaps (drawn once from a seed fixed by the
traffic's own parameters) in *another order*, with other token ids and
sampling seeds. So two seeds do the same amount of work.

Timing rule: a request is timed from when it was *due*, not from when
it was sent, so a stall that delays later requests counts against them;
``sent - due`` is reported as the generator's lag.

A probe's premise (``"ramp_whole": true`` in the traffic file): every
client's row has had its first token when the window opens. A run in
which one has not - the program's admission race split the ramp
(``warmup.py``), or a program was still compiling - is not the cell's
traffic: ``Run.go`` raises ``RampSplit`` at the window's opening and the
caller starts again with a new child (``run.py``).
"""

from __future__ import annotations

import asyncio
import dataclasses
import json
import math
import time
import zlib

import numpy as np

# In a rehearsal (toy widths, CPU) lengths and concurrency are cut to what
# ``server_child.REHEARSE_FLAGS`` can hold; control flow stays the same.
REHEARSE = {"prompt_max": 40, "output_max": 10, "clients_max": 4,
            "prefix_max": 32, "warm_seconds_max": 1.0, "rate_max": 6.0,
            "blocker_max": 48}


class RampSplit(Exception):
    """At the opening of the window a client's row had no first token:
    ``detail`` says how many were in flight, how many were streaming,
    and how long after its send each row's first token came."""

    def __init__(self, detail: dict):
        super().__init__(json.dumps(detail))
        self.detail = detail


@dataclasses.dataclass
class Req:
    due: float                 # seconds from the schedule's origin
    prompt: list[int]
    max_tokens: int
    seed: int
    judged: bool = False       # due inside the window: its latencies count
    session: int = -1
    turn: int = 0
    turns_left: list = dataclasses.field(default_factory=list)


@dataclasses.dataclass
class Result:
    req: Req
    due_t: float = 0.0         # monotonic clock
    sent_t: float = 0.0
    first_t: float | None = None
    last_t: float | None = None
    chunks: list = dataclasses.field(default_factory=list)  # (t, n tokens)
    n_tokens: int = 0
    text: list = dataclasses.field(default_factory=list)
    usage: dict | None = None
    error: str | None = None

    @property
    def ok(self) -> bool:
        return (
            self.error is None and self.usage is not None
            and self.n_tokens == self.req.max_tokens
            and self.usage.get("completion_tokens") == self.req.max_tokens
        )


def _fixed_rng(traffic: dict, what: str) -> np.random.Generator:
    """A generator that depends on the traffic's parameters only."""
    blob = json.dumps({k: traffic.get(k) for k in (
        "prompt_tokens", "output_tokens", "arrival", "clients", "sharing",
        "sessions")}, sort_keys=True) + what
    return np.random.default_rng(zlib.crc32(blob.encode()))


def draw(dist: dict, n: int, rng: np.random.Generator) -> np.ndarray:
    """``n`` whole numbers from a length distribution."""
    kind = dist["dist"]
    if kind == "fixed":
        x = np.full((n,), float(dist["value"]))
    elif kind == "uniform":
        x = rng.uniform(dist["min"], dist["max"], n)
    elif kind == "lognormal":
        x = np.exp(rng.normal(math.log(dist["median"]), dist["sigma"], n))
    else:
        raise ValueError(f"unknown distribution {kind!r}")
    lo, hi = dist.get("min", 1), dist.get("max", 1 << 30)
    return np.clip(np.rint(x), lo, hi).astype(np.int64)


def arrival_gaps(arrival: dict, n: int, rng: np.random.Generator) -> np.ndarray:
    """``n`` gaps of a Poisson (exponential) or gamma process, scaled so
    that they sum to ``n / rate``: every run offers exactly the rate."""
    rate = float(arrival["rate_rps"])
    if arrival["process"] == "poisson":
        gaps = rng.exponential(1.0, n)
    else:
        b = float(arrival.get("burstiness", 1.0))
        gaps = rng.gamma(b, 1.0 / b, n)
    return gaps * (n / rate) / gaps.sum()


def _scaled(traffic: dict, rehearse: bool) -> dict:
    if not rehearse:
        return traffic
    t = json.loads(json.dumps(traffic))

    def cut(d, hi):
        d = dict(d)
        scale = hi / max(d.get("max", d.get("value", hi)), 1)
        for k in ("min", "max", "median", "value"):
            if k in d:
                d[k] = max(2, int(math.ceil(d[k] * min(1.0, scale))))
        return d

    t["prompt_tokens"] = cut(t["prompt_tokens"], REHEARSE["prompt_max"])
    t["output_tokens"] = cut(t["output_tokens"], REHEARSE["output_max"])
    if "clients" in t:
        t["clients"] = min(t["clients"], REHEARSE["clients_max"])
    if "arrival" in t:
        t["arrival"]["rate_rps"] = min(t["arrival"]["rate_rps"],
                                       REHEARSE["rate_max"])
    t["warm_seconds"] = min(t.get("warm_seconds", 0.0),
                            REHEARSE["warm_seconds_max"])
    t["ramp_blocker_tokens"] = min(t.get("ramp_blocker_tokens", 1024),
                                   REHEARSE["blocker_max"])
    # Rows of ten tokens end and are replaced all through a rehearsal.
    t["ramp_whole"] = False
    sh = t.get("sharing") or {}
    if sh.get("prefix_tokens"):
        sh["prefix_tokens"] = min(sh["prefix_tokens"], REHEARSE["prefix_max"])
    if t.get("sessions"):
        t["sessions"]["turns"] = {"dist": "fixed", "value": 2}
        t["sessions"]["think_s"] = {"dist": "fixed", "value": 0}
    return t


class Traffic:
    """The requests of one run: a warm phase, then the window, then
    (``--trace 2``) a tail of ``tail`` seconds of the same traffic, in
    which nothing is measured from the client's side. The tail changes
    no request of warm phase or window: its own requests are drawn after
    theirs (closed loop) or from a generator of its own (open loop)."""

    def __init__(self, traffic: dict, vocab: int, seed: int, seconds: float,
                 rehearse: bool = False, tail: float = 0.0):
        self.spec = t = _scaled(traffic, rehearse)
        self.vocab = vocab
        self.seconds = float(seconds)
        self.warm = float(t.get("warm_seconds", 0.0))
        self.tail = float(tail)
        self.rng = np.random.default_rng([int(seed), 0x10AD])
        self.sampling = {k: v for k, v in t["sampling"].items()
                         if k != "seed"}
        self.per_request_seed = t["sampling"].get("seed") == "per_request"
        sh = t.get("sharing") or {}
        self.prefix_tokens = int(sh.get("prefix_tokens", 0))
        self.prefix_share = float(sh.get("share", 0.0))
        pool = max(1, int(sh.get("pool", 1)))
        self.prefixes = [self._tokens(self.prefix_tokens) for _ in range(pool)]
        self.sessions = t.get("sessions")
        self.closed = t["loop"] == "closed"
        self.ramp_whole = self.closed and bool(t.get("ramp_whole", False))
        if self.closed:
            self.clients = int(t["clients"])
            self._closed_sizes()
        else:
            self.schedule = self._open_schedule()
            if self.tail > 0:
                window_rng = self.rng
                self.rng = np.random.default_rng([int(seed), 0x7A11])
                self.schedule += self._open_phase(
                    "tail", self.warm + self.seconds, self.tail,
                    first_session=len(self.schedule))
                self.rng = window_rng

    # -- pieces -----------------------------------------------------------

    def _tokens(self, n: int) -> list[int]:
        return self.rng.integers(0, self.vocab, int(n)).tolist()

    def _seed(self) -> int:
        return int(self.rng.integers(0, 2**31 - 1))

    def _prompt(self, n_new: int, shared: bool) -> list[int]:
        head = []
        if shared and self.prefix_tokens:
            head = self.prefixes[int(self.rng.integers(len(self.prefixes)))]
        return list(head) + self._tokens(n_new)

    def _sizes(self, n: int, what: str):
        """The fixed set of (prompt, output, shared) triples of a phase,
        in this seed's order."""
        fixed = _fixed_rng(self.spec, what)
        p = draw(self.spec["prompt_tokens"], n, fixed)
        o = draw(self.spec["output_tokens"], n, fixed)
        shared = np.arange(n) < round(self.prefix_share * n)
        return (self.rng.permutation(p), self.rng.permutation(o),
                self.rng.permutation(shared))

    # -- open loop ----------------------------------------------------------

    def _open_schedule(self) -> list[Req]:
        out: list[Req] = []
        for what, start, length in (("warm", 0.0, self.warm),
                                    ("window", self.warm, self.seconds)):
            out += self._open_phase(what, start, length, len(out))
        return out

    def _open_phase(self, what: str, start: float, length: float,
                    first_session: int) -> list[Req]:
        arr = self.spec["arrival"]
        n = int(round(float(arr["rate_rps"]) * length))
        if n <= 0:
            return []
        gaps = self.rng.permutation(
            arrival_gaps(arr, n, _fixed_rng(self.spec, what + "gaps")))
        due = start + np.cumsum(gaps) - gaps
        p, o, shared = self._sizes(n, what)
        out = []
        for i in range(n):
            req = Req(due=float(due[i]),
                      prompt=self._prompt(p[i], bool(shared[i])),
                      max_tokens=int(o[i]), seed=self._seed(),
                      judged=what == "window",
                      session=first_session + len(out))
            if self.sessions:
                req.turns_left = self._session_turns()
            out.append(req)
        return out

    def _session_turns(self) -> list[tuple[int, int, float]]:
        """Further turns of a session: (new tokens, output, think s)."""
        s = self.sessions
        n = int(draw(s["turns"], 1, self.rng)[0]) - 1
        if n <= 0:
            return []
        new = draw(self.spec["prompt_tokens"], n, self.rng)
        out = draw(self.spec["output_tokens"], n, self.rng)
        think = draw(s.get("think_s", {"dist": "fixed", "value": 0}), n,
                     self.rng)
        return [(int(a), int(b), float(c)) for a, b, c in zip(new, out, think)]

    def next_turn(self, res: Result, now_s: float) -> Req | None:
        """The session's next request, once ``res`` has come back: its
        prompt is the whole history plus the turn's new tokens."""
        turns = res.req.turns_left
        if not turns or not res.ok:
            return None
        new, out, think = turns[0]
        ids = [int(w[1:]) for w in " ".join(res.text).split()]
        due = now_s + think
        nxt = Req(due=due, prompt=res.req.prompt + ids + self._tokens(new),
                  max_tokens=out, seed=self._seed(),
                  judged=self.warm <= due < self.warm + self.seconds,
                  session=res.req.session, turn=res.req.turn + 1)
        nxt.turns_left = turns[1:]
        return nxt

    # -- closed loop --------------------------------------------------------

    def _closed_sizes(self) -> None:
        # A fixed set of sizes, handed out in this seed's order - in
        # blocks of one size per client, so that the clients' first
        # requests are the same set for every seed.
        c = self.clients
        fixed = _fixed_rng(self.spec, "closed")
        p = draw(self.spec["prompt_tokens"], 4 * c, fixed)
        o = draw(self.spec["output_tokens"], 4 * c, fixed)
        shared = np.arange(4 * c) % c < round(self.prefix_share * c)
        order = np.concatenate([b * c + self.rng.permutation(c)
                                for b in range(4)])
        self._pool = [(int(p[i]), int(o[i]), bool(shared[i])) for i in order]
        self._next = 0
        # The ramp: a blocker prompt first, so that every client's first
        # request is admitted in one go when the blocker's step ends (an
        # idle engine admits at once; a busy one by luck - warmup.py).
        self.blocker_tokens = int(self.spec.get("ramp_blocker_tokens", 1024))

    def closed_next(self, now_s: float) -> Req:
        p, o, shared = self._pool[self._next % len(self._pool)]
        self._next += 1
        return Req(due=now_s, prompt=self._prompt(p, shared), max_tokens=o,
                   seed=self._seed(),
                   judged=self.warm <= now_s < self.warm + self.seconds)

    def body(self, req: Req) -> dict:
        body = dict(self.sampling, model="bench", prompt=req.prompt,
                    max_tokens=req.max_tokens, stream=True, ignore_eos=True)
        if self.per_request_seed:
            body["seed"] = req.seed
        return body


# --------------------------------------------------------------------------
# Driver.
# --------------------------------------------------------------------------


def count_tokens(text: str) -> int:
    """Tokens in a streamed delta: the child's id-to-text map gives every id
    one whitespace-free word."""
    return len(text.split())


async def send_one(http, base: str, body: dict, res: Result,
                   timeout_s: float = 300.0) -> Result:
    import aiohttp

    res.sent_t = time.monotonic()
    try:
        async with http.post(
            base + "/v1/completions", json=body,
            timeout=aiohttp.ClientTimeout(total=timeout_s),
        ) as resp:
            if resp.status != 200:
                res.error = f"HTTP {resp.status}: {(await resp.text())[:200]}"
                return res
            async for raw in resp.content:
                if not raw.startswith(b"data: "):
                    continue
                now = time.monotonic()
                payload = raw[6:].strip()
                if payload == b"[DONE]":
                    break
                chunk = json.loads(payload)
                choice = chunk["choices"][0]
                n = count_tokens(choice.get("text") or "")
                if n:
                    if res.first_t is None:
                        res.first_t = now
                    res.last_t = now
                    res.n_tokens += n
                    res.chunks.append((now, n))
                    res.text.append(choice["text"])
                if chunk.get("usage"):
                    res.usage = chunk["usage"]
                if choice.get("finish_reason") == "abort":
                    res.error = "aborted by the server"
    except (aiohttp.ClientError, asyncio.TimeoutError, ValueError) as e:
        res.error = f"{type(e).__name__}: {e}"
    return res


class Run:
    """One run's clock, results and hooks. ``origin`` is the monotonic
    time of schedule second 0; the window is ``[w0, w1)``; the traffic
    goes on until ``end`` (``w1`` plus the traffic's tail).
    ``hold_back_s`` (tests only) keeps one client of a closed loop from
    sending for that long and holds the run to a whole ramp."""

    def __init__(self, traffic: Traffic, base: str, hooks: dict | None = None,
                 hold_back_s: float = 0.0):
        self.traffic = traffic
        self.base = base
        self.results: list[Result] = []
        self.hooks = hooks or {}
        self.origin = self.w0 = self.w1 = self.end = 0.0
        self.in_flight_at = {}
        self.hold_back_s = float(hold_back_s)
        self.ramp_whole = traffic.ramp_whole or self.hold_back_s > 0
        self.ramp: dict | None = None

    async def _at(self, t: float, coro_fn):
        await asyncio.sleep(max(0.0, t - time.monotonic()))
        return await coro_fn()

    def _in_flight(self) -> int:
        return sum(1 for r in self.results
                   if r.sent_t and r.usage is None and r.error is None)

    def note_in_flight(self, name: str) -> None:
        self.in_flight_at[name] = self._in_flight()

    def check_ramp(self) -> None:
        """A probe's premise, at the opening of the window: every
        client's row is in flight and has had its first token (and
        nothing else is in flight: the blocker has long ended)."""
        flying = [r for r in self.results
                  if r.sent_t and r.usage is None and r.error is None]
        streaming = [r for r in flying if r.first_t is not None]
        self.ramp = {
            "clients": self.traffic.clients, "in_flight": len(flying),
            "streaming": len(streaming),
            "first_token_after_send_s": sorted(
                round(r.first_t - r.sent_t, 4) for r in streaming),
            "errors": [r.error for r in self.results if r.error][:3]}
        if not (len(flying) == len(streaming) == self.traffic.clients):
            raise RampSplit(self.ramp)

    def end_tail(self) -> None:
        """The tail has served its purpose: send nothing more."""
        self.end = min(self.end, time.monotonic())

    async def _one(self, http, req: Req) -> Result:
        res = Result(req=req, due_t=self.origin + req.due)
        self.results.append(res)
        await send_one(http, self.base, self.traffic.body(req), res)
        return res

    async def _session(self, http, req: Req) -> None:
        """A request, and where it is a session's turn, the turns after."""
        while req is not None:
            await asyncio.sleep(max(0.0, self.origin + req.due
                                    - time.monotonic()))
            if (self.traffic.tail and time.monotonic() >= self.end
                    and self.origin + req.due >= self.w1):
                return             # the tail's request, the tail over
            res = await self._one(http, req)
            now = time.monotonic()
            if now >= self.end:
                return
            req = self.traffic.next_turn(res, now - self.origin)

    async def _blocker(self, http) -> None:
        tr = self.traffic
        req = Req(due=0.0, prompt=tr._tokens(tr.blocker_tokens), max_tokens=1,
                  seed=tr._seed())
        res = Result(req=req, due_t=time.monotonic())
        self.results.append(res)
        await send_one(http, self.base, tr.body(req), res)

    async def _client(self, http, hold_back_s: float = 0.0) -> None:
        await asyncio.sleep(0.02 + hold_back_s)      # behind the blocker
        while True:
            now = time.monotonic()
            if now >= self.end:
                return
            req = self.traffic.closed_next(now - self.origin)
            res = Result(req=req, due_t=now)
            self.results.append(res)
            await send_one(http, self.base, self.traffic.body(req), res)
            if not res.ok:
                await asyncio.sleep(0.05)   # never spin on a dead server

    async def go(self, drain_s: float = 150.0) -> None:
        import aiohttp

        tr = self.traffic
        conn = aiohttp.TCPConnector(limit=0)
        async with aiohttp.ClientSession(connector=conn) as http:
            self.http = http
            self.origin = time.monotonic() + 0.05
            self.w0 = self.origin + tr.warm
            self.w1 = self.w0 + tr.seconds
            self.end = self.w1 + tr.tail
            side = [asyncio.ensure_future(self._at(self.origin + off, fn))
                    for off, fn in self.hooks.get("at", [])]
            marks = [asyncio.ensure_future(self._at(t, self._mark(name)))
                     for name, t in (("w0", self.w0), ("w1", self.w1))]
            if tr.closed:
                work = [asyncio.ensure_future(self._client(
                    http, self.hold_back_s if i == 0 else 0.0))
                    for i in range(tr.clients)]
                work.append(asyncio.ensure_future(self._blocker(http)))
            else:
                work = [asyncio.ensure_future(self._session(http, r))
                        for r in tr.schedule]
            # Until all is sent and answered, or a hook, a mark or a
            # client raises: that fails the run there and then.
            done, pending = await asyncio.wait(
                work + side + marks,
                timeout=tr.warm + tr.seconds + tr.tail + drain_s,
                return_when=asyncio.FIRST_EXCEPTION,
            )
            for task in pending:
                task.cancel()
            await asyncio.gather(*pending, return_exceptions=True)
            for task in done:
                task.result()
            for r in self.results:
                if r.usage is None and r.error is None:
                    r.error = "not finished when the drain ended"

    def _mark(self, name: str):
        async def mark():
            self.note_in_flight(name)
            if name == "w0" and self.ramp_whole:
                self.check_ramp()
            fn = self.hooks.get(name)
            if fn is not None:
                await fn()
        return mark
