"""Elastic swarm: zero-dropped-request node churn via live KV migration.

Covers the whole docs/resilience.md stack: the portable checkpoint wire
format (round-trip + corrupt-frame fuzz), resumed-request accounting
(folded outputs, stream-relative budgets), engine-level KV-image
harvest/adopt bit-exactness, the scheduler's churn guards (busy
probation, dead-peer sweep acceleration + CacheIndex invalidation, drain
directives, CacheIndex-scored migration targeting, where_is), the
dispatcher's post-dispatch re-route rung, the chaos harness's
determinism, and the end-to-end contract: kill a pipeline stage
mid-decode and every affected request migrates to a surviving pipeline
and finishes bit-identically to an unchurned run — zero aborts — under
the overlapped loop and K>1 multi-step windows, greedy and seeded.
"""

import dataclasses
import os
import threading
import time

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from parallax_tpu.config import normalize_config
from parallax_tpu.runtime.checkpoint import (
    CheckpointError,
    KVImage,
    RequestCheckpoint,
    build_resumed_request,
    checkpoint_from_request,
    checkpoint_from_wire,
    checkpoint_to_wire,
)
from parallax_tpu.runtime.request import Request, RequestStatus, SamplingParams
from parallax_tpu.scheduling.scheduler import GlobalScheduler
from parallax_tpu.testing.chaos import ChaosController, _ChaosDropped
from parallax_tpu.utils.hw import HardwareInfo

TINY = normalize_config(dict(
    architectures=["Qwen2ForCausalLM"],
    hidden_size=64, num_hidden_layers=4, num_attention_heads=4,
    num_key_value_heads=2, intermediate_size=128, vocab_size=151,
    max_position_embeddings=256,
))

V5E = HardwareInfo("v5e", 1, 197.0, 16.0, 819.0, 186.0)


def wait_for(cond, timeout=10.0, interval=0.01):
    end = time.monotonic() + timeout
    while time.monotonic() < end:
        if cond():
            return True
        time.sleep(interval)
    return False


# -- checkpoint wire format ------------------------------------------------


def _mk_ckpt(with_kv=True, n_out=5) -> RequestCheckpoint:
    rng = np.random.default_rng(3)
    kv = None
    if with_kv:
        kv = KVImage(
            page_size=4, start_layer=0, end_layer=2, kv_dtype="float32",
            prefix_tokens=4, computed_tokens=4 + 8,
            layers=[
                rng.standard_normal((2, 2, 4, 2, 8), dtype=np.float32)
                for _ in range(2)
            ],
        )
    return RequestCheckpoint(
        request_id="ck-1",
        prompt_ids=[5, 6, 7, 8, 9, 10, 11],
        output_ids=list(range(20, 20 + n_out)),
        output_logprobs=[-0.5] * n_out,
        sampling_params=SamplingParams(
            temperature=0.8, top_k=8, seed=42, max_new_tokens=32,
        ).to_dict(),
        eos_token_ids=[0],
        lora_id=None,
        routing_table=["w2", "w3"],
        age_s=1.25,
        parked_wall=123.0,
        traced=True,
        kv=kv,
    )


class TestCheckpointWire:
    def test_roundtrip_with_kv(self):
        ck = _mk_ckpt()
        # Through msgpack too: the frame must survive real serialization.
        import msgpack

        wire = msgpack.unpackb(
            msgpack.packb(checkpoint_to_wire(ck), use_bin_type=True),
            raw=False,
        )
        back = checkpoint_from_wire(wire)
        assert back.request_id == ck.request_id
        assert back.prompt_ids == ck.prompt_ids
        assert back.output_ids == ck.output_ids
        assert back.output_logprobs == ck.output_logprobs
        assert back.routing_table == ck.routing_table
        assert back.traced is True
        assert back.kv is not None
        assert back.kv.signature == ck.kv.signature
        assert back.kv.prefix_tokens == 4
        for a, b in zip(back.kv.layers, ck.kv.layers):
            assert a.dtype == b.dtype and (a == b).all()

    def test_roundtrip_without_kv(self):
        ck = _mk_ckpt(with_kv=False)
        back = checkpoint_from_wire(checkpoint_to_wire(ck))
        assert back.kv is None
        assert back.output_ids == ck.output_ids

    @pytest.mark.parametrize("mutate,desc", [
        (lambda d: d.update(v=99), "bad version"),
        (lambda d: d.pop("rid"), "missing rid"),
        (lambda d: d.update(rid=7), "non-string rid"),
        (lambda d: d.update(prompt_ids=[]), "empty prompt"),
        (lambda d: d.update(prompt_ids="abc"), "prompt not a list"),
        (lambda d: d.update(prompt_ids=[1, "x"]), "non-int token"),
        (lambda d: d.update(prompt_ids=list(range(1 << 20 | 1))),
         "oversized prompt"),
        (lambda d: d.update(
            output_logprobs=[-0.1] * (len(d["output_ids"]) + 1)
        ), "more logprobs than tokens"),
        (lambda d: d.update(sampling_params=[1, 2]),
         "sampling_params not a map"),
        (lambda d: d.update(routing_table=[1]), "routing table non-str"),
        (lambda d: d["kv"].update(page_size=0), "zero page size"),
        (lambda d: d["kv"].update(prefix_tokens=3),
         "prefix not page aligned"),
        (lambda d: d["kv"].update(prefix_tokens=99999,
                                  computed_tokens=99999 + 8),
         "kv covers more than checkpoint"),
        (lambda d: d["kv"].update(layers=[]), "kv with no layers"),
        (lambda d: d["kv"]["layers"].__setitem__(0, {"bogus": 1}),
         "malformed layer tensor"),
        (lambda d: d["kv"]["layers"][0].update(
            data=d["kv"]["layers"][0]["data"][:-8]
        ), "truncated layer bytes"),
        (lambda d: d["kv"]["layers"][1].update(
            shape=[3] + list(d["kv"]["layers"][1]["shape"])[1:]
        ), "layers disagree on page count"),
        (lambda d: d["kv"].update(computed_tokens=4),
         "empty image token span"),
    ])
    def test_corrupt_frames_rejected(self, mutate, desc):
        d = checkpoint_to_wire(_mk_ckpt())
        mutate(d)
        with pytest.raises(CheckpointError):
            checkpoint_from_wire(d)
        # And a clean frame still parses (the fuzz case didn't poison
        # shared state).
        checkpoint_from_wire(checkpoint_to_wire(_mk_ckpt()))

    def test_truncated_page_count_rejected(self):
        d = checkpoint_to_wire(_mk_ckpt())
        # 8 image tokens at page_size 4 need 2 pages (+1 slack): claim
        # 16 tokens over the same 2 pages -> under-coverage.
        d["kv"]["computed_tokens"] = 4 + 16
        d["prompt_ids"] = list(range(1, 40))   # keep total-token bound ok
        with pytest.raises(CheckpointError, match="do not cover"):
            checkpoint_from_wire(d)


# -- resumed-request accounting --------------------------------------------


class TestResumedRequest:
    def _req(self, n_out=4, **sp):
        req = Request(
            "r1", prompt_ids=[1, 2, 3],
            sampling_params=SamplingParams(
                max_new_tokens=sp.pop("max_new_tokens", 10), **sp
            ),
        )
        for i in range(n_out):
            req.status = RequestStatus.DECODING
            req.commit_token(50 + i, logprob=-0.25 * i)
        return req

    def test_fold_and_offsets(self):
        ck = checkpoint_from_request(self._req(), routing_table=["w9"])
        res = build_resumed_request(ck)
        assert res.prompt_ids == [1, 2, 3, 50, 51, 52, 53]
        assert res.output_ids == []
        assert res.output_offset == 4
        assert res.num_generated == 4
        assert res.full_output_ids == [50, 51, 52, 53]
        assert res.prior_output_ids == [50, 51, 52, 53]
        assert res.full_output_logprobs == [0.0, -0.25, -0.5, -0.75]
        assert res.routing_table == ["w9"]

    def test_budgets_count_from_original_position(self):
        res = build_resumed_request(
            checkpoint_from_request(self._req(n_out=4, max_new_tokens=6))
        )
        res.status = RequestStatus.DECODING
        res.commit_token(60)
        assert not res.status.is_finished
        res.commit_token(61)          # 4 folded + 2 fresh = budget of 6
        assert res.status is RequestStatus.FINISHED_LENGTH
        assert res.full_output_ids == [50, 51, 52, 53, 60, 61]

    def test_min_new_gate_counts_folded_tokens(self):
        req = self._req(n_out=3, max_new_tokens=10)
        req.sampling_params.min_new_tokens = 2
        req.eos_token_ids = (99,)
        res = build_resumed_request(checkpoint_from_request(req))
        res.eos_token_ids = (99,)
        res.status = RequestStatus.DECODING
        res.commit_token(99)   # min_new already satisfied by folded toks
        assert res.status is RequestStatus.FINISHED_EOS

    def test_recheckpoint_never_nests(self):
        """A resumed request that migrates AGAIN peels its folded prior
        outputs back out: the second checkpoint carries the ORIGINAL
        prompt and the full flat output stream."""
        res = build_resumed_request(
            checkpoint_from_request(self._req(n_out=4))
        )
        res.status = RequestStatus.DECODING
        res.commit_token(60, logprob=-1.0)
        ck2 = checkpoint_from_request(res)
        assert ck2.prompt_ids == [1, 2, 3]
        assert ck2.output_ids == [50, 51, 52, 53, 60]
        assert len(ck2.output_logprobs) == 5
        res2 = build_resumed_request(ck2)
        assert res2.prompt_ids == [1, 2, 3, 50, 51, 52, 53, 60]
        assert res2.output_offset == 5


# -- chaos harness determinism ---------------------------------------------


class TestChaosHarness:
    class _FakeTransport:
        def __init__(self, peer_id):
            self.peer_id = peer_id
            self.sent = []

        def call(self, peer, method, payload, timeout=30.0):
            self.sent.append((peer, method))
            return "ok"

        def send(self, peer, method, payload):
            self.call(peer, method, payload)

    def _drive(self, seed):
        chaos = ChaosController(seed=seed)
        t = chaos.wrap(self._FakeTransport("a"))
        chaos.drop_frames(method="beat", p=0.5)
        pattern = []
        for i in range(64):
            try:
                t.call("b", "beat", {"i": i})
                pattern.append(1)
            except _ChaosDropped:
                pattern.append(0)
        return pattern

    def test_seeded_faults_replay_identically(self):
        assert self._drive(7) == self._drive(7)
        assert self._drive(7) != self._drive(8)

    def test_kill_severs_both_directions(self):
        chaos = ChaosController()
        a = chaos.wrap(self._FakeTransport("a"))
        b = chaos.wrap(self._FakeTransport("b"))

        class _W:
            node_id = "b"

            def stop(self):
                pass

        chaos.kill(_W())
        with pytest.raises(_ChaosDropped):
            a.call("b", "x", None)
        with pytest.raises(_ChaosDropped):
            b.call("a", "x", None)
        a.call("c", "x", None)   # unrelated peers unaffected

    def test_rule_limit_and_stats(self):
        chaos = ChaosController()
        t = chaos.wrap(self._FakeTransport("a"))
        chaos.drop_frames(method="x", limit=2)
        for _ in range(2):
            with pytest.raises(_ChaosDropped):
                t.call("b", "x", None)
        t.call("b", "x", None)   # budget spent -> passes
        assert chaos.stats["dropped"] == 2


# -- scheduler churn guards ------------------------------------------------


class TestSchedulerChurnGuards:
    def scheduler(self, n=2, **kw):
        sched = GlobalScheduler(TINY, min_nodes_bootstrapping=1,
                                heartbeat_timeout_s=2.0, **kw)
        sched.start()
        for i in range(n):
            sched.enqueue_join(f"n{i}", V5E)
        assert wait_for(lambda: len(sched.manager.pipelines) >= n), (
            sched.cluster_status()
        )
        for i in range(n):
            sched.enqueue_update(f"n{i}", is_ready=True)
        assert wait_for(
            lambda: all(
                sched.manager.get(f"n{i}").is_ready for i in range(n)
            )
        )
        return sched

    def test_busy_probation_extends_grace(self):
        sched = self.scheduler()
        try:
            sched.enqueue_update("n0", busy=True)
            assert wait_for(lambda: sched.manager.get("n0").reported_busy)
            node = sched.manager.get("n0")
            # Past the base timeout but inside the extended grace:
            # suspect, NOT evicted.
            node.last_heartbeat -= 3.0
            sched._sweep_heartbeats()
            assert sched.manager.get("n0") is not None
            assert sched.manager.get("n0").suspect
            st = sched.cluster_status()
            flags = {
                nd["node_id"]: nd["suspect"]
                for p in st["pipelines"] for nd in p["nodes"]
            }
            assert flags["n0"] is True
            # Past the extended grace too: now it's dead.
            node.last_heartbeat -= 2.0 * sched.BUSY_GRACE_FACTOR + 1.0
            sched._sweep_heartbeats()
            assert sched.manager.get("n0") is None
        finally:
            sched.stop()

    def test_not_busy_node_evicted_at_base_timeout(self):
        sched = self.scheduler()
        try:
            sched.manager.get("n0").last_heartbeat -= 3.0
            sched._sweep_heartbeats()
            assert sched.manager.get("n0") is None
        finally:
            sched.stop()

    def test_heartbeat_clears_probation(self):
        sched = self.scheduler()
        try:
            sched.enqueue_update("n0", busy=True)
            assert wait_for(lambda: sched.manager.get("n0").reported_busy)
            sched.manager.get("n0").last_heartbeat -= 3.0
            sched._sweep_heartbeats()
            assert sched.manager.get("n0").suspect
            sched.enqueue_update("n0", busy=False)
            assert wait_for(
                lambda: not sched.manager.get("n0").reported_busy
            )
            assert not sched.manager.get("n0").suspect
        finally:
            sched.stop()

    def test_peer_down_clears_cache_index_and_accelerates_sweep(self):
        from parallax_tpu.runtime.radix_cache import block_hash_chain

        sched = self.scheduler()
        try:
            toks = list(range(32))
            sched.enqueue_update("n0", cache_digests={
                "seq": 1, "block": 4,
                "full": block_hash_chain(toks, 4),
            })
            assert wait_for(
                lambda: len(sched.manager.get("n0").cache_index) > 0
            )
            sched.enqueue_peer_down("n1", "n0", "send failed")
            # The dead replica's prefixes must stop scoring NOW.
            assert wait_for(
                lambda: len(sched.manager.get("n0").cache_index) == 0
            )
            assert sched.manager.get("n0").peer_down_at is not None
            # Inside the base timeout but past the accelerated one.
            sched.manager.get("n0").last_heartbeat -= 1.8
            sched._sweep_heartbeats()
            assert sched.manager.get("n0") is None
            # The survivor is untouched.
            assert sched.manager.get("n1") is not None
        finally:
            sched.stop()

    def test_live_beat_disproves_peer_down(self):
        sched = self.scheduler()
        try:
            sched.enqueue_peer_down("n1", "n0", "send failed")
            assert wait_for(
                lambda: sched.manager.get("n0").peer_down_at is not None
            )
            sched.enqueue_update("n0", load=0.0)
            assert wait_for(
                lambda: sched.manager.get("n0").peer_down_at is None
            )
        finally:
            sched.stop()

    def test_leave_flags_surviving_heads_for_drain(self):
        """A 2-stage pipeline's tail death must flag the HEAD for drain
        (checkpoint away, don't abort); a dying head flags nobody."""
        from parallax_tpu.scheduling.node_management import (
            NodeManager,
            Pipeline,
        )
        from parallax_tpu.scheduling.node import Node

        sched = GlobalScheduler(TINY, min_nodes_bootstrapping=1)
        mgr = NodeManager(TINY.num_hidden_layers)
        head = Node(node_id="h", hardware=V5E, model=TINY)
        tail = Node(node_id="t", hardware=V5E, model=TINY)
        head.set_layers(0, 2)
        tail.set_layers(2, 4)
        for n in (head, tail):
            n.is_ready = True
            mgr.add(n)
        mgr.register_pipelines([Pipeline(nodes=[head, tail])])
        sched.manager = mgr
        sched._handle_leave("t")
        assert "t" in head.pending_drain
        assert sched.drain_requested("h") == ["t"]
        assert sched.drain_requested("h") == []   # consumed
        assert sched.migration_stats["drains"] == 1

    def test_migration_targets_prefer_warm_replica(self):
        from parallax_tpu.runtime.radix_cache import block_hash_chain

        sched = self.scheduler(n=2, routing="cache_aware")
        try:
            toks = list(range(8 * 4))
            chain = block_hash_chain(toks, 4)
            sched.enqueue_update("n1", cache_digests={
                "seq": 1, "block": 4, "full": chain,
            })
            assert wait_for(
                lambda: len(sched.manager.get("n1").cache_index) > 0
            )
            targets = sched.choose_migration_targets([{
                "rid": "m1", "prompt_tokens": len(toks),
                "chains": {"4": chain}, "lora_id": None,
            }], exclude={"nX"})
            assert targets["m1"]["path"] == ["n1"]
            assert targets["m1"]["predicted_cached_tokens"] > 0
            # Excluding the warm replica forces the cold one.
            t2 = sched.choose_migration_targets([{
                "rid": "m2", "prompt_tokens": len(toks),
                "chains": {"4": chain}, "lora_id": None,
            }], exclude={"n1"})
            assert t2["m2"]["path"] == ["n0"]
        finally:
            sched.stop()

    def test_where_is_follows_migrations(self):
        sched = self.scheduler()
        try:
            assert sched.migrated_head("r1") is None
            sched.record_migration("r1", "n1")
            assert sched.migrated_head("r1") == "n1"
            assert sched.migration_stats["recorded"] == 1
        finally:
            sched.stop()

    def test_reenqueue_preserves_original_arrival(self):
        sched = self.scheduler()
        try:
            t0 = time.monotonic() - 5.0
            pr = sched.receive_request("retry-1", arrival_time=t0)
            assert pr.enqueue_time == t0
            assert pr.event.wait(5.0) and pr.path_ids
        finally:
            sched.stop()


# -- engine-level KV image harvest/adopt bit-exactness ---------------------


@pytest.fixture(scope="module")
def tiny_model_and_params():
    from parallax_tpu.models.base import StageModel

    cfg = normalize_config(dict(
        architectures=["Qwen2ForCausalLM"], hidden_size=64,
        num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
        intermediate_size=128, vocab_size=258, max_position_embeddings=512,
        tie_word_embeddings=False,
    ))
    model = StageModel(cfg, 0, 2, use_pallas=False)
    params = model.init_params(jax.random.key(0), dtype=jnp.float32)
    return model, params


def _mk_engine(tiny_model_and_params, **over):
    from parallax_tpu.runtime.engine import EngineConfig, StageEngine

    model, params = tiny_model_and_params
    cfg = dict(
        page_size=8, num_pages=64, max_model_len=256, kv_dtype="float32",
        host_cache_bytes=1 << 24, enable_prefix_cache=True,
    )
    cfg.update(over)
    return StageEngine(model, params, EngineConfig(**cfg))


def _drive(eng, n_guard=5000):
    from parallax_tpu.runtime.engine import drive_step

    pending, guard = None, 0
    while (eng.has_work() or pending is not None) and guard < n_guard:
        guard += 1
        _outs, pending = drive_step(eng, pending)
    assert guard < n_guard


def _drive_tokens(eng, req, n_tokens, n_guard=5000):
    """Drive until the request has committed >= n_tokens, then park it
    the way the node does: flag it ``migrating`` (the scheduler plans it
    no further, so no window is handed over) and drive until no step in
    flight holds it — the row is quiescent (extractable). The window
    already enqueued behind the one that reached ``n_tokens`` still
    commits: the row ends up to two windows past ``n_tokens``."""
    from parallax_tpu.runtime.engine import drive_step

    pending, guard = None, 0
    while len(req.output_ids) < n_tokens and guard < n_guard:
        guard += 1
        _outs, pending = drive_step(eng, pending)
    req.migrating = True
    while pending is not None and guard < n_guard:
        guard += 1
        _outs, pending = drive_step(eng, pending)
    req.migrating = False
    assert guard < n_guard and not eng._inflight


@pytest.mark.parametrize("sp_kw", [
    dict(temperature=0.0),
    dict(temperature=0.8, top_k=8, seed=1234),
], ids=["greedy", "seeded"])
def test_kv_image_migration_bit_identical(tiny_model_and_params, sp_kw):
    """Full engine-to-engine KV handoff: park mid-decode on A, harvest
    the pinned host image, serialize the checkpoint over the REAL wire
    form, adopt on B (layout-identical stage), resume — the continuation
    matches an uninterrupted run token for token, with no re-prefill."""
    prompt = [3, 5, 7, 11, 13, 17, 19, 23] * 2
    sp = SamplingParams(max_new_tokens=32, ignore_eos=True, **sp_kw)

    # Uninterrupted baseline.
    eng0 = _mk_engine(tiny_model_and_params)
    base = Request("base", prompt_ids=list(prompt),
                   sampling_params=dataclasses.replace(sp))
    eng0.submit(base)
    _drive(eng0)
    assert base.status.is_finished and len(base.output_ids) == 32

    # Source engine: run to mid-decode, park, harvest, checkpoint.
    eng_a = _mk_engine(tiny_model_and_params)
    mig = Request("mig", prompt_ids=list(prompt),
                  sampling_params=dataclasses.replace(sp))
    eng_a.submit(mig)
    _drive_tokens(eng_a, mig, 6)
    assert not mig.status.is_finished
    assert eng_a.cache.preempt_to_host(mig)
    image = eng_a.harvest_kv_image(mig)
    assert image is not None and image.computed_tokens > 0
    extracted = eng_a.extract("mig")
    assert extracted is mig
    ckpt = checkpoint_from_request(mig, routing_table=["B"], kv=image)
    eng_a.cache.release(mig)
    wire = checkpoint_from_wire(checkpoint_to_wire(ckpt))

    # Target engine: adopt the image and resume.
    eng_b = _mk_engine(tiny_model_and_params)
    res = build_resumed_request(wire)
    assert wire.kv is not None
    assert eng_b.adopt_checkpoint_kv(res, wire.kv)
    assert res.status is RequestStatus.PREEMPTED
    assert eng_b.submit(res)
    _drive(eng_b)
    assert res.status.is_finished
    # No prefill re-compute happened: the image swap-in covered the
    # whole committed context.
    assert eng_b.cache.stats.resumes == 1
    assert res.full_output_ids == base.output_ids
    assert res.status == base.status


def test_adopt_falls_back_cleanly_on_layout_mismatch(
    tiny_model_and_params,
):
    """A target with a different page size must refuse the image (the
    caller then re-prefills) without corrupting its own state."""
    eng_a = _mk_engine(tiny_model_and_params)
    mig = Request("m2", prompt_ids=[3, 5, 7, 11] * 3,
                  sampling_params=SamplingParams(temperature=0.0,
                                                 max_new_tokens=32,
                                                 ignore_eos=True))
    eng_a.submit(mig)
    _drive_tokens(eng_a, mig, 5)
    assert eng_a.cache.preempt_to_host(mig)
    image = eng_a.harvest_kv_image(mig)
    assert image is not None
    eng_a.extract("m2")
    ckpt = checkpoint_from_request(mig, kv=image)
    eng_a.cache.release(mig)

    # A different page size is a different KV-page signature: refused,
    # request untouched.
    eng_mismatch = _mk_engine(
        tiny_model_and_params, page_size=4, num_pages=128
    )
    res = build_resumed_request(ckpt)
    assert not eng_mismatch.adopt_checkpoint_kv(res, ckpt.kv)
    assert res.status is not RequestStatus.PREEMPTED

    # A layout-identical target WITHOUT a host tier also refuses the
    # image — the replay rung (original-prompt re-prefill +
    # teacher-forced outputs) still reproduces the exact stream.
    eng_b = _mk_engine(tiny_model_and_params, host_cache_bytes=0)
    assert not eng_b.adopt_checkpoint_kv(res, ckpt.kv)
    assert res.status is not RequestStatus.PREEMPTED
    res = build_resumed_request(ckpt, replay=True)
    assert res.prompt_ids == [3, 5, 7, 11] * 3
    # Adaptive multi-step decode may commit past the 5 requested tokens;
    # the replay stream must carry exactly what the checkpoint recorded.
    assert res.replay_ids == list(ckpt.output_ids)
    assert len(res.replay_ids) >= 5
    assert eng_b.submit(res)
    _drive(eng_b)
    assert res.status.is_finished
    assert res.replay_ids == []   # fully consumed

    eng0 = _mk_engine(tiny_model_and_params)
    base = Request("b2", prompt_ids=[3, 5, 7, 11] * 3,
                   sampling_params=SamplingParams(temperature=0.0,
                                                  max_new_tokens=32,
                                                  ignore_eos=True))
    eng0.submit(base)
    _drive(eng0)
    assert res.full_output_ids == base.output_ids


# -- end-to-end: node kill mid-decode, zero dropped requests ---------------


def _stage_params(model):
    return model.init_params(
        jax.random.key(model.start_layer * 1000 + model.end_layer),
        dtype=jnp.float32,
    )


def _churn_swarm(monkeypatch, chaos, decode_lookahead, overlap):
    """4 workers -> two 2-stage pipelines behind a scheduler, plus a
    SwarmClient, all over chaos-wrapped loopback transports."""
    from parallax_tpu.backend.run import SwarmClient
    from parallax_tpu.backend.scheduler_service import SchedulerService
    from parallax_tpu.p2p.node import WorkerNode
    from parallax_tpu.p2p.transport import LoopbackTransport
    from parallax_tpu.runtime.engine import EngineConfig
    from parallax_tpu.scheduling import node as node_mod

    monkeypatch.setattr(
        node_mod.RooflinePerformanceModel, "max_layers_in_memory",
        lambda self, kv_fraction=0.35: 2,
    )
    registry: dict = {}
    # cache_aware routing turns want_digests on in allocations, so the
    # workers' engines track radix digests (Python manager) and the
    # migration flow can score targets through the CacheIndex.
    sched = GlobalScheduler(TINY, min_nodes_bootstrapping=2,
                            heartbeat_timeout_s=3.0,
                            routing="cache_aware")
    service = SchedulerService(
        sched, chaos.wrap(LoopbackTransport("sched", registry)),
        join_timeout_s=30.0,
    )
    service.start()
    ecfg = EngineConfig(
        page_size=8, num_pages=96, max_model_len=192, kv_dtype="float32",
        max_num_tokens_per_batch=192, max_batch_size=4,
        overlap_steps=overlap, decode_lookahead=decode_lookahead,
        # Digest tracking (Python manager) so the test can assert the
        # migrated streams' block chains landed in a surviving radix.
        cache_digests=True,
    )
    workers = [
        WorkerNode(
            transport=chaos.wrap(
                LoopbackTransport(f"cw{i}", registry)
            ),
            scheduler_peer="sched",
            model_config=TINY,
            engine_config=dataclasses.replace(ecfg),
            load_params=_stage_params,
            heartbeat_interval_s=0.1,
        )
        for i in range(4)
    ]
    starters = [threading.Thread(target=w.start) for w in workers]
    for s in starters:
        s.start()
    for s in starters:
        s.join(timeout=120.0)
    assert wait_for(
        lambda: (
            len(sched.manager.pipelines) >= 2
            and all(
                n.is_ready
                for p in sched.manager.pipelines for n in p.nodes
            )
        ),
        timeout=60.0,
    ), sched.cluster_status()
    client = SwarmClient(
        chaos.wrap(LoopbackTransport("client", registry)), service,
        poll_interval_s=0.002,
    )
    return sched, service, client, workers


def _serve(client, tag, prompts_and_sp, on_tokens=None):
    """Route+submit every request via the REAL client poll path; returns
    the mirror Requests after all finish. ``on_tokens(i, req)`` fires
    once per request when its mirror first shows >= 2 tokens."""
    reqs, evs = [], []
    for i, (prompt, sp) in enumerate(prompts_and_sp):
        rid = f"{tag}-{i}"
        path = client.route(rid, prompt_ids=list(prompt))
        assert path, f"no path for {rid}"
        req = Request(
            request_id=rid, prompt_ids=list(prompt),
            sampling_params=dataclasses.replace(sp),
            routing_table=list(path),
        )
        evs.append(client.submit(req))
        reqs.append(req)
    if on_tokens is not None:
        fired = set()
        deadline = time.monotonic() + 60.0
        while len(fired) < len(reqs) and time.monotonic() < deadline:
            for i, req in enumerate(reqs):
                if i not in fired and (
                    len(req.output_ids) >= 2 or req.status.is_finished
                ):
                    fired.add(i)
                    on_tokens(i, req)
            time.sleep(0.002)
    for rid_ev, req in zip(evs, reqs):
        assert rid_ev.wait(90.0), (
            f"{req.request_id} stuck: {req.status} "
            f"({len(req.output_ids)} tokens)"
        )
    return reqs


GEN = 24


def _request_set():
    base = [7, 8, 9, 10] * 4
    out = []
    for i in range(4):
        sp = (
            SamplingParams(temperature=0.0, max_new_tokens=GEN,
                           ignore_eos=True)
            if i % 2 == 0 else
            SamplingParams(temperature=0.8, top_k=8, seed=77 + i,
                           max_new_tokens=GEN, ignore_eos=True)
        )
        out.append((base + [30 + i, 40 + i, 50 + i], sp))
    return out


@pytest.mark.slow
@pytest.mark.parametrize("decode_lookahead,overlap", [
    (1, True),
    (4, True),
], ids=["overlap-k1", "multistep-k4"])
def test_node_kill_mid_decode_migrates_bit_identically(
    monkeypatch, decode_lookahead, overlap,
):
    """Kill a pipeline's TAIL stage while its requests are mid-decode:
    the head parks them as checkpoints, the scheduler routes them to the
    surviving pipeline, the target resumes via re-prefill, and every
    stream finishes bit-identical to the unchurned baseline — zero
    aborts, pollers follow the {"migrated": ...} redirect.

    The whole episode runs under the lock-order sanitizer
    (docs/static_analysis.md): constructing the ChaosController enables
    it, so every make_lock() lock the swarm creates below is
    instrumented, and the teardown asserts the kill-migration produced
    zero lock-graph cycles."""
    from parallax_tpu.analysis import sanitizer

    chaos = ChaosController(seed=11)          # enables the sanitizer
    sanitizer.reset()                         # this test's window only
    sched, service, client, workers = _churn_swarm(
        monkeypatch, chaos, decode_lookahead, overlap,
    )
    by_id = {w.node_id: w for w in workers}
    try:
        # Phase A: clean baseline on the same swarm.
        baseline = _serve(client, "base", _request_set())
        assert all(
            r.status.value != "finished_abort" for r in baseline
        ), [(r.request_id, r.status, r.abort_reason) for r in baseline]
        base_streams = {
            r.request_id.split("-", 1)[1]: list(r.output_ids)
            for r in baseline
        }
        assert all(len(s) == GEN for s in base_streams.values())

        # Phase B: same requests; kill the tail under the first-routed
        # request's pipeline once it is visibly mid-decode. Slow the
        # victim pipeline's inter-stage link a little first so the kill
        # reliably lands mid-stream.
        counters_before = _migrations_total()
        victim: dict = {}
        lock = threading.Lock()

        def on_tokens(i, req):
            with lock:
                if victim:
                    return
                tail = req.routing_table[-1]
                if tail == req.routing_table[0]:
                    return   # single-stage path; should not happen here
                victim["tail"] = tail
                victim["pipeline"] = list(req.routing_table)
                chaos.kill(by_id[tail])

        churn = _serve(client, "churn", _request_set(),
                       on_tokens=on_tokens)
        assert victim, "kill never fired"
        dead_tail = victim["tail"]

        aborted = [
            r.request_id for r in churn
            if r.status.value == "finished_abort"
        ]
        assert aborted == [], (
            f"dropped requests {aborted} after killing {dead_tail}"
        )
        for r in churn:
            key = r.request_id.split("-", 1)[1]
            assert list(r.output_ids) == base_streams[key], (
                f"{r.request_id}: churned stream diverged\n"
                f"  churn: {list(r.output_ids)}\n"
                f"  base : {base_streams[key]}"
            )

        # At least the victim pipeline's in-flight requests migrated.
        assert _migrations_total() > counters_before
        moved = [
            rid for rid, head in _all_migrations(workers)
            if head not in victim["pipeline"]
        ]
        assert moved, "no request recorded a migration target"

        # Radix digests: the migrated streams' block chains are present
        # in a SURVIVING head's radix exactly as an unchurned serve
        # would have donated them.
        _assert_digests_present(workers, dead_tail, churn)

        # Concurrency hygiene of the episode itself. Dynamic: the lock
        # graph built while heartbeat/sender/step/migration threads ran
        # the kill-migration must be acyclic (a cycle = a latent
        # deadlock even if this run never hit it) — and the sanitizer
        # must actually have been watching. Static: the modules those
        # threads share must carry zero unsuppressed cross-thread
        # unguarded-mutation (lock-discipline) findings.
        rep = chaos.lock_report()
        assert rep["acquisitions"] > 0, (
            "lock sanitizer saw no acquisitions — instrumentation "
            "never engaged"
        )
        assert rep["cycles"] == [], (
            "lock-order cycles during kill-migration (potential "
            f"deadlock): {rep['cycles']}\nedges: {sorted(rep['edges'])}"
        )
        _assert_no_unguarded_mutations()
    finally:
        for w in workers:
            if not chaos.is_dead(w.node_id):
                w.stop()
        service.stop()


def _migrations_total() -> int:
    from parallax_tpu.obs.registry import get_registry

    return int(get_registry().counter(
        "parallax_migrations_total",
        "Requests restored on this head after a live migration "
        "or client resume",
        labelnames=("mode",),
    ).total)


def _assert_no_unguarded_mutations():
    """Zero cross-thread unguarded mutations, the static half: the
    lock-discipline checker over every module the migration's threads
    (step loop, heartbeat, sender, watchdog, migration worker) share."""
    import parallax_tpu
    from parallax_tpu.analysis.linter import LintEngine

    pkg = os.path.dirname(parallax_tpu.__file__)
    # Full checker set: a lock-discipline-only engine would misreport
    # these files' jit-purity/hot-path-sync suppressions as unused.
    engine = LintEngine()
    result = engine.run_paths([
        os.path.join(pkg, "runtime", "engine.py"),
        os.path.join(pkg, "p2p", "node.py"),
        os.path.join(pkg, "p2p", "transport.py"),
        os.path.join(pkg, "scheduling", "scheduler.py"),
        os.path.join(pkg, "testing", "chaos.py"),
        os.path.join(pkg, "obs"),
    ])
    unguarded = [f for f in result.findings
                 if f.checker == "lock-discipline"]
    assert unguarded == [], "\n".join(f.render() for f in unguarded)
    assert result.ok, "\n".join(f.render() for f in result.findings)


def _all_migrations(workers):
    out = []
    for w in workers:
        out.extend(w._migrated_to.items())
    return out


def _assert_digests_present(workers, dead_tail, churn_reqs):
    from parallax_tpu.runtime.radix_cache import block_hash_chain

    digest_sets = []
    for w in workers:
        eng = w.engine
        tree = getattr(getattr(eng, "cache", None), "prefix_cache", None)
        if tree is None or w.node_id == dead_tail:
            continue
        digest_sets.append((w.node_id, set(tree.prefix_digests())))
    assert digest_sets
    for r in churn_reqs:
        toks = list(r.prompt_ids) + list(r.output_ids)
        # Only fully computed pages get donated; the final sampled token
        # has no KV — stay one token short of the boundary.
        chain = block_hash_chain(toks[:-1], 8)
        if not chain:
            continue
        assert any(
            chain[0] in dig for _nid, dig in digest_sets
        ), f"{r.request_id}: no surviving radix holds its first block"


# -- grammar-DFA checkpoint portability (PR 18) ----------------------------


_G_SCHEMA = (
    '{"type": "object", "properties": {"v": {"enum": ["x", "y"]}}, '
    '"required": ["v"]}'
)
_G_VOCAB = [bytes([i]) for i in range(256)] + [b"", b""]
_G_EOS = 257


def _grammar_ckpt(dfa_state=3):
    from parallax_tpu.constrained import grammar_state_hash

    ck = _mk_ckpt(with_kv=False)
    ck.sampling_params = SamplingParams(
        temperature=0.0, max_new_tokens=32, json_schema=_G_SCHEMA,
    ).to_dict()
    ck.dfa_state = dfa_state
    ck.grammar_hash = grammar_state_hash(_G_SCHEMA)
    return ck


class TestGrammarCheckpoint:
    def test_wire_roundtrip(self):
        import msgpack

        ck = _grammar_ckpt()
        wire = msgpack.unpackb(
            msgpack.packb(checkpoint_to_wire(ck), use_bin_type=True),
            raw=False,
        )
        back = checkpoint_from_wire(wire)
        assert back.dfa_state == ck.dfa_state
        assert back.grammar_hash == ck.grammar_hash
        # Unconstrained frames carry no grammar fields at all.
        plain = checkpoint_to_wire(_mk_ckpt(with_kv=False))
        assert "dfa_state" not in plain and "grammar_hash" not in plain
        assert checkpoint_from_wire(plain).dfa_state is None

    @pytest.mark.parametrize("mutate,desc", [
        (lambda d: d.update(dfa_state="x"), "non-int state"),
        (lambda d: d.update(dfa_state=1 << 40), "state out of range"),
        (lambda d: d.update(grammar_hash=""), "state without hash"),
        (lambda d: d.update(grammar_hash="h" * 99), "oversized hash"),
        (lambda d: d.update(
            sampling_params=SamplingParams(max_new_tokens=8).to_dict()
        ), "dfa_state without json_schema"),
    ])
    def test_corrupt_grammar_frames_rejected(self, mutate, desc):
        d = checkpoint_to_wire(_grammar_ckpt())
        mutate(d)
        with pytest.raises(CheckpointError):
            checkpoint_from_wire(d)
        checkpoint_from_wire(checkpoint_to_wire(_grammar_ckpt()))

    def test_replay_does_not_preseed_state(self):
        """Replay mode re-commits the stream from scratch — the DFA
        mirror must advance through the teacher-forced commits from 0,
        not start at the checkpointed (post-stream) state."""
        adopt = build_resumed_request(_grammar_ckpt())
        assert getattr(adopt, "grammar_dfa_state", None) == 3
        rep = build_resumed_request(_grammar_ckpt(), replay=True)
        assert getattr(rep, "grammar_dfa_state", None) is None

    def test_initial_state_validates_hash(self, tiny_model_and_params):
        """The adopting engine trusts the checkpointed state only when
        its own compile of the schema hashes identically; a stale hash
        or out-of-range state recomputes from the committed stream."""
        eng = _mk_engine(tiny_model_and_params)
        eng.set_grammar_vocab(_G_VOCAB, _G_EOS)
        table = eng.grammar.compile(_G_SCHEMA)
        from parallax_tpu.constrained import grammar_state_hash

        def mk_req(**attrs):
            r = Request("gr", prompt_ids=[1, 2],
                        sampling_params=SamplingParams(
                            max_new_tokens=8, json_schema=_G_SCHEMA))
            for k, v in attrs.items():
                setattr(r, k, v)
            return r

        good = mk_req(grammar_dfa_state=2,
                      grammar_hash=grammar_state_hash(_G_SCHEMA))
        assert eng._grammar_initial_state(good, table) == 2
        stale = mk_req(grammar_dfa_state=2, grammar_hash="deadbeef")
        assert eng._grammar_initial_state(stale, table) == 0
        oob = mk_req(grammar_dfa_state=table.dfa.n_states + 7,
                     grammar_hash=grammar_state_hash(_G_SCHEMA))
        assert eng._grammar_initial_state(oob, table) == 0

    def test_constrained_migration_bit_identical(
        self, tiny_model_and_params
    ):
        """The PR 17 fail-fast is gone: a constrained request parked
        mid-decode replays on a fresh engine and finishes bit-identically
        to an unchurned run, with the grammar enforced throughout."""
        import json as _json

        sp = SamplingParams(temperature=0.0, max_new_tokens=36,
                            json_schema=_G_SCHEMA)

        eng0 = _mk_engine(tiny_model_and_params, decode_lookahead=8)
        eng0.set_grammar_vocab(_G_VOCAB, _G_EOS)
        base = Request("base", prompt_ids=[1, 2, 3],
                       sampling_params=dataclasses.replace(sp))
        eng0.submit(base)
        _drive(eng0)
        assert base.status.is_finished
        _json.loads(bytes(t for t in base.output_ids if t < 256))

        eng_a = _mk_engine(tiny_model_and_params, decode_lookahead=8)
        eng_a.set_grammar_vocab(_G_VOCAB, _G_EOS)
        mig = Request("mig", prompt_ids=[1, 2, 3],
                      sampling_params=dataclasses.replace(sp))
        eng_a.submit(mig)
        _drive_tokens(eng_a, mig, 4)
        assert not mig.status.is_finished
        grammar = eng_a.grammar_checkpoint_fields("mig")
        assert grammar is not None and grammar[0] >= 0
        eng_a.extract("mig")
        ckpt = checkpoint_from_request(mig, routing_table=["B"],
                                       grammar=grammar)
        eng_a.cache.release(mig)
        wire = checkpoint_from_wire(checkpoint_to_wire(ckpt))
        assert wire.dfa_state == grammar[0]

        eng_b = _mk_engine(tiny_model_and_params, decode_lookahead=8)
        eng_b.set_grammar_vocab(_G_VOCAB, _G_EOS)
        res = build_resumed_request(wire, replay=True)
        assert eng_b.submit(res)
        _drive(eng_b)
        assert res.status.is_finished
        assert res.full_output_ids == base.output_ids
        _json.loads(bytes(t for t in res.full_output_ids if t < 256))
