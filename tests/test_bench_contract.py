"""Entry-contract smoke tests: ``bench.py`` runs its measurement once in
a child process, prints the child's one JSON line with the required keys
and exits with the child's code (``BENCH_CPU=1`` is the CPU fixture and
says so in ``detail.platform``); ``__graft_entry__.entry()`` must be
jit-lowerable."""

import json
import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_bench_cpu_smoke_prints_one_json_line():
    env = dict(os.environ, BENCH_CPU="1")
    out = subprocess.run(
        [sys.executable, os.path.join(REPO, "bench.py")],
        env=env, capture_output=True, text=True, timeout=300, cwd=REPO,
    )
    assert out.returncode == 0, out.stderr[-1500:]
    json_lines = [l for l in out.stdout.splitlines() if l.startswith("{")]
    assert json_lines, out.stdout
    rec = json.loads(json_lines[-1])
    for key in ("metric", "value", "unit", "vs_baseline"):
        assert key in rec, rec
    assert rec["value"] > 0
    # One line, from the one child; the fixture labels itself as a CPU
    # run (its number is a smoke value, never a device metric).
    assert len(json_lines) == 1, json_lines
    assert rec["detail"]["platform"] == "cpu", rec["detail"]["platform"]
    # Two-phase decode-loop telemetry is part of the bench contract.
    for key in ("host_ms_median", "readback_wait_ms_median",
                "overlapped_steps", "sync_decode_dispatch_ms_median"):
        assert key in rec["detail"], rec["detail"]
    # Cache observability + the host-KV-tier pressure probe: the tier-on
    # run must finish everything without kv_oom while the tier-off run
    # aborts — the new-subsystem acceptance contract.
    assert "cache_stats" in rec["detail"], rec["detail"]
    hc = rec["detail"]["host_cache"]
    for run in ("enabled", "disabled"):
        for key in ("prefix_hit_rate", "tokens_hit_host", "kv_oom_aborts",
                    "preemptions", "completed", "requests"):
            assert key in hc[run], hc
    assert hc["enabled"]["kv_oom_aborts"] == 0, hc
    assert hc["enabled"]["completed"] == hc["enabled"]["requests"], hc
    assert hc["disabled"]["kv_oom_aborts"] > 0, hc
    assert (hc["enabled"]["prefix_hit_rate"]
            > hc["disabled"]["prefix_hit_rate"]), hc
    # Decode-kernel microbench (detail.kernel): structural contract +
    # the deterministic bit-identity verdicts; the main metric line
    # names the impl that produced it. The fused-below-split TIMING
    # comparison is asserted only in the CI fused-decode smoke step
    # (every other assertion here is deterministic — a wall-clock
    # comparison in the unit suite would flake on loaded machines).
    assert rec["detail"]["attn_impl"] in (
        "pallas-fused", "pallas-split", "xla"
    ), rec["detail"]["attn_impl"]
    kp = rec["detail"]["kernel"]
    for name in ("pallas-fused", "pallas-split", "xla"):
        assert kp["impls"][name]["per_token_device_ms"] > 0, kp
    assert kp["tokens_fused_vs_xla_identical"], kp
    assert kp["greedy_rows_identical_all_impls"], kp
    # Multi-tenant QoS probe (detail.qos, docs/qos.md): structural keys
    # plus the deterministic acceptance contract — QoS on sheds AND
    # parks the batch flood (enforcement, never abort: everything
    # completes), holds interactive p99 TTFT within the 2x-of-unloaded
    # budget, and streams are bit-identical to the QoS-off run. The
    # off-vs-on TTFT improvement (wall-clock) is asserted in the CI qos
    # smoke step, not here.
    # Speculative-decoding probe (detail.spec, docs/decode_loop.md):
    # structural keys + the deterministic bit-identity verdicts for
    # every cell of the on/off x K=1/K=8 x repetitive/random matrix.
    # The wall-clock speedup comparison (spec-on strictly below
    # spec-off at K=8 on the repetitive workload) is asserted in the
    # CI spec smoke step, not here.
    sp = rec["detail"]["spec"]
    assert sp["speculative_tokens"] > 0, sp
    for wl in ("repetitive", "random"):
        for run in ("off_k8", "on_k8", "off_k1", "on_k1"):
            cell = sp[wl][run]
            assert cell["per_token_ms"] > 0, (wl, run, cell)
            assert cell["decode_tokens"] > 0, (wl, run, cell)
            assert "goodput" in cell, (wl, run, cell)
        assert sp[wl]["bit_identical"] is True, sp[wl]
        for run in ("on_k8", "on_k1"):
            assert 0.0 <= sp[wl][run]["acceptance_rate"] <= 1.0, sp[wl]
    assert sp["repetitive"]["seeded_bit_identical"] is True, sp
    on_rep = sp["repetitive"]["on_k8"]
    assert on_rep["proposals"] > 0, on_rep
    assert on_rep["accepted"] > 0, on_rep
    # Rejected verify positions land in the goodput ledger's
    # speculative_rejected bucket — the honest waste accounting.
    assert on_rep["goodput"]["speculative_rejected"] > 0, on_rep
    assert on_rep["goodput"]["committed"] > 0, on_rep
    # Constrained-decoding probe (detail.constrained,
    # docs/decode_loop.md): structural keys + the deterministic
    # verdicts — schema-constrained K=8 streams bit-identical to the
    # K=1 host-sync sampler, every output valid under the schema, and
    # zero host-sync fallbacks (the mask ran in-window). The >=80%
    # tokens/s ratio is asserted in the CI constrained smoke step, not
    # here (wall-clock).
    cp = rec["detail"]["constrained"]
    assert cp["k"] > 1, cp
    for side in ("unconstrained", "constrained"):
        assert cp[side]["per_token_ms"] > 0, (side, cp)
        assert cp[side]["decode_tokens"] > 0, (side, cp)
    assert cp["throughput_ratio"] > 0, cp
    assert cp["bit_identical"] is True, cp
    assert cp["all_valid_json"] is True, cp
    assert cp["zero_fallbacks"] is True, cp
    assert cp["summary"]["window_rows"] > 0, cp
    assert cp["summary"]["mask_steps"] > 0, cp
    assert cp["summary"]["table_builds"] >= 1, cp
    # Prefill-roofline probe (detail.prefill, docs/kernels.md):
    # structural keys + the deterministic verdicts — cache bit-equality
    # and attention closeness fused-vs-XLA, warm-prefix chunk skipping
    # recomputing ZERO covered chunks with bit-identical streams, and
    # the interactive workload completing under the long chunked
    # prefill. The fused-below-XLA TIMING comparison is asserted in the
    # CI fused-prefill smoke step only (the warm-prefix wall ratio is
    # informational — one-off JIT compile dominates it on CPU).
    pp = rec["detail"]["prefill"]
    for name in ("pallas-fused", "xla"):
        assert pp["kernel"]["impls"][name]["per_token_device_ms"] > 0, pp
    assert pp["kernel"]["cache_fused_vs_xla_identical"], pp
    assert pp["kernel"]["attn_out_close_fused_vs_xla"], pp
    wp = pp["warm_prefix"]
    assert wp["tokens_chunk_skipped_on"] == wp["covered_tokens"], wp
    assert wp["tokens_chunk_skipped_off"] == 0, wp
    assert wp["covered_tokens_recomputed_on"] == 0, wp
    assert wp["streams_bit_identical"] is True, wp
    ip = pp["interactive_under_long_prefill"]
    assert ip["completed"] == ip["requests"], ip
    assert ip["ttft_p95_ms"] > 0, ip
    assert ip["long_ttft_ms"] > 0, ip
    q = rec["detail"]["qos"]
    for run in ("unloaded", "off", "on"):
        for key in ("requests", "completed", "aborted", "interactive",
                    "batch"):
            assert key in q[run], (run, q[run])
        assert q[run]["aborted"] == 0, q
        assert q[run]["completed"] == q[run]["requests"], q
    assert q["bit_identical"] is True, q
    assert q["interactive_p99_within_2x"] is True, q
    assert q["on"]["sheds"] > 0, q
    assert q["on"]["parks"] > 0, q
    assert q["on"]["shed_transitions"]["sheds"] >= 1, q
    assert q["on"]["shed_transitions"]["releases"] >= 1, q
    assert q["on"]["batch"]["tokens"] > 0, q           # never starved
    assert q["on"]["batch"]["tokens"] == q["off"]["batch"]["tokens"], q
    # Device attribution plane (detail.device, obs/device.py): the HBM
    # ledger invariant must hold, the compile observatory must explain
    # every compile (zero cause="unknown" — that would mean a jit site
    # the engine never declared), and the decode run must attribute
    # device time to at least one program family.
    dev = rec["detail"]["device"]
    hbm = dev["hbm"]
    for key in ("classes", "tracked_bytes", "untracked_bytes",
                "capacity_bytes", "headroom_bytes",
                "high_watermark_bytes", "invariant_ok"):
        assert key in hbm, hbm
    assert hbm["invariant_ok"] is True, hbm
    assert hbm["classes"].get("kv_pages", 0) > 0, hbm
    assert any(c.startswith("weights") for c in hbm["classes"]), hbm
    comp = dev["compile"]
    for key in ("programs", "compiles_total", "unexplained_compiles",
                "compile_ms_total", "storms_total"):
        assert key in comp, comp
    progs = dev["programs"]
    assert progs["seconds_total"] > 0, progs
    assert progs["seconds"], progs
    for fam, share in progs["share"].items():
        assert 0.0 <= share <= 1.0, (fam, progs)


def test_bench_dsa_mode_cpu_smoke():
    env = dict(os.environ, BENCH_CPU="1", BENCH_MODEL="dsa")
    out = subprocess.run(
        [sys.executable, os.path.join(REPO, "bench.py")],
        env=env, capture_output=True, text=True, timeout=300, cwd=REPO,
    )
    assert out.returncode == 0, out.stderr[-1500:]
    json_lines = [l for l in out.stdout.splitlines() if l.startswith("{")]
    assert json_lines, out.stdout
    rec = json.loads(json_lines[-1])
    assert rec["value"] > 0
    assert rec["detail"]["bench_model"] == "dsa"
    assert "ttft_p50_ms" in rec["detail"]


def test_graft_entry_lowers():
    import jax

    sys.path.insert(0, REPO)
    import __graft_entry__ as g

    fn, args = g.entry()
    jax.jit(fn, donate_argnums=(1,)).lower(*args)
