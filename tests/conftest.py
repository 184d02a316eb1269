"""Test configuration: force an 8-device virtual CPU mesh.

Multi-chip hardware is unavailable in CI; all sharding tests (tp/pp/dp/sp)
run over ``--xla_force_host_platform_device_count=8`` CPU devices, mirroring
how the driver dry-runs the multi-chip path.
"""

import os

# Hard override: tests always run on the virtual CPU mesh, whatever
# JAX_PLATFORMS the environment carries — a test session on a machine
# with a chip must not claim it (a chip belongs to one process). Opt out
# with PARALLAX_TPU_TESTS=1 to run the kernel tests compiled on real
# hardware (one such session at a time).
_ON_TPU = os.environ.get("PARALLAX_TPU_TESTS", "") not in ("", "0")
if not _ON_TPU:
    os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8"
    ).strip()

import jax  # noqa: E402  (import after env setup)
import pytest  # noqa: E402

from parallax_tpu.analysis import conformance  # noqa: E402
from parallax_tpu.analysis import sanitizer  # noqa: E402


def pytest_addoption(parser):
    parser.addoption(
        "--lock-sanitizer", action="store_true", default=False,
        help="enable the lock-order sanitizer for the whole session: "
             "every parallax_tpu make_lock() lock created after startup "
             "is instrumented and lock-graph cycles are reported at the "
             "end of the run (docs/static_analysis.md). Equivalent to "
             "PARALLAX_LOCK_SANITIZER=1.",
    )
    parser.addoption(
        "--conformance-sanitizer", action="store_true", default=False,
        help="enable the protocol-conformance sanitizer for the whole "
             "session: every Request status transition, head-ownership "
             "claim, router load charge and wire frame is checked "
             "against the declared FSM/schema model in "
             "analysis/protocol.py, and the swarm e2e tests "
             "(chaos/migration/handoff/QoS) assert a clean report per "
             "test (docs/static_analysis.md). Equivalent to "
             "PARALLAX_CONFORMANCE_SANITIZER=1.",
    )


def pytest_configure(config):
    # Enable BEFORE any test module constructs engines/nodes so their
    # locks are created instrumented (enable() only affects locks made
    # after it). The chaos harness also enables it per-controller.
    if config.getoption("--lock-sanitizer"):
        sanitizer.enable()
    if config.getoption("--conformance-sanitizer"):
        conformance.enable()


@pytest.fixture(autouse=True)
def _scoped_lock_sanitizer(request):
    """Contain ChaosController's process-global sanitizer enable: when
    the session did not opt in with --lock-sanitizer, switch it back
    off after each test so unrelated tests keep creating plain
    (uninstrumented) locks."""
    yield
    if not request.config.getoption("--lock-sanitizer"):
        sanitizer.disable()


# Swarm e2e modules whose tests must leave a clean conformance report
# when the session opted in with --conformance-sanitizer (the CI
# chaos/migration/handoff/QoS smoke steps run exactly these).
CONFORMANCE_E2E_MODULES = {
    "test_churn_migration", "test_disaggregation", "test_ha_failover",
    "test_qos", "test_swarm_e2e", "test_swarm_scale",
}


@pytest.fixture(autouse=True)
def _scoped_conformance_sanitizer(request):
    """Per-test conformance verdict + containment. With the flag on,
    each e2e swarm test starts from a clean slate and must end with
    zero violations; without it, ChaosController's process-global
    enable is switched back off after each test (mirroring the lock
    sanitizer's containment)."""
    opted = request.config.getoption("--conformance-sanitizer")
    mod = request.module.__name__.rsplit(".", 1)[-1]
    guard = opted and mod in CONFORMANCE_E2E_MODULES
    if guard:
        conformance.reset()
    yield
    if guard:
        rep = conformance.report()
        assert not rep["violations"], (
            f"protocol conformance violations in {mod}: "
            f"{rep['violations']}"
        )
    if not opted:
        conformance.disable()


def pytest_terminal_summary(terminalreporter, exitstatus, config):
    _conformance_summary(terminalreporter, config)
    san = sanitizer.get_sanitizer()
    rep = san.report()
    # Print when the user opted in — or unconditionally when a cycle
    # (potential deadlock) was observed: that must never scroll away.
    if san.acquisitions == 0 or not (
        config.getoption("--lock-sanitizer") or rep["cycles"]
    ):
        return
    terminalreporter.section("lock-order sanitizer")
    terminalreporter.write_line(
        f"{rep['acquisitions']} acquisitions over "
        f"{len(rep['locks'])} lock name(s), "
        f"{len(rep['edges'])} order edge(s), "
        f"{len(rep['cycles'])} cycle(s), "
        f"{len(rep['long_holds'])} held-too-long report(s)"
    )
    for cyc in rep["cycles"]:
        terminalreporter.write_line(
            "POTENTIAL DEADLOCK: " + " -> ".join(cyc), red=True)


def _conformance_summary(terminalreporter, config):
    rep = conformance.report()
    total = sum(rep["transitions"].values())
    # Violations print unconditionally — they must never scroll away,
    # even from a run that recorded no status transitions (frame-only
    # or ownership-only violations). Otherwise print only when the
    # user opted in and there was activity to summarize.
    if not rep["violations"] and not (
        config.getoption("--conformance-sanitizer") and total
    ):
        return
    terminalreporter.section("protocol-conformance sanitizer")
    terminalreporter.write_line(
        f"{total} status transitions over "
        f"{len(rep['transitions'])} FSM edge owner(s), "
        f"{rep['commits']} commits, "
        f"{rep['ownership_events']} ownership claims, "
        f"{sum(rep['frames'].values())} frames, "
        f"{len(rep['violations'])} violation(s)"
    )
    for v in rep["violations"]:
        terminalreporter.write_line(
            f"PROTOCOL VIOLATION: {v}", red=True)

# Jit-heavy / e2e suites (each >1 min on CPU). The fast core —
# scheduling, cache bookkeeping, transport, interop, constrained,
# periphery — gives signal in well under a minute with
# ``pytest -m "not slow"``; CI and the driver run everything.
SLOW_MODULES = {
    "test_deepseek_mla", "test_dsa", "test_engine_e2e",
    "test_glm4_gptoss", "test_ha_failover", "test_http_serving",
    "test_linear_prefix_cache",
    "test_lora_serving", "test_mla_pallas", "test_moe", "test_msa",
    "test_multistep_decode", "test_ops_attention", "test_pp_speculative",
    "test_quantization", "test_qwen3_next", "test_ring_attention",
    "test_speculative", "test_swarm_e2e", "test_tensor_parallel",
    "test_weight_refit", "test_zoo_tails",
}


def pytest_collection_modifyitems(config, items):
    for item in items:
        mod = item.module.__name__.rsplit(".", 1)[-1]
        if mod in SLOW_MODULES:
            item.add_marker(pytest.mark.slow)

# The config value too, so nothing that edits the environment after
# import can move this session off the CPU.
if not _ON_TPU:
    jax.config.update("jax_platforms", "cpu")
else:
    # Exact-match oracles assume true f32 math; the TPU default lowers
    # f32 matmuls to bf16 passes (~3e-3 relative error), which is fine in
    # production (weights are bf16 anyway) but not for kernel tests.
    jax.config.update("jax_default_matmul_precision", "highest")
jax.config.update("jax_enable_x64", False)
