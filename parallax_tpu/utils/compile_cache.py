"""Compile-time hygiene: persistent XLA compilation cache + the compile
observatory hookup.

Production restarts and autoscale events re-trace every program in the
engine's shape lattice; without a persistent cache each new process pays
the full recompilation storm before serving its first token. The serving
entrypoints (``serve``/``join``/``generate``/bench) therefore enable
JAX's persistent compilation cache by default — executables land under a
directory placed from outside (``JAX_COMPILATION_CACHE_DIR``) or, failing
that, at ``<checkout>/.jax_cache``, and later processes load them from disk.

Compile OBSERVABILITY lives in :class:`parallax_tpu.obs.device
.CompileObservatory`: this module's JAX monitoring listener feeds every
``backend_compile`` event into it, where the compile is attributed to a
program family and recompile *cause* (the jit sites declare their keys
via ``note_program``), exported as ``parallax_xla_compiles_total
{program,cause}`` plus cumulative compile ms, live executables, and the
recompile-storm detector. A healthy steady-state process compiles during
warmup and then stops; per-family cause labels say WHICH program leaked
a shape when the counter keeps climbing. Compile seconds still land in
the goodput ledger's ``compile`` bucket (a storm shows as a goodput dip
instead of hiding inside step latency); the observatory splits them by
family.
"""

from __future__ import annotations

import os
import threading

from parallax_tpu.utils import get_logger
from parallax_tpu.analysis.sanitizer import make_lock

logger = get_logger(__name__)

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
# Where the cache lives when the environment does not place it: one
# fixed path under the checkout. The directory is part of the cache
# key, so a path built from $HOME, a temp name, a pid or a time would
# never hit again.
_DEFAULT_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)
    ))),
    ".jax_cache",
)
_OFF = ("off", "0", "none", "disabled")
# JAX duration events fired once per backend compilation (only the
# backend compile is the expensive storm signal).
_COMPILE_EVENT = "backend_compile"
# Tracing a function to a jaxpr and lowering it to MLIR fire their own:
# a retrace that ends in no compile costs seconds of Python all the same
# (nested jit functions report at every level, so the sum can count an
# inner trace twice).
_TRACE_EVENTS = (
    "/jax/core/compile/jaxpr_trace_duration",
    "/jax/core/compile/jaxpr_to_mlir_module_duration",
)
# JAX wraps the whole compile-or-load-from-cache call in that duration
# event, so it fires for persistent-cache hits too; a hit announces
# itself first, on the same thread, with this plain event.
_CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"

_lock = make_lock("utils.compile_cache")
_active_path: str | None = None
_counter_registered = False


def enable_compilation_cache(path: str | None = None) -> str | None:
    """Enable the persistent XLA compilation cache; returns the active
    directory, or None when ``path`` says ``"off"`` (or ``"0"`` /
    ``"none"`` / an empty string).

    Placement is decided from outside first: where
    ``JAX_COMPILATION_CACHE_DIR`` is set, JAX has already read it and
    this function sets no directory in code — ``path`` (the
    ``--compilation-cache-dir`` flag) loses to it. Where it is unset,
    the directory is ``path`` if given, else ``<checkout>/.jax_cache``.
    """
    global _active_path
    import jax

    if path is not None and (not path or str(path).lower() in _OFF):
        return None
    env_path = os.environ.get(ENV_VAR)
    if env_path:
        path = env_path
    else:
        path = os.path.abspath(os.path.expanduser(str(path or _DEFAULT_DIR)))
        os.makedirs(path, exist_ok=True)
        jax.config.update("jax_compilation_cache_dir", path)
    # Cache small entries too: the engine's lattice is many small
    # programs, and the storm being avoided is exactly their sum.
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    with _lock:
        _active_path = path
    register_compile_counter()
    logger.info("persistent XLA compilation cache at %s", path)
    return path


def active_cache_dir() -> str | None:
    """The enabled cache directory, or None."""
    return _active_path


def register_compile_counter() -> None:
    """Wire JAX's per-backend-compilation monitoring events into the
    compile observatory (idempotent). Persistent-cache
    HITS are counted apart (``cache_hits_total``) — the compile series
    measures real compile work only. Each event is attributed to the program family /
    cause most recently declared via ``note_program`` and its duration
    lands in the goodput ledger's ``compile`` bucket. Compile seconds
    (loads with them) and trace-and-lowering seconds also go to the
    calling thread's clocks in obs/trace.py, by which a slow visit's
    excess is split, and the latter to ``parallax_jit_trace_ms_total``."""
    global _counter_registered
    with _lock:
        if _counter_registered:
            return
        _counter_registered = True
    from jax import monitoring

    from parallax_tpu.obs.device import get_device_plane
    from parallax_tpu.obs.goodput import get_goodput
    from parallax_tpu.obs.trace import get_slow_visits, note_jit_seconds

    get_slow_visits().bind_registry()
    plane = get_device_plane()
    plane.bind_registry()
    goodput = get_goodput()

    hit = threading.local()

    def _on_event(event: str, **kw) -> None:
        if event == _CACHE_HIT_EVENT:
            hit.pending = True

    def _on_duration(event: str, duration: float, **kw) -> None:
        if event in _TRACE_EVENTS:
            note_jit_seconds("trace", duration)
        elif _COMPILE_EVENT in event:
            note_jit_seconds("compile", duration)
            fun = str(kw.get("fun_name", ""))
            if getattr(hit, "pending", False):
                hit.pending = False
                plane.compile.on_cache_hit(duration, fun)
                return
            plane.compile.on_compile(duration, fun)
            # Goodput time split: compile seconds are not serve
            # seconds — a recompile storm shows up as a goodput dip
            # instead of hiding inside step latency.
            goodput.add_time("compile", duration)

    monitoring.register_event_listener(_on_event)
    monitoring.register_event_duration_secs_listener(_on_duration)
