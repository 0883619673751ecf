"""Where the persistent XLA compilation cache lives.

One rule for every entry point (``initialize_runtime``, ``run_hpo``,
the sweep service, ``chip_smoke.py``): the cache is JAX's own
persistent compilation cache, placed from outside by
``JAX_COMPILATION_CACHE_DIR`` and otherwise kept at one fixed path,
``.jax_cache`` at the checkout root. The directory is part of the
cache key, so a directory that moves — a temp dir, a pid, a timestamp —
never hits; nothing on the training path may hold the cache in one.

The module that turns the cache on also records its traffic: the
**compile log** (:class:`CompileLog`), one listener on
``jax.monitoring`` a process, installed by :func:`enable_compile_cache`
and read through :func:`compile_log`. It says, by program, where an
admission's seconds went: tracing, lowering, and the backend's part
(the cache key, the read and the deserialisation on a warm run, the
compile on a cold one). ``utils/profiling.span`` writes the host's own
spans (``admit:...``) into the same log on the same clock.
"""

from __future__ import annotations

import collections
import os
import threading
import time
from typing import NamedTuple, Optional

# The stages of an entry. The first three are jax's compile events, one
# of each a program on its first call; ``retrieval`` is the cache read
# inside a ``backend`` entry that hit; ``span`` is a host span of the
# program's own (``utils/profiling.span``), its name under ``program``.
STAGE_TRACE = "trace"
STAGE_LOWER = "lower"
STAGE_BACKEND = "backend"
STAGE_RETRIEVAL = "retrieval"
STAGE_SPAN = "span"
_STAGE_OF_EVENT = {
    "/jax/core/compile/jaxpr_trace_duration": STAGE_TRACE,
    "/jax/core/compile/jaxpr_to_mlir_module_duration": STAGE_LOWER,
    "/jax/core/compile/backend_compile_duration": STAGE_BACKEND,
}
_COMPILE_STAGES = (STAGE_TRACE, STAGE_LOWER, STAGE_BACKEND, STAGE_RETRIEVAL)
_RETRIEVAL_EVENT = "/jax/compilation_cache/cache_retrieval_time_sec"
_HIT_EVENT = "/jax/compilation_cache/cache_hits"
_MISS_EVENT = "/jax/compilation_cache/cache_misses"
# Entries kept; the aggregates count on past it. One unrolled step's
# trace leaves its inner traces in the deque until the step's own trace
# ends and folds them: the deque's longest was 10,648 in a set-up of the
# benchmark's largest trace (``moe-mhc-t4096``, PR 35).
LOG_MAXLEN = 32768


class Entry(NamedTuple):
    """One line of the compile log, on ``time.perf_counter()``'s clock."""

    stage: str
    program: str  # ``fun_name`` without ``jit(...)``; a span's name
    end: float
    secs: float
    nested: int = 0  # entries of its stage that lay inside it, folded
    thread: int = 0

    @property
    def start(self) -> float:
        return self.end - self.secs


class Sum(NamedTuple):
    n: int
    secs: float


def _program(fun_name: str) -> str:
    """``step_fn`` and ``jit(step_fn)`` are one program: the trace event
    carries the function's name, lowering and the backend the module's."""
    if fun_name.startswith("jit(") and fun_name.endswith(")"):
        return fun_name[4:-1]
    return fun_name


class CompileLog:
    """What this process traced, lowered, compiled or loaded, by
    program, and the host spans around it.

    **Outermost entries only.** jax reports every traced function, the
    inner ``jit``s and kernels inside a step's trace too, each with its
    own seconds: their plain sum is more than the wall time. An inner
    event ends, and so arrives, before the one that holds it; when an
    entry arrives, the entries of its stage and thread already logged
    inside its interval are folded into it (their count kept in
    ``nested``, their seconds taken back out of the aggregates). Spans
    nest on purpose and are never folded.

    **Bounded.** Entries live in a deque of ``maxlen``; the aggregates
    by ``(stage, program)`` and the cache's hits and misses count for
    the process's life. (An inner entry that a full deque pushed out
    before its outer one arrived is past folding: its seconds stay in
    the aggregates.)
    """

    def __init__(self, maxlen: int = LOG_MAXLEN):
        self._entries: collections.deque[Entry] = collections.deque(
            maxlen=maxlen
        )
        self._totals: dict[tuple[str, str], Sum] = {}
        self._lock = threading.Lock()
        # A cache read, by thread, until its backend entry names it.
        self._retrieved: dict[int, tuple[float, float]] = {}
        self._sinks: list = []
        self.hits = 0
        self.misses = 0

    # -- written by jax.monitoring and by utils/profiling.span ---------

    def on_time_span(
        self,
        event: str,
        start_time: float,
        end_time: float,
        *,
        fun_name: str = "",
        **_,
    ) -> None:
        stage = _STAGE_OF_EVENT.get(event)
        if stage is None:
            return
        # jax stamps with time.time(); the log keeps perf_counter's
        # clock (the benchmark's stamps, profiling.span), less what the
        # callback came late by.
        end = time.perf_counter() - max(0.0, time.time() - end_time)
        self._add(
            Entry(
                stage,
                _program(fun_name),
                end,
                end_time - start_time,
                thread=threading.get_ident(),
            )
        )

    def on_duration(self, event: str, secs: float, **_) -> None:
        if event == _RETRIEVAL_EVENT:
            # Fired inside the backend's span, which names the program
            # only when it ends.
            with self._lock:
                self._retrieved[threading.get_ident()] = (
                    time.perf_counter(),
                    secs,
                )

    def on_event(self, event: str, **_) -> None:
        if event == _HIT_EVENT:
            with self._lock:
                self.hits += 1
        elif event == _MISS_EVENT:
            with self._lock:
                self.misses += 1

    def add_span(self, name: str, start: float, end: float) -> None:
        self._add(
            Entry(
                STAGE_SPAN, name, end, end - start,
                thread=threading.get_ident(),
            )
        )

    def subscribe(self, sink) -> None:
        """``sink(entry)`` for every entry as it is logged;
        ``telemetry/metrics`` counts the ``backend`` entries this way."""
        self._sinks.append(sink)

    def _add(self, entry: Entry) -> None:
        added = [entry]
        with self._lock:
            if entry.stage == STAGE_BACKEND:
                read = self._retrieved.pop(entry.thread, None)
                if read is not None and read[0] >= entry.start:
                    added.insert(
                        0,
                        Entry(
                            STAGE_RETRIEVAL, entry.program, *read,
                            thread=entry.thread,
                        ),
                    )
            if entry.stage != STAGE_SPAN:
                added[-1] = self._fold_into(entry)
            for e in added:
                self._entries.append(e)
                n, secs = self._totals.get((e.stage, e.program), (0, 0.0))
                self._totals[e.stage, e.program] = Sum(n + 1, secs + e.secs)
        for sink in self._sinks:
            for e in added:
                sink(e)

    def _fold_into(self, outer: Entry) -> Entry:
        """Take the entries of ``outer``'s stage and thread that lie
        inside its interval out of the deque and of the aggregates."""
        slack = 1e-6  # the two clocks are read a moment apart
        later, nested = [], 0
        while self._entries and self._entries[-1].end >= outer.start - slack:
            e = self._entries.pop()
            if (
                e.stage == outer.stage
                and e.thread == outer.thread
                and e.start >= outer.start - slack
            ):
                nested += 1 + e.nested
                n, secs = self._totals[e.stage, e.program]
                self._totals[e.stage, e.program] = Sum(n - 1, secs - e.secs)
            else:
                later.append(e)
        self._entries.extend(reversed(later))
        return outer._replace(nested=nested)

    # -- the three readers ---------------------------------------------

    def snapshot(self) -> dict:
        """Since the process began: the persistent cache's ``hits`` and
        ``misses`` (a miss is a program compiled and written) and the
        outermost seconds of each stage: ``trace_s``, ``lower_s``,
        ``backend_s`` (compile or load), ``retrieval_s``."""
        with self._lock:
            out = {"hits": self.hits, "misses": self.misses}
            for stage in _COMPILE_STAGES:
                out[stage + "_s"] = sum(
                    t.secs for (s, _), t in self._totals.items() if s == stage
                )
        return out

    def entries(
        self, since: Optional[float] = None, until: Optional[float] = None
    ) -> list[Entry]:
        """The entries still kept that ended after ``since`` and not
        after ``until``, oldest first; spans among them."""
        with self._lock:
            kept = list(self._entries)
        return [
            e
            for e in kept
            if (since is None or e.end > since)
            and (until is None or e.end <= until)
        ]

    def by_program(
        self, since: Optional[float] = None, until: Optional[float] = None
    ) -> dict[str, dict[str, Sum]]:
        """``{program: {stage: Sum(n, secs)}}`` without the spans: over
        the process's life when no bound is given (the aggregates), else
        over :meth:`entries` of the interval."""
        out: dict[str, dict[str, Sum]] = {}
        if since is None and until is None:
            with self._lock:
                totals = list(self._totals.items())
        else:
            totals = []
            for e in self.entries(since, until):
                totals.append(((e.stage, e.program), Sum(1, e.secs)))
        for (stage, program), (n, secs) in totals:
            if stage == STAGE_SPAN or not n:
                continue
            was = out.setdefault(program, {}).get(stage, Sum(0, 0.0))
            out[program][stage] = Sum(was.n + n, was.secs + secs)
        return out


_log: Optional[CompileLog] = None


def compile_log() -> Optional[CompileLog]:
    """This process's compile log; ``None`` until
    :func:`install_compile_log` has run (every entry point runs it,
    through :func:`enable_compile_cache`)."""
    return _log


def install_compile_log() -> CompileLog:
    """Install the process's one compile log and return it; a second
    call returns the same log and registers nothing (jax's listeners
    cannot be told apart once registered)."""
    global _log
    if _log is None:
        from jax import monitoring

        _log = CompileLog()
        monitoring.register_event_time_span_listener(_log.on_time_span)
        monitoring.register_event_duration_secs_listener(_log.on_duration)
        monitoring.register_event_listener(_log.on_event)
    return _log


def default_cache_dir() -> str:
    """``$JAX_COMPILATION_CACHE_DIR`` if set, else ``.jax_cache`` at the
    checkout root (the parent of the ``multidisttorch_tpu`` package) —
    one shared location regardless of the caller's cwd."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    pkg = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    return os.path.join(os.path.dirname(pkg), ".jax_cache")


def enable_compile_cache() -> str:
    """Turn JAX's persistent compilation cache on for this process and
    return the directory in effect.

    A directory already configured stands — jax reads
    ``JAX_COMPILATION_CACHE_DIR`` into its config at import, so where
    that is set no directory is set in code. Only when none is
    configured does the cache go to ``<checkout>/.jax_cache``. Every
    compile qualifies: jax's default thresholds (1 s of compile time)
    would skip the VAE's programs, which compile in less.

    It also installs the process's compile log (:func:`compile_log`),
    once: there is no switch, because its callbacks fire only while
    something is traced, lowered or compiled, and a steady step does
    none of that.
    """
    import jax

    install_compile_log()
    if not jax.config.jax_compilation_cache_dir:
        jax.config.update("jax_compilation_cache_dir", default_cache_dir())
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return jax.config.jax_compilation_cache_dir


def submesh_defeats_cache(devices, world_size: int) -> bool:
    """Whether programs over ``devices`` cannot be trusted to the
    persistent cache: more than one TPU chip, fewer than the whole
    world. Measured on a four-chip v5e host (libtpu 0.0.34, PR 21): an
    executable with collectives over chips [2, 3], written to the cache
    by one process and deserialized by the next, dies at its first run
    with ``FAILED_PRECONDITION: The program continuator has halted
    unexpectedly`` — every time, whatever ran before it. The same
    program over [0, 1] or over all four chips, and single-chip programs
    on any chip, deserialize and run. The fault is below jax (it hands
    the deserializer the right devices), so the guard is conservative:
    any multi-chip strict subset."""
    return devices[0].platform == "tpu" and 1 < len(devices) < world_size


def guard_submesh(devices) -> None:
    """Called wherever a trial submesh is made (``TrialMesh``). If the
    submesh defeats the cache (:func:`submesh_defeats_cache`), turn the
    persistent cache off for the rest of this process: jax's switch is
    process-wide and programs compile lazily, so there is no narrower
    place to stand. Programs already compiled are unaffected; later ones
    compile cold and write nothing."""
    import warnings

    import jax
    from jax.experimental.compilation_cache import compilation_cache

    if not jax.config.jax_enable_compilation_cache:
        return
    world = len(jax.devices(devices[0].platform))
    if not submesh_defeats_cache(devices, world):
        return
    jax.config.update("jax_enable_compilation_cache", False)
    # jax decides once per process whether the cache is in use; make it
    # decide again.
    compilation_cache.reset_cache()
    warnings.warn(
        f"persistent compile cache turned off for this process: a "
        f"{len(devices)}-chip submesh of a {world}-chip TPU world was "
        "carved, and cached executables with collectives over such a "
        "submesh fail when deserialized (utils/compile_cache.py)",
        RuntimeWarning,
        stacklevel=4,  # past TrialMesh's __post_init__ and __init__
    )
