"""The chip, the compile cache, the clock of set-up, and the profiler."""

from __future__ import annotations

import glob
import os
import shutil
import tempfile

from harness.spec import ROOT


class NoAccelerator(RuntimeError):
    pass


def process_age_s() -> float:
    """Seconds since this process started (Linux ``/proc``), so that
    set-up counts the interpreter's own start and every import."""
    try:
        with open("/proc/self/stat") as f:
            start_ticks = int(f.read().rsplit(")", 1)[1].split()[19])
        with open("/proc/uptime") as f:
            uptime = float(f.read().split()[0])
        return max(uptime - start_ticks / os.sysconf("SC_CLK_TCK"), 0.0)
    except (OSError, ValueError, IndexError):
        return 0.0


def require_tpu(chips: int) -> list:
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoAccelerator(f"no TPU: JAX found {len(devs)} "
                            f"{devs[0].platform} device(s); the benchmark "
                            "never runs on the CPU")
    if len(devs) < chips:
        raise NoAccelerator(f"the cell needs {chips} chips, JAX found "
                            f"{len(devs)}")
    return devs[:chips]


def enable_compile_cache() -> str:
    """JAX's persistent cache at the program's rule:
    ``JAX_COMPILATION_CACHE_DIR`` if set, else ``<checkout>/.jax_cache``
    (a fixed path: the path is part of the cache key)."""
    import jax

    path = (os.environ.get("JAX_COMPILATION_CACHE_DIR")
            or os.path.join(ROOT, ".jax_cache"))
    jax.config.update("jax_compilation_cache_dir", path)
    # No call-site frames in compiled programs' locations: a Pallas
    # kernel body carries the locations of its first trace, so without
    # this the process after the one that measured the micro-step
    # election misses the cache for every micro step (PERF.md).
    jax.config.update("jax_traceback_in_locations_limit", 0)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


class Compiles:
    """Backend compiles as JAX reports them (a persistent-cache hit is
    not a backend compile)."""

    def __init__(self):
        import jax

        self.n = 0

        def on_duration(name, _secs, **_kw):
            if name == "/jax/core/compile/backend_compile_duration":
                self.n += 1

        jax.monitoring.register_event_duration_secs_listener(on_duration)


def device_record(devices) -> dict:
    peak = 0
    for d in devices:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices), "memory_peak_bytes": peak}


def annotate(name: str):
    import jax

    return jax.profiler.TraceAnnotation(name)


class Profiler:
    """A device trace of part of the window, written under TMPDIR and
    removed once reduced."""

    def __init__(self):
        self.dir = None

    def start(self) -> None:
        import jax

        self.dir = tempfile.mkdtemp(prefix="bench_trace_")
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        jax.profiler.start_trace(self.dir, profiler_options=opts)

    def stop(self) -> None:
        import jax

        jax.profiler.stop_trace()

    def reduce(self):
        from harness import trace

        try:
            files = glob.glob(os.path.join(self.dir, "**", "*.xplane.pb"),
                              recursive=True)
            if not files:
                raise ValueError("the profiler wrote no .xplane.pb")
            return trace.reduce_lines(trace.load(files[0]))
        finally:
            shutil.rmtree(self.dir, ignore_errors=True)

