"""What both drivers share: the counter of programs built or loaded, the
profiler's short traced span, and the device's memory reading."""
import os
import shutil
import statistics


class CompileCounter:
    """Counts every executable JAX acquires (its monitoring event fires
    for a compile and for a load from the persistent cache alike): inside
    the measured window there should be none."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax.monitoring

        self.count = 0
        self.seconds = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration_secs, **_kw):
        if event == self.EVENT:
            self.count += 1
            self.seconds += duration_secs


class Tracer:
    """The traced span of a ``--trace 1`` run: a short stretch of the same
    steady work, driven through the window's own loop *before* the window
    opens, so that starting and stopping the profiler (seconds, on this
    host) stalls nothing that the window measures.  The python tracer
    stays off: it floods the host planes and slows the loop it traces."""

    def __init__(self, out_dir, enabled):
        self.dir = os.path.join(out_dir, "trace")
        self.enabled = enabled
        self.traced = False

    def __enter__(self):
        if self.enabled:
            import jax

            shutil.rmtree(self.dir, ignore_errors=True)
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 2
            jax.profiler.start_trace(self.dir, profiler_options=opts)
        return self

    def __exit__(self, *exc):
        if self.enabled:
            import jax

            jax.profiler.stop_trace()
            self.traced = True


def memory_peak_bytes(devices, log=None):
    """The peak on the fullest chip, from the backend's own reading: the
    peak of live arrays (``peak_bytes_in_use``) and, where the backend
    keeps a running program's temporaries in a reservation of its own
    that the first leaves out (the TPU's does: free = limit - in use -
    reserved), the peak of that reservation.  The whole reading goes to
    the log."""
    def peak(s):
        return s.get("peak_bytes_in_use", 0) + s.get("peak_bytes_reserved", 0)

    fullest = max((d.memory_stats() or {} for d in devices), key=peak)
    if log is not None:
        log("memory peak %d = live arrays %d + programs' reservation %d; "
            "memory_stats of the fullest chip: %s" % (
                peak(fullest), fullest.get("peak_bytes_in_use", 0),
                fullest.get("peak_bytes_reserved", 0), fullest))
    return int(peak(fullest))


def percentile(values, q):
    """The q-th percentile (0-100) by the nearest rank above."""
    s = sorted(values)
    if not s:
        return None
    k = min(len(s) - 1, max(0, int(-(-q * len(s) // 100)) - 1))
    return s[k]


def step_stats(stamps):
    """From completion stamps: the longest step, and how many steps took
    over 1.5 x the median."""
    gaps = [b - a for a, b in zip(stamps, stamps[1:])]
    if not gaps:
        return {}
    med = statistics.median(gaps)
    return {"step_ms_max": 1e3 * max(gaps),
            "step_ms_median": 1e3 * med,
            "slow_steps": sum(1 for g in gaps if g > 1.5 * med)}
