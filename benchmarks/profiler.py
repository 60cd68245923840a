"""The benchmark's own hold on jax.profiler: on for the LAST
`trace_seconds` of the measured window of a --trace 1 run, host Python
tracing off (it slows the host the serving loop shares), the traced
interval marked by a `bench.traced_window` annotation so that the reducer
clips device events to it on the profiler's own clock."""
import os
import time


def annotate(name):
    import jax

    return jax.profiler.TraceAnnotation(name)


class WindowTracer:
    def __init__(self, enabled, out_dir, trace_seconds, window_seconds):
        self.enabled = bool(enabled)
        self.out_dir = out_dir
        self.start_at = max(0.0, window_seconds - trace_seconds)
        self.state = "off"  # off -> on -> done
        self._ann = None
        self.t_on = self.t_off = None
        self.start_cost_s = self.stop_cost_s = None

    def tick(self, elapsed):
        """Called by the runner between units of work."""
        if not self.enabled or self.state != "off" or elapsed < self.start_at:
            return
        import jax

        t = time.monotonic()
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        os.makedirs(self.out_dir, exist_ok=True)
        jax.profiler.start_trace(self.out_dir, profiler_options=opts)
        self._ann = annotate("bench.traced_window")
        self._ann.__enter__()
        self.t_on = time.monotonic()
        self.start_cost_s = self.t_on - t
        self.state = "on"

    def stop(self):
        if self.state != "on":
            return
        import jax

        self.t_off = time.monotonic()
        self._ann.__exit__(None, None, None)
        jax.profiler.stop_trace()
        self.stop_cost_s = time.monotonic() - self.t_off
        self.state = "done"

    def xplane_path(self):
        if self.state != "done":
            return None
        found = []
        for root, _, files in os.walk(self.out_dir):
            found += [os.path.join(root, f) for f in files
                      if f.endswith(".xplane.pb")]
        return max(found, key=os.path.getmtime) if found else None
