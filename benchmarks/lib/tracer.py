"""The benchmark's own use of jax.profiler: annotations around its calls
into each layer, and one traced slice at the end of the window.

With tracing off ``span`` is a no-op context, so the measured path of a
``--trace 0`` run carries no profiler call at all."""

import contextlib
import glob
import os
import shutil

from . import trace_reduce


class Tracer:
    """``slice_s`` seconds of jax.profiler trace, started by the driver when
    that much of its window is left and stopped after the window's end."""

    def __init__(self, out_dir, slice_s):
        self.out_dir = out_dir
        self.slice_s = float(slice_s)
        self.active = False
        self.started = False
        self._slice = None

    def span(self, name):
        if not self.active:
            return contextlib.nullcontext()
        import jax.profiler
        return jax.profiler.TraceAnnotation(name)

    def due(self, seconds_left):
        return not self.started and seconds_left <= self.slice_s

    def start(self):
        import jax.profiler
        shutil.rmtree(self.out_dir, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0      # our annotations, not every frame
        opts.host_tracer_level = 2
        jax.profiler.start_trace(self.out_dir, profiler_options=opts)
        self.active = True
        self._slice = jax.profiler.TraceAnnotation(trace_reduce.SLICE)
        self._slice.__enter__()
        self.started = True

    def stop(self):
        if not self.active:
            return
        import jax.profiler
        self._slice.__exit__(None, None, None)
        self.active = False
        jax.profiler.stop_trace()

    def trace_file(self):
        found = sorted(glob.glob(os.path.join(
            self.out_dir, "plugins", "profile", "*", "*.xplane.pb")))
        return found[-1] if found else None


class NoTracer:
    """``--trace 0``: never due, so never started."""
    active = False

    def span(self, name):
        return contextlib.nullcontext()

    def due(self, seconds_left):
        return False

    def stop(self):
        pass
