"""What both loops share inside the worker: the device check, the named
parts of set-up, the profiler around the traced stretch, memory and
compile counters, and the per-leaf norms the comparisons use."""

from __future__ import annotations

import contextlib
import os
import time


class Parts:
    """Named stretches of set-up, in seconds, in the order they ran."""

    def __init__(self):
        self.seconds = {}

    @contextlib.contextmanager
    def __call__(self, name: str):
        t0 = time.perf_counter()
        try:
            yield
        finally:
            self.seconds[name] = (self.seconds.get(name, 0.0)
                                  + time.perf_counter() - t0)


def pin_platform(spec: dict) -> None:
    """A direct worker's first touch of JAX (a launched worker's is
    ``kf.init()``): pin the platform and place the compile cache where
    the parent said (``JAX_COMPILATION_CACHE_DIR``)."""
    import jax

    jax.config.update("jax_platforms", "cpu" if spec["rehearse"] else "tpu")
    if not spec["rehearse"]:
        from kungfu_tpu.utils.compile_cache import enable_compile_cache

        enable_compile_cache()


def check_device(spec: dict) -> dict:
    """The device facts of the result line, as JAX reports them -- after
    refusing anything but the platform and the count the cell asks for.
    No chip is a failure, never a CPU run; only ``--rehearse`` takes the
    CPU, and says so."""
    import jax

    devs = jax.devices()
    want = "cpu" if spec["rehearse"] else "tpu"
    if devs[0].platform != want or len(devs) != spec["cell"]["chips"]:
        raise SystemExit(
            f"kfbench: {spec['workload']} wants {spec['cell']['chips']} "
            f"{want} device(s), JAX has {devs}")
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def memory_peak_bytes() -> int:
    """Peak bytes in use on the fullest device (0 where the backend
    reports none, as the CPU does)."""
    import jax

    return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in jax.devices())


class Tracer:
    """The profiler around the last ``trace_seconds`` of the window, when
    ``--trace 1``.  The last and not the first: stopping the profiler
    stalls the calling thread for tens of seconds while the trace is
    written, and a stall inside the window would land on every request
    then waiting.  The trace goes to ``<out_dir>/trace`` for the parent
    to reduce."""

    def __init__(self, spec: dict):
        self.on = bool(spec["trace"])
        self.seconds = spec["trace_seconds"]
        self.dir = os.path.join(spec["out_dir"], "trace")
        self.window = None  # (start, end) by time.perf_counter
        self._t0 = None

    def start_if_due(self, now: float, window_end: float) -> None:
        """Start once ``now`` is within ``trace_seconds`` of the window's
        end (both by ``time.perf_counter``)."""
        if (not self.on or self._t0 is not None
                or now < window_end - self.seconds):
            return
        import jax

        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0  # annotations only: a cheap host side
        opts.host_tracer_level = 1
        jax.profiler.start_trace(self.dir, profiler_options=opts)
        self._t0 = time.perf_counter()

    @property
    def running(self) -> bool:
        return self._t0 is not None and self.window is None

    def stop(self) -> None:
        if not self.running:
            return
        import jax

        t1 = time.perf_counter()
        jax.profiler.stop_trace()
        self.window = (self._t0, t1)

    def facts(self):
        if self.window is None:
            return None
        return {"dir": self.dir, "t0": self.window[0], "t1": self.window[1],
                "window_s": self.window[1] - self.window[0]}


def annotate(name: str):
    import jax

    return jax.profiler.TraceAnnotation(name)


def leaf_norms(tree):
    """L2 norm of every leaf, as one vector in ``tree_leaves`` order."""
    import jax
    import jax.numpy as jnp

    return jnp.stack([jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32))))
                      for x in jax.tree_util.tree_leaves(tree)])


def leaf_gaps(got, want):
    """The gap between two vectors of per-leaf norms, leaf by leaf: the
    gap between the norms, against the reference's norm of that leaf or
    of the median leaf, whichever is larger (some leaves' gradients are
    all but zero)."""
    import numpy as np

    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return np.abs(got - want) / np.maximum(want, float(np.median(want)))
