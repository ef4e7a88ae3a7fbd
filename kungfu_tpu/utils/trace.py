"""The switch of the flight recorder's ring.

The reference's optional stdtracer (``TRACE_SCOPE``,
``include/kungfu/utils/trace.hpp:1-17``, enabled by
``KUNGFU_ENABLE_TRACE``) is :func:`kungfu_tpu.monitor.timeline.span`
here: one tracing system, whose spans are also annotations on the
profiler's clock.  This module keeps only the environment switch, so
that the launcher can set it without importing the monitor package.
"""

from __future__ import annotations

import os

ENABLE_TRACE = "KF_CONFIG_ENABLE_TRACE"


def trace_enabled() -> bool:
    return os.environ.get(ENABLE_TRACE, "").lower() in ("1", "true", "yes")
