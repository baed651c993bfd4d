"""Captures with JAX's profiler, and the benchmark's own spans inside them.

Used only by the traced run.  The annotations are the benchmark's; spans
inside the program are a later PR's."""

from __future__ import annotations

import contextlib
import glob
import os
import shutil

import jax

#: every span the benchmark writes starts with this, so that the
#: reduction finds its own spans among the host's
SPAN_PREFIX = "bench:"


def span(kind: str, label: str):
    return jax.profiler.TraceAnnotation(f"{SPAN_PREFIX}{kind}:{label}")


@contextlib.contextmanager
def capture(directory: str):
    """Trace what runs inside; afterwards the ``.xplane.pb`` files are
    under ``directory``."""
    shutil.rmtree(directory, ignore_errors=True)
    os.makedirs(directory, exist_ok=True)
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0   # no span per Python call: the host
    options.enable_hlo_proto = False  # path is what some cells measure
    jax.profiler.start_trace(directory, profiler_options=options)
    try:
        yield
    finally:
        jax.profiler.stop_trace()


def xplane_files(directory: str) -> list[str]:
    return sorted(glob.glob(os.path.join(directory, "**", "*.xplane.pb"),
                            recursive=True))
